"""``reduce/host_spans.py`` and the ``engine.*`` readers that use it, on a
small recorded trace: ``data/serve-open-rehearsal.xplane.pb`` is the
profiler file of one CPU rehearsal of ``serve-open`` (seed 7, 3 s; its
``/host:metadata`` plane's 1.9 MB of HLO text taken out, nothing else),
0.44 s of the engine's own events."""
import os

import pytest

from chipbench import manifest
from chipbench.reduce import host_spans
from chipbench.runners import common

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data',
                        'serve-open-rehearsal.xplane.pb')
#: the metrics this reduction feeds, with what the recording reads
NEW_METRICS = {
    'engine.tokenize_ms_p50': 1.1594,
    'engine.queue_wait_ms_p50': 5.2938,
    'engine.handoff_ms_p50': 1.6637,
    'engine.fetch_ms_p50': 0.7321,
    'engine.index_search_ms_p50': 1.9105,
    'engine.deliver_ms_p50': 0.1007,
    'engine.decode_pool_busy_share': 3.1719,
    'engine.gc_pause_ms_max': 0.0,      # no full collection in 0.44 s
}


def _run(tmp_path, xplane):
    """What ``run.read_layers`` hands a reader, as far as these read it."""
    cell = manifest.load_cell('serve-open')
    os.makedirs(tmp_path / 'trace')
    if xplane is not None:
        os.symlink(xplane, tmp_path / 'trace' / 'vm.xplane.pb')
    ctx = common.Context(
        cell=cell, seed=7, trace=True, rehearsal=True, data_root='',
        run_dir=str(tmp_path), config=cell.config,
        settings=cell.config['settings'], traffic=cell.traffic)
    return {'cell': cell, 'log': ctx.log}


def test_events_keep_their_stats_and_their_thread():
    read = host_spans.read_events(RECORDED)
    by_name = {}
    for event in read['events']:
        by_name.setdefault(event.name, []).append(event)
    assert read['window_s'] == pytest.approx(0.4378, abs=1e-3)
    assert {name: len(found) for name, found in by_name.items()} == {
        'serving/tokenize': 12, 'serving/no_work': 9,
        'serving/coalesce': 12, 'serving/pack': 12, 'serving/h2d': 12,
        'serving/dispatch': 12, 'serving/fetch': 12, 'serving/decode': 13,
        'serving/deliver': 13, 'serving/index_search': 5}
    assert set(by_name['serving/pack'][0].stats) == {
        'batch', 'rows', 'bucket', 'requests', 'tier'}
    assert set(by_name['serving/fetch'][0].stats) == {
        'batch', 'rows', 'handoff_ms'}
    assert set(by_name['serving/deliver'][0].stats) == {
        'batch', 'rows', 'tier', 'queue_wait_ms', 'since_enqueue_ms'}
    assert set(by_name['serving/index_search'][0].stats) == {'rows', 'k'}
    # one dispatcher; the two decode workers are the lines with fetches,
    # and a search runs on the worker that delivers the request
    assert len({e.line for e in by_name['serving/pack']}) == 1
    pool = {e.line for e in by_name['serving/fetch']}
    assert len(pool) == 2
    assert {e.line for e in by_name['serving/index_search']} <= pool
    assert not pool & {e.line for e in by_name['serving/tokenize']}
    for search in by_name['serving/index_search']:
        assert any(d.line == search.line and d.start <= search.start
                   and search.end <= d.end
                   for d in by_name['serving/deliver'])


def test_a_requests_phases_tile_its_time_since_enqueue():
    requests = host_spans.request_phases(
        host_spans.read_events(RECORDED)['events'])
    assert len(requests) == 12      # one batch began before the slice
    for request in requests:
        assert set(host_spans.REQUEST_PHASES) < set(request)
        assert all(request[p] >= 0 for p in host_spans.REQUEST_PHASES)
        # what no phase holds: the stamps between two phases
        hole = request['enqueue_to_end_ms'] - request['sum_ms']
        assert 0 <= hole < 0.2, request
    reduced = host_spans.reduce_file(RECORDED)
    assert {t: v['requests'] for t, v in reduced['tiers'].items()} == {
        'attention': 1, 'topk': 6, 'vectors': 5}
    # a neighbour query's search is inside its deliver
    assert reduced['tiers']['vectors']['deliver'] > \
        reduced['index_search_ms_p50'] > \
        10 * reduced['tiers']['topk']['deliver']


@pytest.mark.parametrize('name', sorted(NEW_METRICS))
def test_each_new_metric_has_an_entry_and_a_reader_of_its_own(name, tmp_path):
    cell = manifest.load_cell('serve-open')
    (entry,) = [m for m in cell.per_layer if m['name'] == name]
    assert (entry['layer'], entry['moves'], entry['source']) == (
        'engine', 'serve_p50_ms', 'program_span')
    assert entry['workloads'] == ['serve-open']
    readers = manifest.layer_readers([entry])
    assert list(readers) == [name]      # not the layer's engine.py
    values = readers[name].read(_run(tmp_path, RECORDED))
    assert values == {name: pytest.approx(NEW_METRICS[name], rel=1e-3)}


@pytest.mark.parametrize('xplane', [
    None,       # the run wrote no trace
    # the committed TPU trace of five train steps (test_layers.py)
    os.path.join(manifest.ROOT, 'profiles', 'java14m_step', 'plugins',
                 'profile', '2026_07_29_13_58_54', 'vm.xplane.pb'),
], ids=['no-trace', 'a-program-without-the-events'])
def test_readers_say_nothing_where_the_program_wrote_no_events(
        xplane, tmp_path):
    """The parent commit's side of this PR's comparison: no event, no
    value, no exception."""
    if xplane is not None:
        assert os.path.isfile(xplane)
    run = _run(tmp_path, xplane)
    assert host_spans.of_run(run) == {}
    cell = manifest.load_cell('serve-open')
    entries = [m for m in cell.per_layer if m['name'] in NEW_METRICS]
    for module in manifest.layer_readers(entries).values():
        assert module.read(run) == {}


def test_pool_share_is_of_the_configured_workers():
    reduced = host_spans.reduce_file(RECORDED)
    assert reduced['pool_lines'] == 2
    two = host_spans.pool_busy_share(reduced, 2)
    assert two == pytest.approx(
        100 * reduced['pool_busy_s'] / (2 * reduced['window_s']))
    assert host_spans.pool_busy_share(reduced, 4) == pytest.approx(two / 2)
    assert host_spans.pool_busy_share({}, 2) is None


@pytest.mark.parametrize('names, longest', [
    (('serving/dispatch',), None),      # a program before the hook
    (('serving/dispatch', 'serving/deliver'), 0.0),
    (('serving/deliver', 'process/gc_pause', 'process/gc_pause'), 90.0),
], ids=['no-hook', 'no-collection', 'two-collections'])
def test_longest_pause_of_the_collector(names, longest):
    stats = {'serving/deliver': {'batch': 1, 'rows': 1, 'tier': 'topk',
                                 'queue_wait_ms': 1.0,
                                 'since_enqueue_ms': 2.0},
             'process/gc_pause': {'generation': 2}}
    events = [host_spans.HostEvent(name, 0.1 * i, 0.1 * i + 0.03 * (i + 1),
                                   1, stats.get(name, {}))
              for i, name in enumerate(names)]
    reduced = host_spans.reduce_events(events, 1.0)
    if longest is None:
        assert reduced['gc_pause_ms_max'] is None
    else:
        assert reduced['gc_pause_ms_max'] == pytest.approx(longest)
