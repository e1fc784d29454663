"""Per-request distributed tracing (ISSUE 8; telemetry/tracing.py,
OBSERVABILITY.md "Per-request serving traces").

Three layers:

1. **tracer core** — head sampling vs tail retention, the bounded
   flight-recorder ring with debounced dumps, shed-burst detection, and
   valid JSONL under concurrent writers;
2. **latency_report** — the phase x bucket x tier table, queue-vs-device
   decomposition, span trees, and the Perfetto conversion over synthetic
   spans;
3. **the acceptance drill** — overload (queue bound + injected
   ``slow_dispatch``) plus extractor_crash and a canary rollback:
   every submitted request's full span tree reconstructs from the JSONL
   log, shed/expired/closed requests carry their reason span, per-phase
   durations sum to within tolerance of end-to-end latency,
   latency_report produces the breakdown from that log, and the compile
   counter confirms ZERO post-warmup compiles with tracing enabled.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
_SCRIPTS = os.path.join(REPO, 'scripts')
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

import latency_report  # noqa: E402

from code2vec_tpu.config import Config  # noqa: E402
from code2vec_tpu.resilience import faults  # noqa: E402
from code2vec_tpu.serving.errors import (DeadlineExceeded,  # noqa: E402
                                         EngineClosed, EngineOverloaded)
from code2vec_tpu.telemetry.tracing import (SPAN_CATALOG,  # noqa: E402
                                            Tracer)
from tests.test_train_overfit import make_dataset  # noqa: E402

PREDICT_LINES = [
    'get|a toka0,pA,toka1 toka1,pB,toka2',
    'set|b tokb0,pA,tokb1',
    'run|c tokc0,pC,tokc1 tokc2,pA,tokc0 tokc1,pB,tokc2',
]

#: disjoint per-request phases whose durations must (nearly) tile the
#: root span of a delivered request
PHASE_CHAIN = latency_report.PHASE_CHAIN


@pytest.fixture(autouse=True)
def clear_fault_plan():
    faults.configure('')
    yield
    faults.configure('')


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('tracing'))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8')
    return Code2VecModel(config)


def _wait_until(predicate, timeout=10.0, what='condition'):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if predicate():
            return
        time.sleep(0.001)
    raise AssertionError('timed out waiting for %s' % what)


def _stall_dispatcher(engine, line):
    """Submit a plug request and wait for the dispatcher to POP it into
    the injected slow_dispatch stall (test_serving_resilience idiom)."""
    plug = engine.submit([line], tier='topk')
    _wait_until(lambda: engine.queue_depth.snapshot() == 0,
                what='dispatcher to pop the plug batch')
    return plug


def _read_traces(spans_path):
    return latency_report.group_traces(
        latency_report.load_spans(spans_path))


def _names(entry):
    return [rec['name'] for rec in entry['spans']]


# ------------------------------------------------------------ tracer core
def test_head_sampling_and_tail_retention(tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=0.0, slow_ms=50.0)
    # fast + ok + unsampled: counted, ringed, NOT written
    tracer.begin('serving.request').finish(status='ok')
    assert not os.path.exists(tracer.spans_path)
    # shed: tail-retained regardless of sampling
    trace = tracer.begin('serving.request')
    trace.event('serving.shed', attrs={'reason': 'queue bound'})
    trace.finish(status='shed')
    # slow: tail-retained past TRACING_SLOW_MS
    slow = tracer.begin('serving.request')
    slow.root.t0 -= 0.2  # 200ms ago
    slow.finish(status='ok')
    traces = _read_traces(tracer.spans_path)
    statuses = sorted(e['root']['status'] for e in traces.values())
    assert statuses == ['ok', 'shed']
    assert tracer.stats()['traces_total'] == 3
    assert tracer.stats()['retained_total'] == 2
    # sampled=1.0 writes everything
    always = Tracer(str(tmp_path / 'b'), sample_rate=1.0)
    always.begin('serving.request').finish(status='ok')
    assert len(_read_traces(always.spans_path)) == 1


def test_finish_is_idempotent_and_closes_open_spans(tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    trace = tracer.begin('serving.request')
    open_span = trace.span('serving.queue_wait')
    trace.finish(status='closed', reason='shutdown')
    trace.finish(status='ok')  # second finish: dropped
    trace.span_at('serving.pack', 0.0, 1.0)  # post-finish span: dropped
    traces = _read_traces(tracer.spans_path)
    (entry,) = traces.values()
    assert entry['root']['status'] == 'closed'
    assert entry['root']['attrs']['reason'] == 'shutdown'
    names = _names(entry)
    assert names.count('serving.request') == 1
    assert 'serving.pack' not in names
    # the open queue span was closed AT finish, not truncated
    queue = [r for r in entry['spans']
             if r['name'] == 'serving.queue_wait']
    assert queue and queue[0]['t1'] >= queue[0]['t0']
    assert open_span.span_id > 0


def test_flight_ring_bounded_dump_and_debounce(tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=0.0, flight_traces=4,
                    dump_min_interval_s=3600.0)
    for _ in range(10):
        tracer.begin('serving.request').finish(status='ok')
    path = tracer.dump_flight('close', force=True)
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines[0]['flight'] == 'close' and lines[0]['traces'] == 4
    assert sum(1 for rec in lines[1:] if rec.get('parent') is None) == 4
    # debounced: a second dump of the same event inside the window skips
    assert tracer.dump_flight('close') is None
    assert tracer.dump_flight('close', force=True) is not None
    # memory-only tracers never dump
    assert Tracer(None).dump_flight('close', force=True) is None


def test_shed_burst_triggers_overload_dump(tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=0.0, shed_burst=3,
                    shed_window_s=60.0)
    tracer.begin('serving.request').finish(status='shed')
    for _ in range(2):
        tracer.note_shed()
    assert not os.path.exists(
        os.path.join(str(tmp_path), 'flight_overload.jsonl'))
    tracer.note_shed()  # third shed inside the window: burst
    assert os.path.exists(
        os.path.join(str(tmp_path), 'flight_overload.jsonl'))
    assert tracer.stats()['flight_dumps_total'] == 1


def test_concurrent_trace_writers_produce_valid_jsonl(tmp_path):
    """ISSUE 8 satellite: submitters, the dispatcher, and decode workers
    finish traces concurrently; the span log must never tear."""
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    n_threads, n_traces, n_spans = 8, 20, 6

    def worker(idx):
        for k in range(n_traces):
            trace = tracer.begin('serving.request',
                                 attrs={'tier': 'topk', 'rows': idx})
            for s in range(n_spans):
                trace.span_at('serving.pack', float(k), float(k + 1),
                              attrs={'bucket': s})
            trace.finish(status='ok')

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    with open(tracer.spans_path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    traces = latency_report.group_traces(records)
    assert len(traces) == n_threads * n_traces
    for entry in traces.values():
        assert entry['root'] is not None
        assert len(entry['spans']) == 1 + n_spans
    assert tracer.stats()['traces_total'] == n_threads * n_traces


# --------------------------------------------------------- latency_report
def _synth_records():
    recs = []

    def span(trace, sid, parent, name, t0, t1, status=None, attrs=None):
        rec = {'trace': trace, 'span': sid, 'parent': parent,
               'name': name, 't0': t0, 't1': t1,
               'dur_ms': (t1 - t0) * 1e3}
        if attrs:
            rec['attrs'] = attrs
        if status:
            rec['status'] = status
        recs.append(rec)

    span('t1', 0, None, 'serving.request', 0.0, 0.1, status='ok',
         attrs={'tier': 'topk', 'rows': 2})
    span('t1', 1, 0, 'serving.queue_wait', 0.0, 0.04)
    span('t1', 2, 0, 'serving.pack', 0.04, 0.05,
         attrs={'bucket': 8, 'tier': 'topk'})
    span('t1', 3, 0, 'serving.device_execute', 0.05, 0.09)
    span('t1', 4, 3, 'serving.fetch', 0.06, 0.09)
    span('t2', 0, None, 'serving.request', 0.0, 0.01, status='shed',
         attrs={'tier': 'full', 'reason': 'queue bound'})
    span('t2', 1, 0, 'serving.shed', 0.01, 0.01)
    return recs


def test_latency_report_tables_and_decomposition():
    traces = latency_report.group_traces(_synth_records())
    rows = latency_report.phase_rows(traces)
    # replica '-' = single-engine traffic (a mesh stamps its replica id
    # on the pack span, scripts/latency_report.py per-replica columns)
    assert rows[('serving.request', 'topk', '8', '-')] == [100.0]
    # shed trace never dispatched: bucket '-'
    assert rows[('serving.shed', 'full', '-', '-')] == [0.0]
    decomp = latency_report.decomposition(traces)
    assert decomp['end_to_end'] == [100.0]
    assert decomp['queue_wait'] == [pytest.approx(40.0)]
    assert decomp['device'] == [pytest.approx(40.0)]
    assert latency_report.status_counts(traces) == {'ok': 1, 'shed': 1}
    # nearest-rank percentiles
    assert latency_report.percentile([1.0, 2.0, 10.0], 0.5) == 2.0
    assert latency_report.percentile([], 0.99) == 0.0


def test_latency_report_tree_and_perfetto(tmp_path):
    traces = latency_report.group_traces(_synth_records())
    (t1_lines,) = [latency_report.format_tree(entry)
                   for tid, entry in traces.items() if tid == 't1']
    assert 'serving.request' in t1_lines[0]
    # fetch nests two deep (request -> device_execute -> fetch)
    (fetch_line,) = [line for line in t1_lines if 'serving.fetch' in line]
    assert fetch_line.startswith('  ' * 3)
    events = latency_report.to_perfetto(traces)
    assert len(events) == 7
    assert all(e['ph'] == 'X' and e['ts'] >= 0 and e['dur'] >= 0
               for e in events)
    lanes = {e['tid'] for e in events}
    assert len(lanes) == 2  # one lane per trace


# --------------------------------------------------- engine span lifecycle
def test_span_tree_complete_with_oversize_split_and_join(model, tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    lines = PREDICT_LINES * 7  # 21 rows > bucket 8: splits into 3 chunks
    with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                              tracer=tracer) as engine:
        single = engine.predict(PREDICT_LINES[:1], tier='topk',
                                timeout=60)
        assert single[0].topk_predicted_words
        results = engine.predict(lines, tier='topk', timeout=120)
        assert len(results) == len(lines)
    traces = _read_traces(tracer.spans_path)
    assert len(traces) == 2
    by_rows = {e['root']['attrs']['rows']: e for e in traces.values()}
    # the single request carries the full disjoint phase chain
    names = _names(by_rows[1])
    for phase in PHASE_CHAIN:
        if phase == 'serving.stall':
            continue  # drills only
        assert phase in names, (phase, names)
    # the oversize request: 3 chunk spans, phases nested under them,
    # one join, root finished ok
    oversize = by_rows[21]
    assert oversize['root']['status'] == 'ok'
    chunks = [r for r in oversize['spans'] if r['name'] == 'serving.chunk']
    assert [c['attrs']['rows'] for c in chunks] == [8, 8, 5]
    assert sum(1 for r in oversize['spans']
               if r['name'] == 'serving.join') == 1
    chunk_ids = {c['span'] for c in chunks}
    packs = [r for r in oversize['spans'] if r['name'] == 'serving.pack']
    assert len(packs) == 3
    assert all(p['parent'] in chunk_ids for p in packs)
    # chunk spans were closed at deliver, not left open
    assert all(c['t1'] > c['t0'] for c in chunks)


def test_phase_durations_sum_to_end_to_end(model, tmp_path):
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                              tracer=tracer) as engine:
        futures = [engine.submit([line], tier='topk')
                   for line in PREDICT_LINES * 3]
        for future in futures:
            future.result(timeout=60)
    traces = _read_traces(tracer.spans_path)
    assert len(traces) == 9
    for entry in traces.values():
        total = float(entry['root']['dur_ms'])
        phase_sum = sum(float(r['dur_ms']) for r in entry['spans']
                        if r['name'] in PHASE_CHAIN)
        # disjoint phases tile the root up to scheduler gaps (handoffs
        # between submitter/dispatcher/decode threads): they must cover
        # most of it and can overshoot only by clock-read epsilon
        assert phase_sum <= total * 1.05 + 2.0, (phase_sum, total)
        assert phase_sum >= total * 0.5, \
            'phases cover %.2f of %.2fms only: %r' % (
                phase_sum, total,
                [(r['name'], r['dur_ms']) for r in entry['spans']])


def test_canary_shadow_span_and_rollback_flight_dump(model, tmp_path):
    import jax
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    broken = jax.tree_util.tree_map(lambda leaf: -leaf, model.params)
    jax.block_until_ready(broken)
    with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                              tracer=tracer) as engine:
        handle = engine.load_params(broken, canary_batches=1,
                                    min_agreement=0.9)
        engine.predict(PREDICT_LINES, tier='topk', timeout=60)
        report = handle.result(timeout=60)
    assert report['swapped'] is False
    traces = _read_traces(tracer.spans_path)
    shadows = [e for e in traces.values()
               if e['root']['name'] == 'serving.canary_shadow']
    assert len(shadows) == 1
    attrs = shadows[0]['root']['attrs']
    assert attrs['rows'] == 3 and 'agree_rows' in attrs
    assert os.path.exists(
        os.path.join(str(tmp_path), 'flight_rollover_rollback.jsonl'))


def test_extractor_pool_spans_and_breaker_flight_dump(tmp_path):
    from code2vec_tpu.serving.extractor_bridge import ExtractorPool
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    config = Config(MAX_CONTEXTS=6, EXTRACTOR_RETRIES=1,
                    EXTRACTOR_BACKOFF_SECS=0.0,
                    EXTRACTOR_BREAKER_THRESHOLD=2,
                    EXTRACTOR_BREAKER_COOLDOWN_SECS=60.0)
    faults.configure('extractor_crash@call=0..63')
    with ExtractorPool(config,
                       extractor_command=[sys.executable, '-c', 'pass'],
                       tracer=tracer) as pool:
        from code2vec_tpu.serving.errors import (ExtractorCrash,
                                                 ExtractorUnavailable)
        for _ in range(2):  # threshold crashes (each retried once)
            with pytest.raises(ExtractorCrash):
                pool.extract_paths(str(tmp_path / 'T.java'), timeout=60)
        assert pool.state() == 'open'
        with pytest.raises(ExtractorUnavailable):
            pool.extract_paths(str(tmp_path / 'T.java'), timeout=60)
    traces = _read_traces(tracer.spans_path)
    calls = [e for e in traces.values()
             if e['root']['name'] == 'extractor.call']
    statuses = sorted(e['root']['status'] for e in calls)
    assert statuses == ['crash', 'crash', 'unavailable']
    crash_attrs = [e['root']['attrs'] for e in calls
                   if e['root']['status'] == 'crash']
    # attempt count rides the span: 1 original + 1 retry
    assert all(a['attempts'] == 2 for a in crash_attrs)
    assert all(a['breaker'] in ('closed', 'half-open', 'open')
               for a in crash_attrs)
    assert os.path.exists(
        os.path.join(str(tmp_path), 'flight_breaker_open.jsonl'))


# ------------------------------------------------------- acceptance drill
def test_overload_drill_reconstructs_every_request(model, tmp_path):
    """ISSUE 8 acceptance: overload + slow_dispatch, then a fail-fast
    close with queued work — every submitted request's span tree
    reconstructs from the JSONL log with its terminal reason, the
    flight recorder dumps on the shed burst AND on close, latency_report
    produces the phase x bucket x tier breakdown from that log, and the
    compile counter stays flat post-warmup with tracing enabled."""
    from code2vec_tpu.telemetry import core
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener
    line = PREDICT_LINES[0]
    tracer = Tracer(str(tmp_path), sample_rate=1.0, shed_burst=3,
                    shed_window_s=30.0)
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  queue_bound=8, tracer=tracer)
    core.reset()
    core.enable()
    submitted = 0
    try:
        assert install_compile_listener()
        compiles = core.registry().counter('jit/compiles_total')
        engine.predict([line], tier='topk', timeout=60)  # end-to-end warm
        submitted += 1
        warm_compiles = compiles.value

        faults.configure('slow_dispatch@req=0..63')
        plug = _stall_dispatcher(engine, line)
        submitted += 1
        # deadlined requests expire behind the >=250ms stall; the
        # deadline sits above any plausible drain estimate (seeded from
        # the warm request's sojourn) but a loaded host can still push
        # the estimate over it — those shed at admission instead, and
        # the tallies below absorb either path
        doomed, early_shed = [], 0
        for _ in range(4):
            submitted += 1
            try:
                doomed.append(engine.submit([line], tier='topk',
                                            deadline_ms=150.0))
            except EngineOverloaded:
                early_shed += 1
        # open-loop burst: the queued doomed requests occupy part of the
        # bound, the rest fill it, the overflow sheds; total sheds are 6
        # either way (>= the burst threshold of 3, dumping the recorder)
        admitted, shed = [], 0
        for _ in range(10):
            submitted += 1
            try:
                admitted.append(engine.submit([line], tier='topk'))
            except EngineOverloaded:
                shed += 1
        assert len(admitted) == 8 - len(doomed)
        assert shed == 10 - len(admitted)
        for future in doomed:
            assert isinstance(future.exception(timeout=60),
                              DeadlineExceeded)
        for future in admitted + [plug]:
            future.result(timeout=60)
        # park two more behind a fresh stall, then fail-fast close: the
        # queued traces must still get their terminal serving.closed span
        plug2 = _stall_dispatcher(engine, line)
        submitted += 1
        queued = [engine.submit([line], tier='topk') for _ in range(2)]
        submitted += 2
        postwarm_compiles = compiles.value - warm_compiles
    finally:
        faults.configure('')
        engine.close()
        # an INJECTED tracer is the injector's to close (a mesh shares
        # one across replicas — a retiring replica must not end the
        # fleet's flight recorder); this test owns it, so the close
        # dump happens here
        tracer.close()
        core.disable()
        core.reset()
    plug2.result(timeout=60)  # in-flight batch still delivered
    for future in queued:
        assert isinstance(future.exception(timeout=10), EngineClosed)
    assert postwarm_compiles == 0, (
        '%d XLA compiles during the traced drill' % postwarm_compiles)

    # ---- every submitted request reconstructs, with its reason
    traces = _read_traces(os.path.join(str(tmp_path), 'spans.jsonl'))
    requests = {tid: e for tid, e in traces.items()
                if e['root']['name'] == 'serving.request'}
    assert len(requests) == submitted
    statuses = {}
    for entry in requests.values():
        statuses.setdefault(entry['root']['status'],
                            []).append(entry)
    # warm + 2 plugs + the burst admits
    assert len(statuses.get('ok', ())) == 3 + len(admitted)
    assert len(statuses.get('shed', ())) == early_shed + shed == 6
    assert len(statuses.get('expired', ())) == len(doomed)
    assert len(statuses.get('closed', ())) == 2
    for entry in statuses['shed']:
        (reason,) = [r for r in entry['spans']
                     if r['name'] == 'serving.shed']
        assert 'shed at admission' in reason['attrs']['reason']
    for entry in statuses.get('expired', ()):
        names = _names(entry)
        assert 'serving.expired' in names
        assert 'serving.queue_wait' in names  # admitted, then expired
        assert 'serving.pack' not in names    # never dispatched
    for entry in statuses['closed']:
        (reason,) = [r for r in entry['spans']
                     if r['name'] == 'serving.closed']
        assert 'close(drain=True)' in reason['attrs']['reason']
    # delivered requests: full chain, stall span included, durations
    # sum to within tolerance of the recorded end-to-end latency
    stalled = 0
    for entry in statuses['ok']:
        names = _names(entry)
        for phase in ('serving.queue_wait', 'serving.pack',
                      'serving.device_execute', 'serving.decode',
                      'serving.deliver'):
            assert phase in names, (phase, names)
        stalled += int('serving.stall' in names)
        total = float(entry['root']['dur_ms'])
        phase_sum = sum(float(r['dur_ms']) for r in entry['spans']
                        if r['name'] in PHASE_CHAIN)
        assert phase_sum <= total * 1.05 + 2.0
        assert phase_sum >= total * 0.5, (phase_sum, total)
    assert stalled >= 5  # the drill's stalls are visible in the trees

    # ---- flight recorder: shed burst + close
    assert os.path.exists(
        os.path.join(str(tmp_path), 'flight_overload.jsonl'))
    close_dump = os.path.join(str(tmp_path), 'flight_close.jsonl')
    assert os.path.exists(close_dump)
    dumped = latency_report.load_spans(close_dump)
    assert {r['name'] for r in dumped} >= {'serving.request',
                                           'serving.shed'}

    # ---- latency_report produces the breakdown + perfetto conversion
    perfetto_path = str(tmp_path / 'serving_trace.json')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts',
                                      'latency_report.py'),
         '--spans', os.path.join(str(tmp_path), 'spans.jsonl'),
         '--json', '--perfetto', perfetto_path],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    phase_rows = [r for r in rows if r['measure'] == 'phase_latency_ms']
    assert any(r['phase'] == 'serving.queue_wait' and r['tier'] == 'topk'
               and r['bucket'] == '8' for r in phase_rows)
    assert any(r['phase'] == 'serving.shed' and r['bucket'] == '-'
               for r in phase_rows)
    assert all(r['p50'] <= r['p99'] for r in phase_rows)
    decomp = [r for r in rows
              if r['measure'] == 'latency_decomposition_ms']
    assert {r['part'] for r in decomp} >= {'end_to_end', 'queue_wait',
                                           'device'}
    with open(perfetto_path) as f:
        perfetto = json.load(f)
    assert perfetto['traceEvents'], 'empty perfetto conversion'


# ------------------------------------------- phases as profiler events
#: every phase site of the engine: profiler event -> the stats it carries
PROFILER_SITES = {
    'serving/tokenize': {'rows', 'native'},
    'serving/no_work': set(),
    'serving/coalesce': set(),
    'serving/pack': {'batch', 'rows', 'bucket', 'requests', 'tier',
                     'early'},
    'serving/h2d': {'batch'},
    'serving/dispatch': {'batch'},
    'serving/fetch': {'batch', 'rows', 'handoff_ms'},
    'serving/decode': {'batch'},
    'serving/deliver': {'batch', 'rows', 'tier', 'queue_wait_ms',
                        'since_enqueue_ms'},
    'serving/index_search': {'rows', 'k'},
    'process/gc_pause': {'generation'},
}


def _profiler_events(trace_dir):
    """{event name: [(line, start_ns, end_ns, stats)]} of the named host
    events (Python frames aside) in the newest xplane under
    ``trace_dir``; ``line`` tells threads apart."""
    import glob
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                         recursive=True), key=os.path.getmtime)
    events, line_no = {}, 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            line_no += 1
            for event in line.events:
                if '/' in event.name and not event.name.startswith('$'):
                    events.setdefault(event.name, []).append(
                        (line_no, event.start_ns,
                         event.start_ns + event.duration_ns,
                         dict(event.stats)))
    return events


def _small_index(model):
    import numpy as np
    from code2vec_tpu.index.exact import ExactIndex
    rows = np.random.default_rng(3).standard_normal(
        (32, model.config.CODE_VECTOR_SIZE)).astype(np.float32)
    return ExactIndex(rows, metric='dot', query_buckets=(1, 8))


@pytest.fixture(scope='module')
def profiled(model, tmp_path_factory):
    """One engine driven under a live ``jax.profiler`` session: a topk
    request, a neighbour query from lines and one from vectors, a full
    collection. Gives the profiler's events, the span log, the results."""
    import gc
    import jax
    import numpy as np
    out = tmp_path_factory.mktemp('profiled')
    tracer = Tracer(str(out / 'spans'), sample_rate=1.0)
    index = _small_index(model)
    # one request at a time into an idle engine: a decode slot is always
    # free, so every batch closes early and the delay is never waited
    engine = model.serving_engine(tiers=('topk', 'vectors'),
                                  max_delay_ms=10_000.0, tracer=tracer)
    engine.attach_index(index)
    try:
        engine.predict(PREDICT_LINES[:1], timeout=120)   # warm, untraced
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(out / 'trace'),
                                 profiler_options=options)
        try:
            topk = engine.predict(PREDICT_LINES, tier='topk', timeout=120)
            from_lines = engine.predict_neighbors(PREDICT_LINES[:2], k=3,
                                                  timeout=120)
            vectors = np.stack([r.code_vector for r in engine.predict(
                PREDICT_LINES[:2], tier='vectors', timeout=120)])
            from_vectors = engine.predict_neighbors(vectors, k=3,
                                                    timeout=120)
            time.sleep(0.02)    # the dispatcher back in its idle wait
            gc.collect(0)       # a young collection: no event
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
    return {'events': _profiler_events(str(out / 'trace')),
            'traces': _read_traces(tracer.spans_path),
            'topk': topk, 'from_lines': from_lines,
            'from_vectors': from_vectors}


@pytest.mark.parametrize('name', sorted(PROFILER_SITES))
def test_every_phase_site_emits_its_profiler_event(profiled, name):
    found = profiled['events'].get(name)
    assert found, 'no %s event in the capture: %s' % (
        name, sorted(profiled['events']))
    for _line, start, end, stats in found:
        assert end >= start
        assert set(stats) == PROFILER_SITES[name], (name, stats)
    # the name is the catalog entry's, '.' -> '/'
    assert name.replace('/', '.') in SPAN_CATALOG


def test_profiler_events_sit_on_the_thread_that_does_the_work(profiled):
    events = profiled['events']
    lines = {name: {e[0] for e in found} for name, found in events.items()}
    dispatcher = lines['serving/pack']
    assert len(dispatcher) == 1
    for name in ('serving/no_work', 'serving/coalesce', 'serving/h2d',
                 'serving/dispatch'):
        assert lines[name] == dispatcher, name
    workers = lines['serving/fetch']
    assert not workers & dispatcher
    assert lines['serving/decode'] <= workers
    assert lines['serving/deliver'] <= workers
    assert not lines['serving/tokenize'] & (dispatcher | workers)
    # the module's model reads with READER_USE_NATIVE off: the fallback
    assert {int(t[3]['native']) for t in events['serving/tokenize']} == {0}
    # every batch found a free decode slot (the fixture's delay is 10 s)
    assert {int(p[3]['early']) for p in events['serving/pack']} == {1}
    # a neighbour query's search runs inside its deliver (from lines), or
    # as a pool task of its own (from vectors): once each
    searches = events['serving/index_search']
    assert len(searches) == 2
    nested = [s for s in searches
              if any(d[0] == s[0] and d[1] <= s[1] and s[2] <= d[2]
                     for d in events['serving/deliver'])]
    assert len(nested) == 1
    assert {s[3]['rows'] for s in searches} == {2}
    assert {s[3]['k'] for s in searches} == {3}
    # the cross-thread waits travel as stats of the consuming thread's event
    for _line, _start, _end, stats in events['serving/deliver']:
        assert 0 <= stats['queue_wait_ms'] <= stats['since_enqueue_ms']
    assert all(e[3]['handoff_ms'] >= 0 for e in events['serving/fetch'])
    # only the full collection is an event
    assert [e[3]['generation'] for e in events['process/gc_pause']] == [2]


def test_results_and_span_log_without_a_profiler_session(model, tmp_path,
                                                         profiled):
    """No session: same results as under one, and a delivered request's
    spans are the old chain plus ``serving.handoff``."""
    import numpy as np
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    with model.serving_engine(tiers=('topk', 'vectors'), max_delay_ms=2.0,
                              tracer=tracer) as engine:
        engine.attach_index(_small_index(model))
        topk = engine.predict(PREDICT_LINES, tier='topk', timeout=120)
        from_lines = engine.predict_neighbors(PREDICT_LINES[:2], k=3,
                                              timeout=120)
    for got, want in zip(topk, profiled['topk']):
        assert got.topk_predicted_words == want.topk_predicted_words
        np.testing.assert_array_equal(got.topk_predicted_words_scores,
                                      want.topk_predicted_words_scores)
    for got, want in zip(from_lines, profiled['from_lines']):
        np.testing.assert_array_equal(got.indices, want.indices)
        np.testing.assert_array_equal(got.scores, want.scores)
    requests = [e for e in _read_traces(tracer.spans_path).values()
                if e['root']['name'] == 'serving.request']
    assert len(requests) == 2
    for entry in requests:
        assert sorted(_names(entry)) == sorted([
            'serving.request', 'serving.admission', 'serving.tokenize',
            'serving.queue_wait', 'serving.coalesce', 'serving.pack',
            'serving.h2d', 'serving.dispatch', 'serving.handoff',
            'serving.device_execute', 'serving.fetch', 'serving.decode',
            'serving.deliver'])


def _holes_ms(entry):
    """Gaps between consecutive phases of a delivered request's root
    span, in ms (PHASE_CHAIN order; the stall is a drill's)."""
    spans = {r['name']: r for r in entry['spans']}
    chain = [spans[name] for name in PHASE_CHAIN
             if name != 'serving.stall']
    edges = [entry['root']['t0']]
    for span in chain:
        edges += [span['t0'], span['t1']]
    edges.append(entry['root']['t1'])
    return [1e3 * (edges[i + 1] - edges[i])
            for i in range(0, len(edges), 2)]


def test_phases_tile_the_root_span(model, tmp_path):
    """admission, tokenize, queue_wait, pack, h2d, dispatch, handoff,
    device_execute, decode, deliver: no hole over 0.2 ms in any delivered
    request. A hole is a few stamps wide unless the thread is preempted
    inside one, so one of three rounds has to be clean."""
    worst = []
    for attempt in range(3):
        tracer = Tracer(str(tmp_path / str(attempt)), sample_rate=1.0)
        with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  tracer=tracer) as engine:
            for line in PREDICT_LINES * 2:
                engine.predict([line], tier='topk', timeout=60)
        traces = _read_traces(tracer.spans_path)
        assert len(traces) == 6
        holes = [hole for entry in traces.values()
                 for hole in _holes_ms(entry)]
        # phases never overlap (a shared stamp ends one and starts the next)
        assert min(holes) > -1e-6, holes
        worst.append(max(holes))
        if worst[-1] <= 0.2:
            return
    raise AssertionError('holes over 0.2 ms in all three rounds: %r'
                         % worst)


def test_handoff_is_cut_out_of_device_execute(profiled):
    """``serving.handoff`` + what is left of ``serving.device_execute`` is
    the old span: dispatch return to fetch completion."""
    requests = [e for e in profiled['traces'].values()
                if e['root']['name'] == 'serving.request']
    assert len(requests) == 4
    for entry in requests:
        spans = {r['name']: r for r in entry['spans']}
        handoff, device = spans['serving.handoff'], \
            spans['serving.device_execute']
        fetch = spans['serving.fetch']
        assert handoff['t0'] == spans['serving.dispatch']['t1']
        assert handoff['t1'] == device['t0'] == fetch['t0']
        assert device['t1'] == fetch['t1'] == spans['serving.decode']['t0']
        assert fetch['parent'] == device['span']
        assert handoff['dur_ms'] >= 0


@pytest.mark.parametrize('branch', ['from_lines', 'from_vectors'])
def test_index_search_span_once_per_neighbour_request(model, tmp_path,
                                                      branch):
    import numpy as np
    tracer = Tracer(str(tmp_path), sample_rate=1.0)
    with model.serving_engine(tiers=('vectors',), max_delay_ms=0.0,
                              tracer=tracer) as engine:
        engine.attach_index(_small_index(model))
        query = PREDICT_LINES[:2] if branch == 'from_lines' else np.ones(
            (2, model.config.CODE_VECTOR_SIZE), np.float32)
        for _ in range(3):
            assert len(engine.predict_neighbors(query, k=3,
                                                timeout=120)) == 2
    searches = [e for e in _read_traces(tracer.spans_path).values()
                if e['root']['name'] == 'serving.index_search']
    assert len(searches) == 3
    for entry in searches:
        assert entry['root']['attrs'] == {'rows': 2, 'k': 3}
        assert entry['root']['t1'] > entry['root']['t0']


def test_gc_hook_lives_as_long_as_the_engine(model):
    import gc
    before = list(gc.callbacks)
    engine = model.serving_engine(tiers=('topk',))
    try:
        (hook,) = [c for c in gc.callbacks if c not in before]
        gc.collect()    # no profiler session: a TraceMe check, no error
    finally:
        engine.close()
    assert hook not in gc.callbacks
    assert gc.callbacks == before
    engine.close()      # idempotent
