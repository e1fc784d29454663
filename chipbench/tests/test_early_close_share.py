"""``layer_metrics/engine.early_close_share.py``: the share of batches the
dispatcher closed because a decode slot was free, from the ``early`` stat
of the ``serving/pack`` events; nothing from a program without the stat."""
import os

import pytest

from chipbench import manifest
from chipbench.reduce.host_spans import HostEvent
from chipbench.tests.test_host_spans import RECORDED, _run

NAME = 'engine.early_close_share'


@pytest.fixture(scope='module')
def reader():
    cell = manifest.load_cell('serve-open')
    (entry,) = [m for m in cell.per_layer if m['name'] == NAME]
    assert (entry['layer'], entry['moves'], entry['source'],
            entry['unit'], entry['workloads']) == (
        'engine', 'serve_p50_ms', 'program_span', '%', ['serve-open'])
    readers = manifest.layer_readers([entry])
    assert list(readers) == [NAME]      # not the layer's engine.py
    return readers[NAME]


def _pack(batch, **stats):
    return HostEvent('serving/pack', 0.001 * batch, 0.001 * batch + 0.0003,
                     3, dict(stats, batch=batch, rows=4, bucket=8,
                             requests=1, tier='topk'))


def test_share_of_the_pack_events_that_carry_the_stat(reader):
    events = [_pack(1, early=1), _pack(2, early=1), _pack(3, early=0),
              _pack(4, early=1),
              _pack(5),     # a batch without the stat is not counted
              # another event's stat of the same name is not a batch
              HostEvent('serving/fetch', 0.01, 0.02, 4,
                        {'batch': 1, 'early': 0})]
    assert reader.early_close_share(events) == pytest.approx(75.0)
    assert reader.early_close_share([_pack(1, early=0)]) == 0.0


def test_nothing_where_no_pack_event_carries_the_stat(reader):
    assert reader.early_close_share([_pack(1), _pack(2)]) is None
    assert reader.early_close_share([]) is None


@pytest.mark.parametrize('xplane', [None, RECORDED],
                         ids=['no-trace', 'a-program-without-the-stat'])
def test_reader_says_nothing_for_the_parent(reader, xplane, tmp_path):
    """The recording predates the stat: its twelve ``serving/pack`` events
    carry none, as the parent commit's do."""
    if xplane is not None:
        assert os.path.isfile(xplane)
    assert reader.read(_run(tmp_path, xplane)) == {}
