"""The trace reducer: a hand-made trace with known answers, and the trace
of five train steps committed under profiles/java14m_step."""
import os

import numpy as np
import pytest

from chipbench.reduce import trace
from chipbench.reduce.trace import Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_three_events_with_a_known_idle_share():
    # a window of 10 ms: two operations of 2 ms and 3 ms, a gap of 4 ms
    # between them that a named host span covers, and 1 ms idle at the end,
    # of which a named span covers too little to own it
    planes = {
        '/device:TPU:0': {
            'XLA Ops': [
                Event('%fusion.1 = f32[8,128]{1,0} fusion(f32[8] %p)', 0.000,
                      0.002),
                Event('%all-reduce.2 = f32[8]{0} all-reduce(f32[8] %q)',
                      0.006, 0.009)],
            'XLA Modules': [Event('jit_step(123)', 0.000, 0.002),
                            Event('jit_step(123)', 0.006, 0.009)]},
        '/host:CPU': {
            'python': [Event('host/batch_wait', 0.0015, 0.0062),
                       Event('host/sync', 0.0098, 0.010),
                       Event('$loop.py:1 run', 0.0, 0.012)]},
    }
    reduced = trace.reduce_planes(planes)
    assert reduced['window_s'] == pytest.approx(0.010)
    assert reduced['busy_s'] == pytest.approx(0.005)
    device = reduced['devices'][0]
    assert device['idle_share'] == pytest.approx(0.5)
    assert device['collective_s'] == pytest.approx(0.003)
    # nothing else ran during the all-reduce: all of it is exposed
    assert device['collective_exposed_s'] == pytest.approx(0.003)
    assert device['modules'] == {'jit_step': {'count': 2,
                                              'seconds': pytest.approx(0.005)}}
    assert reduced['device_ops'][0][0] == 'all-reduce.2 all-reduce f32[8]'
    assert reduced['device_ops'][1] == ['fusion.1 fusion f32[8,128]',
                                        pytest.approx(0.002)]
    gaps = dict(reduced['idle_gaps'])
    assert gaps['host/batch_wait'] == pytest.approx(0.004)
    # the last millisecond falls to the Python frame that covers all of it
    assert gaps['$loop.py:1 run'] == pytest.approx(0.001)
    assert trace.top_module(reduced) == ('jit_step', 2, pytest.approx(0.005))


def test_a_hidden_collective_is_not_exposed():
    planes = {'/device:TPU:0': {'XLA Ops': [
        Event('%all-reduce-start.1 = f32[4] all-reduce-start(f32[4] %g)',
              0.0, 0.004),
        Event('%fusion.7 = f32[4] fusion(f32[4] %x)', 0.001, 0.003)]}}
    device = trace.reduce_planes(planes)['devices'][0]
    assert device['collective_s'] == pytest.approx(0.004)
    assert device['collective_exposed_s'] == pytest.approx(0.002)


def test_no_device_operation_reduces_to_nothing():
    assert trace.reduce_planes({'/host:CPU': {
        'python': [Event('host/sync', 0.0, 1.0)]}}) == {}
    assert trace.reduce_planes({}) == {}


def test_interval_arithmetic():
    spans = trace.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert spans.tolist() == [[0, 3], [5, 6]]
    assert trace.length(spans) == 4
    left = trace.subtract(spans, np.asarray([[2.5, 5.5]]))
    assert left.tolist() == [[0, 2.5], [5.5, 6]]


def test_the_committed_trace_of_five_train_steps():
    """profiles/java14m_step: five runs of jit_train_step at 45.9 ms each
    (PERF.md section 5 has 45.94), read without TensorFlow."""
    reduced = trace.reduce_trace(os.path.join(ROOT, 'profiles',
                                              'java14m_step'))
    name, runs, seconds = trace.top_module(reduced)
    assert (name, runs) == ('jit_train_step', 5)
    assert 1e3 * seconds / runs == pytest.approx(45.95, abs=0.05)
    device = reduced['devices'][0]
    assert device['busy_s'] == pytest.approx(0.2297, abs=1e-3)
    # the window ends with the fetch that waits for the last step, not with
    # the seconds stop_trace takes to collect the trace
    assert reduced['window_s'] < 0.3
    assert 0.1 < device['idle_share'] < 0.25
    assert device['collective_s'] == 0.0
    assert len(reduced['device_ops']) == 10
    # the Adam walk over the token table leads, by the trace's own name
    assert reduced['device_ops'][0][0].startswith('fusion.10 fusion (f32[13')
