"""``engine.fetch_ms_p50``: median length of the ``serving/fetch`` events: a
decode worker blocked on the device's results.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'fetch_ms_p50')
