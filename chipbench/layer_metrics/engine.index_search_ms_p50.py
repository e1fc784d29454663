"""``engine.index_search_ms_p50``: median length of the
``serving/index_search`` events: a neighbour query's search, on the decode
worker that delivers it.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'index_search_ms_p50')
