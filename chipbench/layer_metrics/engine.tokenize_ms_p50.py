"""``engine.tokenize_ms_p50``: median length of the ``serving/tokenize``
events: a caller's thread turning a request's lines into a plane batch
(interpreter-lock waits included).
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'tokenize_ms_p50')
