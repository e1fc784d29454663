"""``engine.deliver_ms_p50``: median length of the ``serving/deliver`` events:
resolving one request's future, a neighbour query's index search inside it.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'deliver_ms_p50')
