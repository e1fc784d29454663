"""``engine.queue_wait_ms_p50``: median ``queue_wait_ms`` of the
``serving/deliver`` events: a request's enqueue to the dispatcher's pop, the
coalescing delay inside it.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'queue_wait_ms_p50')
