"""``engine.handoff_ms_p50``: median ``handoff_ms`` of the ``serving/fetch``
events: a dispatched batch's wait for a free decode worker.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'handoff_ms_p50')
