"""``engine.gc_pause_ms_max``: the longest ``process/gc_pause`` event of the
slice: a generation-2 collection stops every thread of the process. 0 where
the engine's events are there and the slice held no such collection; nothing
where the program has no hook for them.
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans


def read(run):
    return host_spans.read_metric(run, 'gc_pause_ms_max')
