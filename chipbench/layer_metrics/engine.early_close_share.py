"""``engine.early_close_share``: percent of the slice's batches that the
dispatcher closed before ``SERVING_MAX_DELAY_MS`` because a decode slot was
free: the ``early`` stat (1, else 0: deadline or full bucket) of the
``serving/pack`` events. Nothing where no ``serving/pack`` event carries
the stat (a program from before the engine closed batches early).
From the events the engine wrote into the run's profiler trace
(``reduce/host_spans.py``)."""
from chipbench.reduce import host_spans
from chipbench.reduce import trace as trace_lib


def early_close_share(events):
    """Percent of the ``serving/pack`` events with an ``early`` stat whose
    ``early`` is 1; None where none has the stat."""
    flags = [int(e.stats['early']) for e in events
             if e.name == 'serving/pack' and 'early' in e.stats]
    return 100.0 * sum(flags) / len(flags) if flags else None


def read(run):
    path = trace_lib.find_xplane(run['log'].__self__.trace_dir)
    if path is None:
        return {}
    share = early_close_share(
        host_spans.read_events(path, prefixes=('serving/pack',))['events'])
    return {} if share is None else {'engine.early_close_share': share}
