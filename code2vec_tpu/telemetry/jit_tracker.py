"""jit-compilation accounting.

Two complementary signals:

- ``install_compile_listener()`` hooks ``jax.monitoring`` (the duration
  stream every backend compile reports,
  ``/jax/core/compile/backend_compile_duration``) into the telemetry
  registry — compile COUNT and TIME, including compiles the trainer
  never sees (eval twins, checkpoint init, collective warmup).
- ``CapacityTracker`` watches the packed-wire context capacity feeding
  the step: every NEW capacity is one more specialization of the whole
  train-step program (data/packed.py buckets capacities precisely to
  bound these), so each first sight is counted AND logged with its
  bucket — the "silent jit re-specialization" PR 1 made possible and
  this PR makes visible.

The monitoring listener is installed once per process and kept, but it
forwards through ``core.enabled()``, so with telemetry off its cost is
one bool read per compile (compiles are seconds-scale; this is nothing).

A persistent-cache HIT still fires the event (jax times
``compile_or_get_cached``, and a hit's duration is its retrieval time),
so ``jit/compiles_total`` and the goodput ``compile`` bucket count
programs that were built OR loaded for this process — "zero after
warm-up" means neither happened.
"""
from __future__ import annotations

from code2vec_tpu.telemetry import core
from code2vec_tpu.telemetry import goodput

_LISTENER_INSTALLED = False

_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


def _on_event_duration(name: str, secs: float, **_kwargs) -> None:
    if not core.enabled():
        return
    if name == _COMPILE_EVENT:
        reg = core.registry()
        reg.counter('jit/compiles_total').inc()
        reg.timer('jit/compile_ms').record(secs)
        # compile wall is badput: feed the active goodput ledger (a
        # single attribute read when no trainer has one armed)
        goodput.on_compile(secs)


def install_compile_listener() -> bool:
    """Idempotently register the jax.monitoring compile listener; True
    once it is in place."""
    global _LISTENER_INSTALLED
    if not _LISTENER_INSTALLED:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _LISTENER_INSTALLED = True
    return True


class CapacityTracker:
    """Counts and logs packed-capacity re-specializations of the step
    program.  One instance per trainer; single-threaded (hot loop only)."""

    def __init__(self, log=None):
        self._log = log
        self._seen = set()

    def observe(self, capacity: int, step: int, rows: tuple = ()) -> None:
        """``rows``: the batch's touched-row capacities (token, path)
        where it ships them — a new one re-specializes the step too."""
        reg = core.registry()
        reg.gauge('jit/packed_capacity').set(capacity)
        if (capacity,) + rows in self._seen:
            return
        first = not self._seen
        self._seen.add((capacity,) + rows)
        if not first:
            # the first capacity is the program's initial specialization,
            # already billed by the compile listener — only GROWTH beyond
            # it is a re-specialization
            reg.counter('jit/respecializations_total').inc()
        if self._log is not None:
            self._log('telemetry: packed-capacity %s at step %d '
                      '(bucket %d%s; %d seen) — new step-program '
                      'specialization'
                      % ('re-specialization' if not first else
                         'specialization', step, capacity,
                         '; touched rows %d token, %d path' % rows
                         if rows else '', len(self._seen)))
