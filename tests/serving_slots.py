"""Test helper: hold a ``ServingEngine``'s decode slots.

The dispatcher closes a batch as soon as a decode slot is free
(serving/engine.py ``_dispatch_loop``), so a test that wants requests to
coalesce holds the slots, not the clock: one plug batch a decode worker,
each parked on a gate at the top of its ``_decode``. While they are
parked every slot is taken and what the test submits gathers in the
queue until ``release()`` (a slot frees, the dispatcher wakes) or the
engine's ``max_delay_ms`` passes.
"""
import contextlib
import threading


TIMEOUT_S = 60.0


class HeldSlots:
    def __init__(self, engine, gate):
        self._gate = gate
        stats = engine.stats()
        #: ``batches_total`` / ``early_close_total`` once the plugs went
        #: out (each plug is a batch of its own, closed early)
        self.batches = stats['batches_total']
        self.early = stats['early_close_total']

    def release(self):
        self._gate.set()


@contextlib.contextmanager
def decode_slots_held(engine, line):
    """Park one ``topk`` plug batch of ``[line]`` in every decode worker
    of ``engine``; yields a ``HeldSlots``. Leaving the block releases
    the gate, waits for the plugs and takes the gate out again."""
    gate = threading.Event()
    parked = threading.Semaphore(0)
    decode = engine._decode

    def gated(*batch):
        if not gate.is_set():
            parked.release()
            gate.wait(TIMEOUT_S)
        return decode(*batch)

    engine._decode = gated
    plugs = []
    try:
        for _ in range(engine._decode_slots):
            plugs.append(engine.submit([line], tier='topk'))
            assert parked.acquire(timeout=TIMEOUT_S), \
                'a plug batch never reached its decode worker'
        yield HeldSlots(engine, gate)
    finally:
        gate.set()
        for plug in plugs:
            plug.exception(timeout=TIMEOUT_S)
        del engine._decode  # the class's method again
