"""On-chip A/B: threefry vs hardware-RNG (`rbg`) dropout mask.

The 2026-07-29 diag capture showed dropout's ~131M threefry draws cost
~4.8 ms of the 49.25 ms java14m train step (PERF.md). This measures the
same devargs/sync-at-end step with `DROPOUT_PRNG_IMPL='rbg'` against the
default, to decide whether the knob should become the TPU default.

Prints one JSON line per measurement (chained sync-at-end methodology,
PERF.md).
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402

SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
# Shared methodology: one end-of-chain sync over the benchlib step counts
# (10 warmup / 60 measured); hardcoding fewer steps made ms/step
# incomparable with the diag table.
WARMUP, STEPS = benchlib.bench_steps(SMOKE)


def measure(label: str, **overrides) -> None:
    config = benchlib.headline_config(SHAPES, **overrides)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    feeds = benchlib.staged(trainer, benchlib.random_batches(SHAPES, 4))
    for i in range(WARMUP):
        state, loss = trainer.train_step_placed(state, feeds[i % len(feeds)])
        float(loss)
    t0 = time.perf_counter()
    last = None
    for i in range(STEPS):
        state, last = trainer.train_step_placed(state, feeds[i % len(feeds)])
    float(last)
    dt = (time.perf_counter() - t0) / STEPS
    if SMOKE:
        label += '_SMOKE_ONLY'  # never mistakable for a java14m capture
    print(json.dumps({'measure': label, 'value': round(dt * 1e3, 2),
                      'examples_per_sec': round(SHAPES.batch_size / dt, 1)}),
          flush=True)


def main() -> None:
    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower()}),
          flush=True)
    # Every arm pins BOTH knobs explicitly: the config DEFAULTS are now
    # 'rbg' + bf16 mu (flipped on this A/B's own 2026-07-31 capture), so
    # any unpinned "baseline" arm would silently measure default vs
    # default and report a ~0 delta.
    pins = dict(ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32')
    measure('step_ms_dropout_threefry', DROPOUT_PRNG_IMPL='threefry2x32',
            ADAM_MU_DTYPE='float32', **pins)
    measure('step_ms_dropout_rbg', DROPOUT_PRNG_IMPL='rbg',
            ADAM_MU_DTYPE='float32', **pins)
    measure('step_ms_bf16_mu', DROPOUT_PRNG_IMPL='threefry2x32',
            ADAM_MU_DTYPE='bfloat16', **pins)
    measure('step_ms_rbg_and_bf16_mu',
            DROPOUT_PRNG_IMPL='rbg', ADAM_MU_DTYPE='bfloat16', **pins)


if __name__ == '__main__':
    main()
