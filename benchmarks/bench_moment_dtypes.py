"""On-chip A/B: Adam second-moment storage dtype (ADAM_NU_DTYPE).

The nu tree is the last full-precision optimizer stream in the dense
update after the measured ADAM_MU_DTYPE flip: 1.54 GB fp32 at java14m's
384M params, read+write every step (~1.9 ms/step analytic at the measured
~819 GB/s — PERF.md roofline). This measures the current default recipe
(rbg dropout + bf16 mu, the 2026-07-31 flips) against the same recipe
with nu stored bf16 (training/adam_dtypes.py), to decide whether
ADAM_NU_DTYPE joins the defaults under the >=2% flip rule.

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
        label += '_SMOKE_ONLY'
    print(json.dumps({'measure': label, 'value': round(dt * 1e3, 2),
                      'examples_per_sec': round(SHAPES.batch_size / dt, 1)}),
          flush=True)


def main() -> None:
    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower()}),
          flush=True)
    # Arms pin every knob the A/B touches — INCLUDING GRADS_DTYPE in the
    # nu-only arms: if its default ever flips, an unpinned baseline
    # would silently absorb the flip and corrupt the nu attribution.
    measure('step_ms_nu_fp32',
            DROPOUT_PRNG_IMPL='rbg', ADAM_MU_DTYPE='bfloat16',
            ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32')
    measure('step_ms_nu_bf16',
            DROPOUT_PRNG_IMPL='rbg', ADAM_MU_DTYPE='bfloat16',
            ADAM_NU_DTYPE='bfloat16', GRADS_DTYPE='float32')
    # Cross-check: bf16 nu alone against the pre-flip parity recipe, so
    # the lever's solo effect is attributable (mirrors how mu was
    # measured in bench_rbg_dropout.py).
    measure('step_ms_nu_bf16_parity_recipe',
            DROPOUT_PRNG_IMPL='threefry2x32', ADAM_MU_DTYPE='float32',
            ADAM_NU_DTYPE='bfloat16', GRADS_DTYPE='float32')
    # GRADS_DTYPE='bfloat16' (bf16 table-grad scatters + grad tree,
    # trainer.py cast_for_grads): solo on the default recipe, then the
    # full combined candidate (rbg + bf16 mu + bf16 nu + bf16 grads).
    measure('step_ms_grads_bf16',
            DROPOUT_PRNG_IMPL='rbg', ADAM_MU_DTYPE='bfloat16',
            ADAM_NU_DTYPE='float32', GRADS_DTYPE='bfloat16')
    measure('step_ms_nu_and_grads_bf16',
            DROPOUT_PRNG_IMPL='rbg', ADAM_MU_DTYPE='bfloat16',
            ADAM_NU_DTYPE='bfloat16', GRADS_DTYPE='bfloat16')


if __name__ == '__main__':
    main()
