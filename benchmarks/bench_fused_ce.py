"""On-chip A/B: flash-style fused softmax-CE (ops/pallas_ce.py) vs the
materialized-logits XLA path, at the java14m train step.

The fused kernel removes ~4.3 GB/step of (B, 261K) logits HBM traffic
(module docstring) — roughly 5 ms at the measured ~819 GB/s — IF its
blockwise matmuls keep the MXU as busy as XLA's monolithic ones. This
measures the full train step both ways (same chained devargs/sync-at-end
methodology as the other harnesses, PERF.md), plus the combined
fused-CE + rbg-dropout + bf16-mu candidate default set.

Engagement check: before timing the fused arm, the compiled HLO is
searched for the Mosaic custom call so the kernel demonstrably ran
(the same guard bench_pallas_encode.py uses).

Compile-stall resilience (VERDICT r3 #4): the C=1024 encode kernel proved
a Mosaic compile can exceed a stage timeout, so each arm runs in its OWN
subprocess under a per-arm timeout (the parent stays off JAX, so each
child takes the chip in turn); if the fused arm's compile stalls, the
harness retries unattended with smaller vocab tiles
(PALLAS_CE_VOCAB_TILE=512, then 256) instead of burning the whole stage
on one hang. Set BENCH_FUSED_CE_ARM to run a single arm directly.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402

SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
WARMUP, STEPS = benchlib.bench_steps(SMOKE)


def measure(label: str, check_engaged: bool = False, **overrides) -> None:
    config = benchlib.headline_config(SHAPES, **overrides)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    feeds = benchlib.staged(trainer, benchlib.random_batches(SHAPES, 4))
    if check_engaged:
        engaged = benchlib.mosaic_engaged(trainer._train_step, state,
                                          feeds[0])
        print(json.dumps({'measure': label + '_kernel_engaged',
                          'value': bool(engaged)}), flush=True)
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


ARMS = {
    # The xla/fused pair pins threefry + fp32 mu explicitly: the config
    # DEFAULTS flipped to rbg + bf16 mu on the 2026-07-31 capture, and an
    # unpinned pair would (a) stop being comparable with the 2026-07-29/31
    # series PERF.md's fused-CE verdict is built on and (b) make 'fused'
    # config-identical to 'fused_rbg_bf16mu' (default-vs-default, ~0
    # delta).
    'xla': dict(label='step_ms_ce_xla',
                DROPOUT_PRNG_IMPL='threefry2x32', ADAM_MU_DTYPE='float32',
                ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32'),
    'fused': dict(label='step_ms_ce_fused', check_engaged=True,
                  USE_PALLAS_FUSED_CE=True,
                  DROPOUT_PRNG_IMPL='threefry2x32',
                  ADAM_MU_DTYPE='float32',
                  ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32'),
    # the full round-5 default set plus the kernel (its measured -1.4%
    # increment rides on top of the rbg+bf16-mu recipe). No second
    # engagement check: same kernel flag as the arm above, and each check
    # costs a full extra AOT compile of the java14m step.
    'fused_rbg_bf16mu': dict(label='step_ms_ce_fused_rbg_bf16mu',
                             USE_PALLAS_FUSED_CE=True,
                             DROPOUT_PRNG_IMPL='rbg',
                             ADAM_MU_DTYPE='bfloat16',
                             ADAM_NU_DTYPE='float32',
                             GRADS_DTYPE='float32'),
}


def run_arm(arm: str) -> None:
    device = benchlib.tpu_or_exit('bench_fused_ce', SMOKE)
    print(json.dumps({**device, 'arm': arm}), flush=True)
    spec = dict(ARMS[arm])
    label = spec.pop('label')
    check = spec.pop('check_engaged', False)
    with benchlib.smoke_kernels(SMOKE):
        measure(label, check_engaged=check, **spec)


def _spawn(arm: str, timeout: float, tile: int | None = None) -> bool:
    """One arm in a subprocess (stdout inherited, so its JSON lines land in
    the capture like before); returns True on clean completion."""
    env = dict(os.environ, BENCH_FUSED_CE_ARM=arm)
    if tile is not None:
        env['PALLAS_CE_VOCAB_TILE'] = str(tile)
    try:
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                              env=env, timeout=timeout)
        ok = proc.returncode == 0
    except subprocess.TimeoutExpired:
        ok = False
    if not ok:
        print(json.dumps({'measure': 'fused_ce_arm_failed', 'arm': arm,
                          'tile': tile,
                          'timeout_s': timeout}), flush=True)
    return ok


def main() -> None:
    arm = os.environ.get('BENCH_FUSED_CE_ARM', '')
    if arm:
        run_arm(arm)
        return
    per_arm = float(os.environ.get('BENCH_FUSED_CE_ARM_TIMEOUT',
                                   '120' if SMOKE else '300'))
    ok = _spawn('xla', per_arm)
    # fused arm: shrink the vocab tile and retry if Mosaic compile stalls
    fused_ok = False
    won_tile = None
    for tile in (None, 512, 256):
        if _spawn('fused', per_arm, tile=tile):
            fused_ok = True
            won_tile = tile
            if tile is not None:
                print(json.dumps({'measure': 'fused_ce_tile_fallback',
                                  'tile': tile}), flush=True)
            break
    if not fused_ok:
        # every tile stalled: rerunning the combined arm would hit the
        # same compile; exit nonzero instead of locking in the xla arm
        # alone
        sys.exit(4)
    ok = _spawn('fused_rbg_bf16mu', per_arm, tile=won_tile) and ok
    if not ok:
        sys.exit(4)


if __name__ == '__main__':
    main()
