"""On-chip A/B for the fused Pallas encode kernel (VERDICT r1 #2).

The kernel serves the DETERMINISTIC forward only (training applies dropout
inside the encode block, so ``encode`` routes Pallas exclusively when no
dropout is active — functional.py:120-128); the honest product-level A/B is
therefore the jitted **eval step** (forward + sharded top-k) at the java14m
headline configuration:

  {"metric": "eval_examples_per_sec_per_chip_java14m", "variant": "xla", ...}
  {"metric": "eval_examples_per_sec_per_chip_java14m", "variant": "pallas", ...}
  {"verdict": "keep-pallas" | "keep-xla", "speedup": ...}

The pallas variant additionally verifies the kernel actually ENGAGED by
checking the compiled HLO for the Pallas custom-call — without this a
routing mistake compares XLA against itself and the "A/B" is
meaningless.

Run it on a machine that holds the chip (the parent stays off JAX; each
arm is a child that takes the chip in turn):

  python benchmarks/bench_pallas_encode.py            # full java14m shapes
  BENCH_SMOKE=1 python benchmarks/bench_pallas_encode.py  # harness check
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
# BENCH_CONTEXTS overrides the bag size: the kernel's best case is
# long-context configs where the encode block dominates the eval step.
_contexts = int(os.environ.get('BENCH_CONTEXTS', '0'))
if _contexts:
    SHAPES = SHAPES._replace(max_contexts=_contexts)
WARMUP_STEPS, MEASURE_STEPS = benchlib.bench_steps(SMOKE)


def measure(use_pallas: bool):
    """Returns (examples_per_sec_per_chip, engaged)."""
    import jax
    import jax.numpy as jnp

    config = benchlib.headline_config(SHAPES,
                                      USE_PALLAS_FUSED_ENCODE=use_pallas)
    trainer, params = benchlib.build_eval_trainer(config, SHAPES)

    # Device-resident batches placed via the trainer's mesh-aware staging —
    # but unlike train steps, eval steps carry no cross-step data
    # dependency. Thread a scalar from each step's output into the next
    # step's input (weight + 0*token), serializing the chain exactly like
    # train's state dependency, and fetch a VALUE once at the end:
    # elapsed = sum of true step times + one host sync.
    placed = benchlib.staged(trainer, benchlib.random_batches(SHAPES, 4))
    # AOT HLO inspection costs a full extra compile of the java14m eval
    # program — only pay it for the variant whose engagement is in doubt.
    engaged = (benchlib.mosaic_engaged(trainer._eval_step, params,
                                       placed[0])
               if use_pallas else False)

    chain_weight = jax.jit(lambda w, t: w + t * 0)

    def run_chain(steps: int) -> float:
        token = jnp.zeros((), jnp.float32)
        for i in range(steps):
            source, path, target, mask, label, weight = placed[i % len(placed)]
            arrays = (source, path, target, mask, label,
                      chain_weight(weight, token))
            out = trainer.eval_step_placed(params, arrays)
            token = out['loss_sum']
        return float(token)

    run_chain(WARMUP_STEPS)
    start = time.perf_counter()
    run_chain(MEASURE_STEPS)
    elapsed = time.perf_counter() - start
    per_chip = (MEASURE_STEPS * SHAPES.batch_size / elapsed
                / len(jax.devices()))
    return per_chip, engaged


def run_variant(variant: str) -> None:
    """Child mode: one A/B arm in this process. Prints the same JSON lines
    the old single-process harness did."""
    device = benchlib.tpu_or_exit('bench_pallas_encode', SMOKE)
    use_pallas = variant == 'pallas'
    try:
        with benchlib.smoke_kernels(SMOKE):
            examples_per_sec, engaged = measure(use_pallas)
    except Exception as exc:  # a kernel compile failure IS the answer
        print(json.dumps({'variant': variant, 'error': str(exc)[:300]}),
              flush=True)
        sys.exit(1)
    if use_pallas and not engaged and not SMOKE:
        # (SMOKE runs the kernel interpreted; engagement is a TPU-only
        # check)
        print(json.dumps({
            'variant': variant, 'error': 'kernel_not_engaged',
            'detail': 'compiled eval HLO has no Pallas custom-call; '
                      'the A/B would compare XLA against itself'}),
            flush=True)
        sys.exit(3)
    metric = ('eval_examples_per_sec_SMOKE_ONLY' if SMOKE
              else 'eval_examples_per_sec_per_chip_java14m')
    if _contexts:
        metric += f'_c{_contexts}'  # non-headline bag size
    print(json.dumps({
        'metric': metric,
        'variant': variant,
        'value': round(examples_per_sec, 1),
        'unit': 'examples/sec/chip', **device}), flush=True)


def main() -> None:
    """Parent: each variant in its own subprocess under a per-arm timeout,
    so a Mosaic compile stall (the observed C=1024 failure mode — 900 s
    stage timeout burned with nothing to show, round-3 capture log) costs
    one arm, not the whole stage. The parent imports no jax, so it never
    holds the chip its children need."""
    variant = os.environ.get('BENCH_PALLAS_ENCODE_VARIANT', '')
    if variant:
        run_variant(variant)
        return
    import subprocess
    per_arm = float(os.environ.get('BENCH_PALLAS_ARM_TIMEOUT',
                                   '240' if SMOKE else '780'))
    results = {}
    for variant in ('xla', 'pallas'):
        env = dict(os.environ, BENCH_PALLAS_ENCODE_VARIANT=variant)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=per_arm)
            out, rc = proc.stdout, proc.returncode
        except subprocess.TimeoutExpired as e:
            out = (e.stdout.decode(errors='replace')
                   if isinstance(e.stdout, bytes) else (e.stdout or ''))
            rc = -1
            print(json.dumps({'variant': variant,
                              'error': 'arm_timeout',
                              'timeout_s': per_arm}), flush=True)
        for line in out.splitlines():
            line = line.strip()
            if not line.startswith('{'):
                continue
            print(line, flush=True)
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get('variant') == variant and 'value' in rec:
                results[variant] = rec['value']
        if rc == 2:
            sys.exit(2)  # the arm found no TPU: no A/B to report
        if rc != 0 and variant == 'pallas':
            print(json.dumps({'verdict': 'keep-xla',
                              'reason': 'pallas arm failed or timed out'}),
                  flush=True)
            # nonzero exit: this verdict is a placeholder, not a measured
            # A/B — a later run must retry rather than lock it in
            sys.exit(4)
        if rc != 0:
            sys.exit(4)
    if 'xla' in results and 'pallas' in results:
        speedup = results['pallas'] / results['xla']
        print(json.dumps({
            'verdict': 'keep-pallas' if speedup > 1.02 else 'keep-xla',
            'speedup': round(speedup, 4)}), flush=True)


if __name__ == '__main__':
    main()
