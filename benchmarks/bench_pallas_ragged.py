"""On-chip A/B for the ragged packed-wire fusion (ISSUEs 10 + 12).

Measures the packed TRAIN step, the TRAIN BACKWARD (value_and_grad
alone — the custom-VJP recompute kernel pair vs the unpack path's
stored-residual autodiff, isolated from the Adam update that dominates
the full step), and the packed PREDICT step (attention tier) with
``USE_PALLAS_RAGGED_FUSION`` off (unpack-then-dense, the PR-1 path) and
on + ``RAGGED_TRAIN_KERNEL`` (the full Pallas pair, the flip the >=2%
rule gates — scripts/flip_verdict.py settles it from these records), at
the java14m headline shape and realistic fill. Each arm runs in its OWN
subprocess so the per-arm ``peak_hbm_bytes``
(benchlib.device_memory_record) is that arm's peak, not the max over
both; the train-backward record additionally carries the grad program's
AOT ``memory_analysis`` temp bytes — the residual footprint the
recompute backward exists to cut.

Knobs (the capture stages set them):

  BENCH_SMOKE=1       tiny CPU shapes, metrics renamed *_SMOKE_ONLY
  BENCH_CONTEXTS=N    override max_contexts (the fused path's best case
                      is high capacity / low fill, where the dense
                      planes are mostly padding)
  BENCH_FILL=F        mean fill fraction of the packed batches
                      (default benchlib.JAVA14M_FILL = 0.25)

Emits one JSON line per (arm x step kind), then the fused/unfused
speedup + peak-HBM/temp-bytes ratio records summarize_captures.py
surfaces:

  {"measure": "step_ms_ragged_train_fused", "kind": "train", ...}
  {"measure": "step_ms_ragged_train_bwd_fused", "temp_bytes": ..., ...}
  {"measure": "ragged_fusion_train_speedup", "value": ..., ...}
  {"measure": "ragged_train_kernel_speedup", "value": ..., ...}
  {"measure": "ragged_fusion_train_bwd_temp_ratio", "value": ..., ...}
  {"verdict": "keep-fused" | "keep-unfused", ...}   (fusion, vs unpack)
  {"verdict": "kernel-on" | "kernel-off", ...}      (RAGGED_TRAIN_KERNEL)
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
_contexts = int(os.environ.get('BENCH_CONTEXTS', '0'))
if _contexts:
    SHAPES = SHAPES._replace(max_contexts=_contexts)
FILL = float(os.environ.get('BENCH_FILL', str(benchlib.JAVA14M_FILL)))
WARMUP_STEPS, MEASURE_STEPS = benchlib.bench_steps(SMOKE)
# three arms, two decisions:
#   unfused       — fusion OFF (unpack-then-dense, the PR-1 path)
#   fused         — fusion ON, train via the custom-VJP jnp twin: the
#                   SHIPPED default
#   fused_kernel  — fused + RAGGED_TRAIN_KERNEL (the Pallas train pair)
# ragged_fusion_*_speedup (unfused/fused) confirms the default flip;
# ragged_train_kernel_speedup (fused/fused_kernel) is what gates
# RAGGED_TRAIN_KERNEL — the kernel pair must beat the twin it would
# replace, not the unpack path nothing ships anymore.
VARIANTS = ('unfused', 'fused', 'fused_kernel')


def _suffix(name: str) -> str:
    name = name + ('_SMOKE_ONLY' if SMOKE else '')
    return name + (('_c%d' % _contexts) if _contexts else '')


def measure(variant: str):
    """One arm: ({kind: ms_per_step}, grad_temp_bytes, engaged)."""
    import jax
    import jax.numpy as jnp

    fused = variant != 'unfused'
    train_kernel = variant == 'fused_kernel'
    config = benchlib.headline_config(
        SHAPES, USE_PALLAS_RAGGED_FUSION=fused,
        RAGGED_TRAIN_KERNEL=train_kernel)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    host = benchlib.random_batches(SHAPES, 4, seed=1, fill=FILL)
    packed = benchlib.pack_batches(host, trainer)
    placed = benchlib.staged(trainer, packed)

    # engagement check (TPU fused arms only): the compiled attention-tier
    # packed program must contain the Mosaic custom-call, or the "A/B"
    # compares XLA against itself (bench_pallas_encode precedent)
    engaged = False
    if fused and not SMOKE:
        fn = trainer._predict_steps[('attention', 'packed')]
        engaged = benchlib.mosaic_engaged(fn, state.params, placed[0])

    # ---- train: steps serialize on the state dependency; block once
    def train_chain(steps: int) -> float:
        nonlocal state
        loss = None
        for i in range(steps):
            state, loss = trainer.train_step_placed(
                state, placed[i % len(placed)])
        return float(loss)

    train_chain(WARMUP_STEPS)
    t0 = time.perf_counter()
    train_chain(MEASURE_STEPS)
    train_ms = 1e3 * (time.perf_counter() - t0) / MEASURE_STEPS

    # ---- predict (attention tier): thread a scalar from each output
    # into the next input's count so the chain serializes on device
    # exactly like train's state dependency (bench.py methodology)
    chain_count = jax.jit(
        lambda count, token: count + (token * 0).astype(jnp.int32))

    def predict_chain(steps: int) -> float:
        token = jnp.zeros((), jnp.float32)
        for i in range(steps):
            ctx, count, label, weight = placed[i % len(placed)]
            out = trainer.predict_step_placed(
                state.params, (ctx, chain_count(count, token), label,
                               weight), tier='attention')
            token = out['topk_scores'].sum()
        return float(token)

    predict_chain(WARMUP_STEPS)
    t0 = time.perf_counter()
    predict_chain(MEASURE_STEPS)
    predict_ms = 1e3 * (time.perf_counter() - t0) / MEASURE_STEPS

    # ---- train BACKWARD (ISSUE 12): value_and_grad alone, the axis
    # the custom-VJP recompute pair moves, isolated from the Adam
    # update (which walks the full 384M params either way and would
    # dilute the encoder-backward delta at java14m shapes). The arm
    # mirrors its trainer's packed train path: loss_fn_packed always
    # runs the ragged encoder, so the unfused arm must take the
    # unpack-then-dense route explicitly.
    loss_mesh = trainer.mesh if trainer.mesh.size > 1 else None
    rng = jax.random.PRNGKey(7)
    if fused:
        def loss_call(p, arrays):
            return trainer.backend.loss_fn_packed(p, arrays, rng,
                                                  mesh=loss_mesh)[0]
    else:
        from code2vec_tpu.data import packed as packed_lib

        def loss_call(p, arrays):
            ctx, count, label, weight = arrays
            planes = packed_lib.unpack_device(
                ctx, count, config.MAX_CONTEXTS,
                trainer.backend.token_pad_index,
                trainer.backend.path_pad_index)
            return trainer.backend.loss_fn(
                p, planes + (label, weight), rng, mesh=loss_mesh)[0]
    grad_fn = jax.jit(jax.value_and_grad(loss_call))

    def bwd_chain(steps: int) -> float:
        token = jnp.zeros((), jnp.float32)
        for i in range(steps):
            ctx, count, label, weight = placed[i % len(placed)]
            loss, _grads = grad_fn(
                state.params, (ctx, chain_count(count, token), label,
                               weight))
            token = loss
        return float(token)

    bwd_chain(WARMUP_STEPS)
    t0 = time.perf_counter()
    bwd_chain(MEASURE_STEPS)
    bwd_ms = 1e3 * (time.perf_counter() - t0) / MEASURE_STEPS
    # AOT residual footprint of the grad program (temp bytes = XLA's
    # temporary allocation incl. fwd->bwd residuals); None where the
    # backend has no memory analysis
    try:
        analysis = grad_fn.lower(
            state.params, placed[0]).compile().memory_analysis()
        temp_bytes = int(analysis.temp_size_in_bytes)
    except Exception:
        temp_bytes = None
    if train_kernel and not SMOKE:
        # the kernel verdict gates RAGGED_TRAIN_KERNEL: this arm's
        # BACKWARD program must contain the Mosaic custom-call too, or
        # the kernel-vs-twin comparison compares XLA against itself
        engaged = engaged and benchlib.mosaic_engaged(
            grad_fn, state.params, placed[0])
    return ({'train': train_ms, 'predict': predict_ms,
             'train_bwd': bwd_ms}, temp_bytes, engaged)


def run_variant(variant: str) -> None:
    """Child mode: one arm in this process (own peak-HBM watermark)."""
    device = benchlib.tpu_or_exit('bench_pallas_ragged', SMOKE)
    try:
        with benchlib.smoke_kernels(SMOKE):
            step_ms, temp_bytes, engaged = measure(variant)
    except Exception as exc:  # a kernel compile failure IS the answer
        print(json.dumps({'variant': variant, 'error': str(exc)[:300]}),
              flush=True)
        sys.exit(1)
    if variant != 'unfused' and not engaged and not SMOKE:
        print(json.dumps({
            'variant': variant, 'error': 'kernel_not_engaged',
            'detail': 'compiled packed predict/grad HLO has no Mosaic '
                      'custom-call'}), flush=True)
        sys.exit(3)
    memory = benchlib.device_memory_record()
    for kind, value in step_ms.items():
        record = {
            'measure': _suffix('step_ms_ragged_%s_%s' % (kind, variant)),
            'value': round(value, 3), 'unit': 'ms/step',
            'kind': kind, 'variant': variant, 'fill': FILL,
            'contexts': SHAPES.max_contexts,
            'batch': SHAPES.batch_size, **memory, **device}
        if kind == 'train_bwd':
            # the residual-footprint axis: AOT temp bytes of the grad
            # program (None = backend without memory analysis, an
            # explicit gap like peak_hbm_bytes)
            record['temp_bytes'] = temp_bytes
        print(json.dumps(record), flush=True)


def main() -> None:
    """Parent: each arm in its own subprocess under a per-arm timeout
    (a Mosaic compile stall costs one arm, not the stage); the parent
    imports no jax, so it never holds the chip its children need."""
    variant = os.environ.get('BENCH_PALLAS_RAGGED_VARIANT', '')
    if variant:
        run_variant(variant)
        return
    import subprocess
    per_arm = float(os.environ.get('BENCH_PALLAS_ARM_TIMEOUT',
                                   '240' if SMOKE else '780'))
    values: dict = {}
    hbm: dict = {}
    temps: dict = {}
    for variant in VARIANTS:
        env = dict(os.environ, BENCH_PALLAS_RAGGED_VARIANT=variant)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=per_arm)
            out, rc = proc.stdout, proc.returncode
        except subprocess.TimeoutExpired as e:
            out = (e.stdout.decode(errors='replace')
                   if isinstance(e.stdout, bytes) else (e.stdout or ''))
            rc = -1
            print(json.dumps({'variant': variant, 'error': 'arm_timeout',
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
            # the record carries its kind explicitly — substring-parsing
            # the measure name would confuse 'train' with 'train_bwd'
            kind = rec.get('kind')
            if rec.get('variant') == variant and 'value' in rec and kind:
                values[(kind, variant)] = rec['value']
                hbm[variant] = rec.get('peak_hbm_bytes')
                if rec.get('temp_bytes') is not None:
                    temps[variant] = rec['temp_bytes']
        if rc == 2:
            sys.exit(2)  # the arm found no TPU: no A/B to report
        if rc != 0:
            if variant == 'unfused':
                sys.exit(4)
            if variant == 'fused':
                print(json.dumps({
                    'verdict': 'keep-unfused',
                    'reason': 'fused arm failed or timed out'}),
                    flush=True)
                sys.exit(4)
            # a dead fused_kernel arm settles ITS verdict without
            # discarding the completed fusion A/B
            print(json.dumps({
                'verdict': 'kernel-off',
                'reason': 'fused_kernel arm failed or timed out'}),
                flush=True)
    speedups = {}
    for kind in ('train', 'predict', 'train_bwd'):
        if (kind, 'unfused') in values and (kind, 'fused') in values \
                and values[(kind, 'fused')] > 0:
            speedups[kind] = values[(kind, 'unfused')] \
                / values[(kind, 'fused')]
            print(json.dumps({
                'measure': _suffix('ragged_fusion_%s_speedup' % kind),
                'value': round(speedups[kind], 4), 'fill': FILL,
                'contexts': SHAPES.max_contexts}), flush=True)
    # the kernel-vs-twin measures: the Pallas train pair against the
    # SHIPPED default it would replace (fused custom-VJP twin) — this,
    # not the unpack comparison, is what gates RAGGED_TRAIN_KERNEL
    kernel_speedups = {}
    for kind, name in (('train', 'ragged_train_kernel_speedup'),
                       ('train_bwd', 'ragged_train_kernel_bwd_speedup')):
        if (kind, 'fused') in values and (kind, 'fused_kernel') in values \
                and values[(kind, 'fused_kernel')] > 0:
            kernel_speedups[kind] = values[(kind, 'fused')] \
                / values[(kind, 'fused_kernel')]
            print(json.dumps({
                'measure': _suffix(name),
                'value': round(kernel_speedups[kind], 4), 'fill': FILL,
                'contexts': SHAPES.max_contexts}), flush=True)
    if hbm.get('unfused') and hbm.get('fused'):
        print(json.dumps({
            'measure': _suffix('ragged_fusion_peak_hbm_ratio'),
            'value': round(hbm['fused'] / hbm['unfused'], 4),
            'fill': FILL, 'contexts': SHAPES.max_contexts}), flush=True)
    if temps.get('unfused') and temps.get('fused'):
        # grad-program temp allocation, custom-VJP vs stored-residual
        # autodiff: the recompute backward's cut (<1 is the win)
        print(json.dumps({
            'measure': _suffix('ragged_fusion_train_bwd_temp_ratio'),
            'value': round(temps['fused'] / temps['unfused'], 4),
            'fill': FILL, 'contexts': SHAPES.max_contexts}), flush=True)
    # both verdicts decide on the ROUNDED speedup with strict '>', the
    # same comparison scripts/flip_verdict.py applies to the emitted
    # (rounded) measure records — so one capture round can never write
    # contradictory decisions at the 2% boundary
    if 'train' in speedups:
        # fusion confirmation (the default is already ON; keep-unfused
        # here argues for reverting it)
        print(json.dumps({
            'verdict': ('keep-fused'
                        if round(speedups['train'], 4) > 1.02
                        else 'keep-unfused'),
            'speedup': round(speedups['train'], 4)}), flush=True)
    if 'train' in kernel_speedups:
        # the >2% rule on the kernel pair (RAGGED_TRAIN_KERNEL)
        print(json.dumps({
            'verdict': ('kernel-on'
                        if round(kernel_speedups['train'], 4) > 1.02
                        else 'kernel-off'),
            'speedup': round(kernel_speedups['train'], 4)}), flush=True)


if __name__ == '__main__':
    main()
