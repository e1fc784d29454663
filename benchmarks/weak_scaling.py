"""Weak scaling on virtual CPU meshes + analytic ICI projection
(VERDICT r2 next #5).

Part 1 — measurement: fixed per-device batch over 1/2/4/8 virtual CPU
devices (data-parallel axis). Virtual devices SHARE the host's cores, so
absolute throughput cannot scale — what this measures is the SPMD
partitioning overhead: with perfect partitioning, t(N) == N * t(1) on a
fixed core budget, and

    overhead(N) = t(N) / (N * t(1)) - 1

is the fraction the gradient psum + sharded-program bookkeeping add on
top of the N-fold compute. That overhead is the piece of multi-chip
scaling this environment CAN falsify (collective deadlocks, pathological
partitions, per-shard recompilation); the ICI part is projected
analytically below from on-chip measurements.

Part 2 — projection (--project): aggregate examples/sec for a v5e-pod
data-parallel mesh at the java14m config, from measured constants:
  * 49.25 ms/chip/step at B=1024 (PERF.md, 2026-07-29 capture)
  * grad psum bytes/step = fp32 grads for 384.4M params = 1.538 GB
  * ring all-reduce moves 2*(N-1)/N * bytes over each chip's ICI links
Overlap assumption: XLA overlaps the psum of layer k's grads with the
backward of layer k-1; the model has effectively 2 big "layers" (tables,
dense), so we project both a fully-overlapped and a zero-overlap bound.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/weak_scaling.py [--project]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# ---- measured constants (PERF.md / BASELINE.json) ----
STEP_MS_PER_CHIP = 49.25        # java14m B=1024, v5e-class, 2026-07-29
BATCH_PER_CHIP = 1024
PARAM_COUNT = 384.4e6           # java14m tables + dense
GRAD_BYTES = PARAM_COUNT * 4    # fp32 grads
# v5e: 4 ICI links/chip x ~45 GB/s each direction (public v5e specs);
# a 2D-torus ring all-reduce sustains ~1 link pair per ring direction
ICI_GBPS_PER_LINK = 45e9
NORTH_STAR_AGG = 18700.0        # BASELINE.json multi-chip reference point


def measure(per_device_batch: int = 64,
            opt_sharding: str = 'mirror') -> None:
    import jax

    from code2vec_tpu import benchlib

    results = []
    n_max = len(jax.devices())
    for n in (1, 2, 4, 8):
        if n > n_max:
            break
        shapes = benchlib.SMOKE_SHAPES._replace(
            batch_size=per_device_batch * n)
        # dtype knobs pinned to the values the committed r3/r5 artifacts
        # were measured under, so re-runs stay comparable as config
        # defaults move (a clean-host isolate showed the nu flip itself
        # is step-time-neutral on virtual CPU meshes —
        # weak_scaling_r5_postflip_note.jsonl)
        config = benchlib.headline_config(
            shapes, COMPUTE_DTYPE='float32', MESH_DATA_AXIS_SIZE=n,
            MESH_MODEL_AXIS_SIZE=1,
            OPTIMIZER_STATE_SHARDING=opt_sharding,
            DROPOUT_PRNG_IMPL='threefry2x32', ADAM_MU_DTYPE='float32',
            ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32')
        from code2vec_tpu.models.backends import create_backend
        from code2vec_tpu.parallel import mesh as mesh_lib
        from code2vec_tpu.training.trainer import Trainer
        from code2vec_tpu.vocab import SizeOnlyVocabs
        backend = create_backend(config, SizeOnlyVocabs(
            shapes.token_vocab, shapes.path_vocab, shapes.target_vocab))
        mesh = mesh_lib.create_mesh(config, devices=jax.devices()[:n])
        trainer = Trainer(config, backend, mesh=mesh)
        state = trainer.init_state(seed=0)
        feeds = benchlib.staged(trainer, benchlib.random_batches(shapes, 4))
        for i in range(3):
            state, loss = trainer.train_step_placed(state,
                                                    feeds[i % len(feeds)])
            float(loss)
        # best-of-3 repeats: shared-core virtual devices time-share with
        # whatever else the host runs, so a single 10-step sample can
        # absorb a transient load spike (the round-4 4-device +69.9%
        # outlier, VERDICT r4 weak #6/#9). The minimum is the estimate
        # least contaminated by foreign load; all repeats + the host
        # load average are recorded as provenance.
        repeat_ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            last = None
            steps = 10
            for i in range(steps):
                state, last = trainer.train_step_placed(
                    state, feeds[i % len(feeds)])
            float(last)
            repeat_ms.append((time.perf_counter() - t0) / steps * 1e3)
        dt = min(repeat_ms) / 1e3
        results.append((n, dt))
        base = results[0][1]
        overhead = dt / (n * base) - 1 if n > 1 else 0.0
        print(json.dumps({
            'measure': 'weak_scaling_virtual_cpu',
            'devices': n,
            'per_device_batch': per_device_batch,
            'opt_sharding': opt_sharding,
            'step_ms': round(dt * 1e3, 2),
            'repeat_step_ms': [round(r, 2) for r in repeat_ms],
            'loadavg_1m': round(os.getloadavg()[0], 2),
            'partition_overhead_vs_1dev': round(overhead, 4),
            # VERDICT r3 weak #5: virtual devices share one host's cores,
            # so N*t(1) is inflated by fixed per-step overheads that
            # amortize at N>1 — negative values are an artifact of the
            # normalizer, not free collectives. This harness falsifies
            # deadlocks/recompilation; it cannot resolve a genuine
            # few-percent collective overhead.
            'normalizer': 'min of 3 repeats vs N*t(1); t(1) inflated by '
                          'fixed overheads on shared-core virtual '
                          'devices; negative overhead is not a real '
                          'win'}), flush=True)


def project() -> None:
    """Aggregate-throughput projection for data-parallel v5e meshes."""
    for n in (4, 8, 16, 32, 64):
        # bidirectional ring over the data axis: each chip sends+receives
        # 2*(N-1)/N * GRAD_BYTES split across 2 ring directions
        ring_bytes = 2 * (n - 1) / n * GRAD_BYTES
        ici_ms = ring_bytes / (2 * ICI_GBPS_PER_LINK) * 1e3
        step = STEP_MS_PER_CHIP
        best = max(step, ici_ms)          # full compute/comm overlap
        worst = step + ici_ms             # zero overlap
        agg_best = n * BATCH_PER_CHIP / (best / 1e3)
        agg_worst = n * BATCH_PER_CHIP / (worst / 1e3)
        print(json.dumps({
            'measure': 'ici_projection_v5e_dp',
            'chips': n,
            'grad_allreduce_ms': round(ici_ms, 2),
            'agg_examples_per_sec_overlapped': round(agg_best, 0),
            'agg_examples_per_sec_no_overlap': round(agg_worst, 0),
            'vs_north_star_18700': round(agg_best / NORTH_STAR_AGG, 2)},
        ), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--project', action='store_true',
                        help='print the analytic ICI projection only')
    parser.add_argument('--per-device-batch', type=int, default=64)
    parser.add_argument('--opt-sharding', choices=['mirror', 'zero'],
                        default='mirror',
                        help="moment layout (ZeRO-1 'zero' adds the "
                             'reduce-scatter/all-gather pair this '
                             'harness then prices)')
    args = parser.parse_args()
    if args.project:
        project()
    else:
        measure(args.per_device_batch, args.opt_sharding)
        project()


if __name__ == '__main__':
    main()
