"""Capture on-chip evidence for the train step: a jax.profiler trace plus
the compiled step's XLA cost analysis (FLOPs / bytes accessed), at the
java14m headline configuration.

Outputs:
  profiles/java14m_step/...   profiler trace (TensorBoard/Perfetto viewable)
  one JSON line per artifact on stdout

The cost analysis is the roofline input: with ~0.9 TFLOP of matmul work and
~11 GB of HBM traffic per step (dense Adam over 384M params dominates), the
measured ~49 ms step sits near the HBM bound, not the MXU bound (PERF.md).
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

SHAPES = benchlib.JAVA14M


def main() -> None:
    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower()}),
          flush=True)
    # A failed artifact must fail the STAGE: the watcher done-marks on
    # rc=0 + any fresh JSON line, and the platform line above would
    # otherwise done-mark a capture whose trace/cost analysis both died
    # (advisor finding, round 5).
    failed = []

    config = benchlib.headline_config(SHAPES)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    (arrays, _), = trainer.stage_batches(iter(benchlib.random_batches(
        SHAPES, 1)))

    # --- XLA cost analysis of the compiled train step
    compiled = trainer._train_step.lower(state, arrays).compile()
    try:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops = float(cost.get('flops', 0.0))
        bytes_accessed = float(cost.get('bytes accessed', 0.0))
        print(json.dumps({
            'artifact': 'train_step_cost_analysis',
            'gflops_per_step': round(flops / 1e9, 1),
            'gbytes_accessed_per_step': round(bytes_accessed / 1e9, 2)}),
            flush=True)
    except Exception as exc:
        failed.append('cost_analysis')
        print(json.dumps({'artifact': 'train_step_cost_analysis',
                          'error': str(exc)[:200]}), flush=True)

    # --- profiler trace over a few chained steps
    trace_dir = os.path.join(REPO, 'profiles', 'java14m_step')
    os.makedirs(trace_dir, exist_ok=True)
    for _ in range(5):  # warmup
        state, loss = trainer.train_step_placed(state, arrays)
    float(loss)
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(5):
            state, loss = trainer.train_step_placed(state, arrays)
        float(loss)
        jax.profiler.stop_trace()
        files = []
        for root, _dirs, names in os.walk(trace_dir):
            files += [os.path.relpath(os.path.join(root, n), trace_dir)
                      for n in names]
        print(json.dumps({'artifact': 'profiler_trace', 'dir': trace_dir,
                          'n_files': len(files),
                          'files': sorted(files)[:8]}), flush=True)
    except Exception as exc:
        failed.append('profiler_trace')
        print(json.dumps({'artifact': 'profiler_trace',
                          'error': str(exc)[:300]}), flush=True)

    # --- timed reference point alongside the artifacts
    start = time.perf_counter()
    for _ in range(20):
        state, loss = trainer.train_step_placed(state, arrays)
    float(loss)
    step_ms = (time.perf_counter() - start) / 20 * 1e3
    print(json.dumps({'artifact': 'step_time_ms',
                      'value': round(step_ms, 2)}), flush=True)
    if failed:
        sys.exit(2)   # keep the stage pending for a later healthy window


if __name__ == '__main__':
    main()
