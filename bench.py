"""Benchmark: training throughput at java14m scale on the available chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"platform", "device_kind", "device_count"} — the device as JAX reports it
rides every line. One process, no fallback: off a TPU it exits non-zero
and prints no result (``BENCH_SMOKE=1`` is the explicit tiny-shape CPU
rehearsal, and renames the metric).

Methodology mirrors the reference's throughput trace (windowed average over
train steps, tensorflow_model.py:424-430) at the reference's headline
configuration (config.py:47-70): batch 1024, 200 contexts/example, dims
128/128/384, full java14m vocabularies (1.3M token / 911K path / 261K
target). Baseline: ~4,700 examples/sec on a Tesla V100 (README.md:69,127 —
14M examples / 50 min per epoch; BASELINE.md).

Data is synthetic (uniform random indices): this measures the device compute
path the way the reference's numbers measure theirs — the host input
pipeline is overlap-hidden behind the step in training and is benchmarked
separately (benchmarks/bench_host_pipeline.py; results in PARITY.md).

Timing methodology: batches are made device-resident up front and the timed
loop enqueues all steps, blocking once on the final loss. Each step's state
feeds the next, so device execution cannot overlap across steps — elapsed
time is the sum of true per-step device times plus ONE host sync.
"""
from __future__ import annotations

import json
import os
import time

from code2vec_tpu import benchlib

METRIC_NAME = 'train_examples_per_sec_per_chip_java14m'

# BENCH_SMOKE=1: tiny shapes so the harness itself can be validated on CPU.
# The emitted metric is renamed so a smoke line can never be mistaken for a
# java14m benchmark number.
SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
WARMUP_STEPS, MEASURE_STEPS = benchlib.bench_steps(SMOKE)

# BENCH_RECIPE selects which knob set the headline measures now that the
# measured winners are config defaults (2026-07-31 A/B ladder):
#   'default' — the config as shipped (rbg dropout + bf16 Adam-mu)
#   'parity'  — the reference-parity knobs (threefry + fp32 mu), kept
#               refreshable so the 4.69x-vs-V100 comparison row in
#               PERF.md never goes stale while defaults move
# Unknown values fall back to 'default' (the driver must never crash on a
# stray env var); the emitted JSON carries the resolved recipe.
BENCH_RECIPE = os.environ.get('BENCH_RECIPE', 'default')
if BENCH_RECIPE not in ('default', 'default_v2', 'parity', 'ragged'):
    BENCH_RECIPE = 'default'
RECIPE_OVERRIDES = {
    'default': {},
    # the full ragged-fusion candidate (ISSUEs 10 + 12): the fusion is
    # the shipped default now, so this recipe adds the train-side
    # Pallas kernel pair (RAGGED_TRAIN_KERNEL) — the headline re-capture
    # arm once scripts/flip_verdict.py records the >=2% train win from
    # the bench_pallas_ragged A/B
    'ragged': dict(USE_PALLAS_RAGGED_FUSION=True,
                   RAGGED_TRAIN_KERNEL=True),
    # the 2026-07-31 morning default set (rbg + bf16 mu, fp32 nu/grads),
    # pinned so the headline_v2 capture stays reproducible now that the
    # shipped default moved on (bf16 nu) — a 'default' re-run would
    # silently measure the newer recipe under the older label
    'default_v2': dict(ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32'),
    'parity': dict(DROPOUT_PRNG_IMPL='threefry2x32',
                   ADAM_MU_DTYPE='float32',
                   ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32'),
}[BENCH_RECIPE]


def run_measurement() -> None:
    """Init the backend, run the timed loop, print the JSON line."""
    # a CPU/GPU number is not the java14m TPU metric: exit 1, no line
    device = benchlib.tpu_or_exit('bench.py', SMOKE, code=1)
    n_devices = device['device_count']

    config = benchlib.headline_config(SHAPES, **RECIPE_OVERRIDES)
    trainer, state = benchlib.build_trainer(config, SHAPES)

    # Device-resident batches, placed with the trainer's own mesh-aware
    # staging: training overlaps uploads behind the step, so upload cost
    # must not be billed to the per-step number.
    host_batches = benchlib.random_batches(SHAPES, 4)
    if config.USE_PALLAS_RAGGED_FUSION:
        # the fused path lives behind the PACKED wire twins: plane
        # batches dispatch (by arity) to the planes program the flag
        # never touches, so the 'ragged' recipe would silently measure
        # the unfused step under the fused label — the same mislabeling
        # trap the default_v2 pin above guards against
        host_batches = benchlib.pack_batches(host_batches, trainer)
    batches = benchlib.staged(trainer, host_batches)

    for i in range(WARMUP_STEPS):
        state, loss = trainer.train_step_placed(state, batches[i % len(batches)])
        float(loss)

    # Enqueue every step, block once: steps serialize on the state
    # dependency, so this sums true device step times + one host sync.
    start = time.perf_counter()
    for i in range(MEASURE_STEPS):
        state, loss = trainer.train_step_placed(state, batches[i % len(batches)])
    float(loss)
    elapsed = time.perf_counter() - start

    examples_per_sec = MEASURE_STEPS * SHAPES.batch_size / elapsed
    per_chip = examples_per_sec / n_devices
    # bytes/batch each wire format would put on the host->device link at
    # the realistic java14m fill (the timed loop above is device-resident
    # by design, so this is a computed property, not a timing)
    filled = benchlib.random_batches(SHAPES, 1, seed=2,
                                     fill=benchlib.JAVA14M_FILL)
    wire = {'planes': benchlib.wire_bytes(filled[0]),
            'packed': benchlib.wire_bytes(
                benchlib.pack_batches(filled, trainer)[0])}
    line = {
        'metric': ('train_examples_per_sec_SMOKE_ONLY' if SMOKE
                   else METRIC_NAME),
        'value': round(per_chip, 1),
        'unit': 'examples/sec/chip',
        'vs_baseline': (0.0 if SMOKE else round(
            per_chip / benchlib.V100_BASELINE_EXAMPLES_PER_SEC, 3)),
        'recipe': BENCH_RECIPE,
        'wire_bytes_per_batch': wire,
        # per-stage peak HBM (ISSUE 9): footprint rides the headline
        # record so the bench trajectory tracks memory next to
        # throughput (None on stats-less backends, an explicit gap)
        **benchlib.device_memory_record(),
        **device,
    }
    if SMOKE:
        # echo the RESOLVED knobs so the smoke test can assert the recipe
        # actually reached the config, not just the label
        line['knobs'] = {'dropout_prng': config.DROPOUT_PRNG_IMPL,
                         'adam_mu': config.ADAM_MU_DTYPE,
                         'adam_nu': config.ADAM_NU_DTYPE,
                         'grads': config.GRADS_DTYPE}
    print(json.dumps(line))


if __name__ == '__main__':
    run_measurement()
