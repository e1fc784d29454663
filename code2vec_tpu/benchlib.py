"""Shared harness pieces for the repo-root ``bench.py`` and the scripts
under ``benchmarks/`` — one definition of the java14m headline
configuration (reference config.py:47-70) and the synthetic batch maker,
so a change to the benchmark configuration cannot silently apply to some
scripts and not others. Importing this module does not import JAX: bench
parents stay off the chip and run their arms as children."""
from __future__ import annotations

import os
from typing import NamedTuple

V100_BASELINE_EXAMPLES_PER_SEC = 4700.0  # reference README.md:69,127


class BenchShapes(NamedTuple):
    token_vocab: int
    path_vocab: int
    target_vocab: int
    batch_size: int
    max_contexts: int


JAVA14M = BenchShapes(token_vocab=1301136, path_vocab=911417,
                      target_vocab=261245, batch_size=1024, max_contexts=200)
# Fraction of the 200 context slots a real java14m example fills —
# contexts/method p50 is 28 with a long tail (corpus_stats_r4.json), so
# ~0.25 mean is the honest shape for wire-format measurements. The
# device-compute benchmarks keep full batches (fill 1.0): masked slots
# cost the same FLOPs, and changing them would break comparability with
# every prior capture.
JAVA14M_FILL = 0.25
# Tiny shapes so a harness can be validated on CPU; metric names must be
# renamed by the caller so a smoke line is never mistaken for a real one.
SMOKE_SHAPES = BenchShapes(token_vocab=1000, path_vocab=1000,
                           target_vocab=500, batch_size=64, max_contexts=16)


def smoke_requested() -> bool:
    return os.environ.get('BENCH_SMOKE', '') not in ('', '0', 'false')


def bench_timer(name: str = 'bench', window: int = 1024):
    """A telemetry ``Timer`` for benchmark loops — the shared timer API
    (code2vec_tpu/telemetry/core.py) the timed harnesses use instead of
    hand-rolled ``time.perf_counter`` arithmetic:

        sw = benchlib.bench_timer()
        with sw.time():
            <timed region>
        seconds = sw.last          # or .total / .snapshot() for stats

    Standalone instrument, NOT registered in the process-global registry:
    benchmark timings must never leak into a live run's exported
    metrics."""
    from code2vec_tpu.telemetry.core import Timer
    return Timer(name, window=window)


def bench_steps(smoke: bool):
    """(warmup_steps, measure_steps) shared by every timed harness."""
    return (2, 5) if smoke else (10, 60)


def bench_timer_wall(fn) -> float:
    """Wall-clock one call of ``fn`` through the shared Timer (the same
    clock discipline as ``bench_timer``; returns seconds). For variants
    whose result is host numpy — already synchronized — so no extra
    device fence is needed."""
    sw = bench_timer()
    with sw.time():
        fn()
    return sw.last


def device_memory_record() -> dict:
    """Per-stage HBM footprint for the bench JSON records (ISSUE 9):
    ``peak_bytes_in_use`` / ``bytes_in_use`` summed over local devices
    from the runtime's ``memory_stats()``.  Backends without memory
    stats (CPU smoke) report None — an EXPLICIT gap on the memory axis,
    not a silently absent key, so summarize_captures.py can show that a
    round is missing its footprint numbers."""
    from code2vec_tpu.telemetry.memory import backend_memory
    devices = backend_memory()['devices']  # one stats-reading code path
    if not devices:
        return {'peak_hbm_bytes': None, 'hbm_bytes_in_use': None}
    return {'peak_hbm_bytes': sum(d['peak_bytes_in_use']
                                  for d in devices),
            'hbm_bytes_in_use': sum(d['bytes_in_use'] for d in devices)}


def device_record() -> dict:
    """The device a measurement ran on, as JAX reports it — every bench
    line carries it so a CPU number can never pass for a chip number."""
    import jax
    devices = jax.devices()
    return {'platform': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'device_count': len(devices)}


def tpu_or_exit(script: str, smoke: bool, code: int = 2) -> dict:
    """``device_record()`` for a measurement that needs the chip: off a
    TPU (and not in the explicit ``BENCH_SMOKE`` rehearsal) it says what
    it found on stderr and exits ``code`` — no result line, no CPU
    fallback."""
    import json
    import sys
    device = device_record()
    if not smoke and device['platform'] != 'tpu':
        print('%s: needs a TPU, found %s; BENCH_SMOKE=1 runs the tiny-shape '
              'CPU rehearsal' % (script, json.dumps(device)),
              file=sys.stderr)
        sys.exit(code)
    return device


def smoke_kernels(smoke: bool):
    """Context for a bench arm that forces a Pallas kernel: under the
    explicit ``BENCH_SMOKE`` CPU rehearsal the kernel runs in the Pallas
    interpreter; on the chip it compiles or the arm fails."""
    import contextlib
    if not smoke:
        return contextlib.nullcontext()
    from code2vec_tpu.ops._pallas_common import interpret_kernels
    return interpret_kernels()


def headline_config(shapes: BenchShapes, **overrides):
    """The java14m benchmark Config (bfloat16 compute, jax backend)."""
    from code2vec_tpu.config import Config
    kwargs = dict(
        TRAIN_DATA_PATH_PREFIX='bench', DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='bfloat16', VERBOSE_MODE=0, READER_USE_NATIVE=False,
        TRAIN_BATCH_SIZE=shapes.batch_size, TEST_BATCH_SIZE=shapes.batch_size,
        MAX_CONTEXTS=shapes.max_contexts,
        MAX_TOKEN_VOCAB_SIZE=shapes.token_vocab,
        MAX_PATH_VOCAB_SIZE=shapes.path_vocab,
        MAX_TARGET_VOCAB_SIZE=shapes.target_vocab,
        # every timed harness here re-feeds the same staged arrays across
        # warmup+measure steps; donation would invalidate them after the
        # first consuming step on real devices
        DONATE_STAGED_BATCHES=False)
    kwargs.update(overrides)
    return Config(**kwargs)


def mosaic_engaged(jitted, *args) -> bool:
    """True iff the compiled program contains the Pallas (Mosaic) TPU
    custom-call. A bare 'custom-call' match would false-positive on other
    TPU custom-calls (e.g. top-k lowerings), so look for the Mosaic
    target 'tpu_custom_call' specifically. Costs one AOT compile — use
    once per A/B arm family, not per variant."""
    return 'tpu_custom_call' in jitted.lower(*args).compile().as_text()


def _make_trainer(config, shapes: BenchShapes):
    from code2vec_tpu import compile_cache
    from code2vec_tpu.models.backends import create_backend
    from code2vec_tpu.training.trainer import Trainer
    from code2vec_tpu.vocab import SizeOnlyVocabs
    compile_cache.configure()  # before the harness's first compile
    backend = create_backend(
        config, SizeOnlyVocabs(shapes.token_vocab, shapes.path_vocab,
                               shapes.target_vocab))
    return Trainer(config, backend)


def build_trainer(config, shapes: BenchShapes):
    """(trainer, initial training state) for the benchmark Config."""
    trainer = _make_trainer(config, shapes)
    return trainer, trainer.init_state(seed=0)


def build_eval_trainer(config, shapes: BenchShapes):
    """(trainer, sharded params) WITHOUT optimizer state — eval-only
    harnesses must not burn device memory on ~3 GB of Adam moments they
    never read."""
    import jax

    from code2vec_tpu.parallel import mesh as mesh_lib
    trainer = _make_trainer(config, shapes)
    params = mesh_lib.shard_params(trainer.backend.init(
        jax.random.PRNGKey(0)), trainer.mesh)
    return trainer, params


def random_batches(shapes: BenchShapes, n: int, seed: int = 0,
                   fill: float = 1.0):
    """``n`` synthetic host batches of uniform random indices.

    ``fill`` < 1.0 gives each example a random effective length around
    ``fill * max_contexts`` (PAD-filled tail, mask zeroed) — the realistic
    shape for wire-format measurements (JAVA14M_FILL); the default keeps
    the historical full batches the compute benchmarks are calibrated on.
    """
    import numpy as np

    from code2vec_tpu.data.reader import Batch
    rng = np.random.default_rng(seed)
    batch, contexts = shapes.batch_size, shapes.max_contexts
    out = []
    for _ in range(n):
        source = rng.integers(1, shapes.token_vocab,
                              (batch, contexts)).astype(np.int32)
        path = rng.integers(1, shapes.path_vocab,
                            (batch, contexts)).astype(np.int32)
        target = rng.integers(1, shapes.token_vocab,
                              (batch, contexts)).astype(np.int32)
        mask = np.ones((batch, contexts), np.float32)
        if fill < 1.0:
            lengths = rng.integers(
                max(1, int(fill * contexts * 0.5)),
                max(2, int(fill * contexts * 1.5)) + 1, (batch,))
            dead = np.arange(contexts)[None, :] >= lengths[:, None]
            source[dead] = 0
            path[dead] = 0
            target[dead] = 0
            mask[dead] = 0.0
        out.append(Batch(
            source=source, path=path, target=target, mask=mask,
            label=rng.integers(1, shapes.target_vocab,
                               (batch,)).astype(np.int32),
            weight=np.ones((batch,), np.float32)))
    return out


def pack_batches(batches, trainer):
    """Plane batches -> PackedBatch list for the trainer's mesh (packed
    per data shard, PAD indices from the trainer's backend). All batches
    share ONE capacity so a timed loop compiles exactly one packed
    program — per-batch capacities straddling a bucket boundary would
    bill recompiles to the measurement."""
    from code2vec_tpu.data import packed as packed_lib
    from code2vec_tpu.parallel import mesh as mesh_lib
    shards = trainer.mesh.shape[mesh_lib.DATA_AXIS]

    def pack_all(minimum):
        return [packed_lib.pack_batch(
            batch, trainer._token_pad, trainer._path_pad,
            data_shards=shards, capacity_minimum=minimum)
            for batch in batches]

    packed = pack_all(packed_lib.MIN_CAPACITY)
    caps = {p.ctx.shape[1] for p in packed}
    if len(caps) > 1:
        packed = pack_all(max(caps))
    return packed


def wire_bytes(batch) -> int:
    """Bytes/batch on the host->device wire (either format)."""
    from code2vec_tpu.data import packed as packed_lib
    return packed_lib.wire_bytes(batch)


def staged(trainer, host_batches):
    """Mesh-aware device placement via the trainer's own staging path (a
    bare jax.device_put would pin every array to device 0 and bill a
    redistribution to each timed step on multi-device meshes)."""
    return [arrays for arrays, _ in trainer.stage_batches(iter(host_batches))]
