"""Typed configuration for code2vec_tpu.

Replicates every knob of the reference ``Config`` (reference config.py:46-70)
and its file-naming contract (config.py:173-230) so existing ``.c2v`` datasets
and launch scripts drop in unchanged, and adds TPU-specific knobs (mesh shape,
compute dtype, checkpointing) that have no reference counterpart.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import os
import sys
from argparse import ArgumentParser
from typing import Optional, Iterator, Tuple, Any


@dataclasses.dataclass
class Config:
    # ---- training schedule (reference config.py:47-57) ----
    NUM_TRAIN_EPOCHS: int = 20
    SAVE_EVERY_EPOCHS: int = 1
    # 0 = per-epoch saves only. At java14m scale an epoch is ~14K steps
    # (~an hour of chip time); step-interval async saves bound the work a
    # preemption can destroy — the reference had no equivalent.
    SAVE_EVERY_N_STEPS: int = 0
    TRAIN_BATCH_SIZE: int = 1024
    TEST_BATCH_SIZE: int = 1024
    TOP_K_WORDS_CONSIDERED_DURING_PREDICTION: int = 10
    NUM_BATCHES_TO_LOG_PROGRESS: int = 100
    NUM_TRAIN_BATCHES_TO_EVALUATE: int = 1800
    READER_NUM_PARALLEL_BATCHES: int = 6
    SHUFFLE_BUFFER_SIZE: int = 10000
    CSV_BUFFER_SIZE: int = 100 * 1024 * 1024
    MAX_TO_KEEP: int = 10

    # ---- model hyper-params (reference config.py:60-70) ----
    MAX_CONTEXTS: int = 200
    MAX_TOKEN_VOCAB_SIZE: int = 1301136
    MAX_TARGET_VOCAB_SIZE: int = 261245
    MAX_PATH_VOCAB_SIZE: int = 911417
    DEFAULT_EMBEDDINGS_SIZE: int = 128
    TOKEN_EMBEDDINGS_SIZE: int = 128
    PATH_EMBEDDINGS_SIZE: int = 128
    CODE_VECTOR_SIZE: int = 384          # = context_vector_size by default
    TARGET_EMBEDDINGS_SIZE: int = 384    # = CODE_VECTOR_SIZE by default
    DROPOUT_KEEP_RATE: float = 0.75
    SEPARATE_OOV_AND_PAD: bool = False

    # ---- TPU-native knobs (no reference counterpart) ----
    # Compute dtype for the forward/backward pass. Params are always fp32;
    # 'bfloat16' casts activations/matmuls for the MXU and keeps the loss in
    # fp32. 'float32' matches reference numerics bit-closely for tests.
    COMPUTE_DTYPE: str = 'bfloat16'
    # PRNG implementation for the dropout mask. 'threefry2x32' is JAX's
    # default counter-based generator — portable across platforms, but the
    # (B, C, 3d) mask is ~131M draws/step at the java14m config, ~10% of
    # the measured train step (PERF.md). 'rbg' derives a per-step key for
    # the hardware RngBitGenerator instead — same keep-probability, a
    # different (still deterministic, seed-keyed) random stream. The
    # checkpointed key stays threefry either way; the rbg key is derived
    # inside the step, so checkpoints are unaffected by this knob.
    # DEFAULT 'rbg' per the ≥2% rule: the on-chip A/B measured 43.36 vs
    # 47.32 ms/step (-8.4%; 2026-07-31, earlier installation, PERF.md), and the
    # full-dims learning curve under rbg matches the threefry/fp32 twin
    # (accuracy_cpu_full_bf16.json: F1 0.7487 vs 0.7470). 'threefry2x32'
    # remains the portable reference behavior.
    DROPOUT_PRNG_IMPL: str = 'rbg'
    # Mesh shape: (data, model). data axis = DP (gradient psum over ICI);
    # model axis = row-sharded embedding tables + column-sharded softmax.
    MESH_DATA_AXIS_SIZE: int = -1   # -1: all devices on the data axis
    MESH_MODEL_AXIS_SIZE: int = 1
    # Learning rate for Adam (reference uses tf.train.AdamOptimizer defaults,
    # tensorflow_model.py:232 -> lr=0.001).
    LEARNING_RATE: float = 0.001
    # Storage dtype for Adam's FIRST moment (optax mu_dtype). 'bfloat16'
    # halves the first-moment HBM traffic (~1.5 GB/step read+write at
    # java14m's 384M params) in the HBM-bound update (PERF.md roofline);
    # params stay fp32 (the second moment has its own knob below).
    # DEFAULT 'bfloat16' per the
    # ≥2% rule: the on-chip A/B measured 44.89 vs 47.32 ms/step (-5.1%
    # alone; -13.4% combined with rbg dropout; 2026-07-31, earlier
    # installation, PERF.md); the equivalence twins
    # (accuracy_*bf16mu*.json) pair its F1 curve against the fp32-moment
    # runs. Changing it changes the optimizer-state dtype; resuming a
    # checkpoint written under the OTHER setting adapts automatically
    # (checkpoints.py restores mu as stored, warns, and casts to the
    # configured dtype — set --adam-mu-dtype to the stored dtype to
    # resume bit-exactly).
    ADAM_MU_DTYPE: str = 'bfloat16'
    # Storage dtype for Adam's SECOND moment (training/adam_dtypes.py).
    # The nu tree is the same-size stream as mu before its flip (~1.54 GB
    # fp32 at java14m's 384M params, read+write every step of the
    # HBM-bound dense update): 'bfloat16' halves it (~1.9 ms/step
    # analytic at the measured ~819 GB/s). Moment math stays fp32 every
    # step — only HBM storage narrows (the sqrt denominator is formed
    # after an fp32 upcast). DEFAULT 'bfloat16' per the >=2% flip rule
    # (PERF.md): the on-chip A/B measured 38.24 vs 41.10 ms/step on the
    # default recipe (-7.0%, 26,777 ex/s/chip;
    # moment_dtypes_manual_2026-07-31T0716Z.jsonl) and the learning-curve
    # twin matches — best F1 0.5606 (accuracy_cpu_full_bf16nu.json) vs
    # 0.5565/0.5566 for the bf16-mu and fp32-moment twins on the
    # identical dataset. Cross-dtype checkpoint resume adapts
    # automatically, like ADAM_MU_DTYPE (checkpoints.py).
    ADAM_NU_DTYPE: str = 'bfloat16'
    # Dtype the GRADIENTS are produced and streamed in (training/
    # trainer.py): 'bfloat16' differentiates the loss wrt the pre-cast
    # bf16 params, so the two table-grad scatter-adds and the full grad
    # tree cross HBM at half width (~1.54 GB fp32 -> 0.77 GB at java14m
    # scale, plus the eliminated bf16->fp32 cast of the table
    # cotangents). Requires COMPUTE_DTYPE='bfloat16' (enforced by
    # verify()): under bf16 compute the FORWARD is unchanged — every
    # param is cast to bf16 before use either way — and master params +
    # Adam
    # moment MATH stay fp32 (training/adam_dtypes.py upcasts before any
    # arithmetic; only storage narrows). What changes numerically is one
    # rounding of each gradient to bf16 — the standard mixed-precision
    # regime (fp32 master + bf16 grads). DEFAULT 'float32' until the
    # on-chip A/B (benchmarks/bench_moment_dtypes.py) and the
    # learning-curve twin (profile cpu_full_bf16grads) clear the >=2%
    # flip rule, like every perf knob here (PERF.md).
    GRADS_DTYPE: str = 'float32'
    # Route the TRAINING cross-entropy through the flash-style fused Pallas
    # kernel (ops/pallas_ce.py): logsumexp + label pick computed blockwise
    # over the target table, so the (B, target_vocab) logits matrix never
    # exists in HBM in either direction (~4.3 GB/step at java14m shapes).
    # Multi-device meshes use the shard_mapped variant (table row-sharded
    # over 'model', batch over 'data', online stats merged over ICI).
    # The on-chip A/B measured it NEUTRAL at java14m shapes (47.18 vs
    # 47.23 ms/step alone; +1.4% on top of the rbg+bf16-mu winner;
    # 2026-07-31, earlier installation, PERF.md) — below the ≥2% flip
    # rule, so it stays opt-in: XLA's own CE fusion already avoids most of the logits
    # round-trip. Eval/predict always materialize logits (top-k needs
    # them).
    USE_PALLAS_FUSED_CE: bool = False
    # Shard the contexts axis (the 'sequence' analog, MAX_CONTEXTS) over the
    # model mesh axis — order-free sequence parallelism for large bags: the
    # attention softmax reductions become XLA collectives (SURVEY.md §5
    # 'long-context'). Off by default (MAX_CONTEXTS=200 fits comfortably).
    SHARD_CONTEXTS: bool = False
    # Rematerialize the encode block (jax.checkpoint): the (B, C, 3d)
    # activations — gathered context embeddings, dropout output, tanh
    # input — are recomputed during the backward instead of living in HBM
    # across the loss. FLOPs-for-memory for long-context configs (large
    # MAX_CONTEXTS / big batch); pointless at C=200 where they fit easily.
    REMAT_ENCODE: bool = False
    # Layout of Adam's moment tables over the mesh. 'mirror' (default)
    # copies each parameter's own sharding: row-sharded over 'model',
    # REPLICATED along 'data' — every data shard stores the full ~3.1 GB
    # of moments at java14m scale. 'zero' (ZeRO-1-style) additionally
    # shards the three tables' moments over the data axis: per-device
    # optimizer memory drops by the data-axis size and XLA turns the
    # update into reduce-scatter/all-gather collectives it places itself.
    # Parameters stay replicated along 'data' either way (this is
    # optimizer-STATE partitioning, not ZeRO-3). Numerics are unchanged
    # (tests/test_sharding.py); requires PARAM_ROW_ALIGNMENT divisible by
    # the whole mesh size.
    OPTIMIZER_STATE_SHARDING: str = 'mirror'
    # Embedding tables are padded to a multiple of this many rows so they
    # shard evenly over any model axis that DIVIDES this value (validated at
    # Trainer construction), keeping checkpoint shapes topology-independent.
    # Padded target rows are masked out of the softmax/top-k. Changing this
    # changes checkpoint shapes — it is recorded in a checkpoint sidecar and
    # verified on restore.
    PARAM_ROW_ALIGNMENT: int = 128
    # Host input pipeline.
    READER_PREFETCH_BATCHES: int = 8
    # How many batches fit()/evaluate() stage onto the device ahead of the
    # step consuming them, so host->device transfer overlaps the previous
    # steps' compute (jax transfers are async; without staging, each
    # step's dispatch serializes behind its own upload). 0 disables.
    DEVICE_PREFETCH_BATCHES: int = 2
    # What crosses the host->device wire per batch (data/packed.py).
    # 'planes' is the v1 format: six padded arrays, 16 bytes per context
    # SLOT — at the java14m fill rate (contexts/method p50 28 of 200)
    # mostly padding. 'packed' (default) densifies each example's
    # contexts to its effective length: 12 bytes per RETAINED slot + 12
    # per example (~3-5x fewer bytes/batch at java14m shape), with a
    # jitted device-side unpack that reproduces the v1 planes
    # BIT-exactly (tests/test_packed.py), so the model and its numerics
    # are untouched. Multi-host runs fall back to 'planes'
    # (wire_format_for): per-shard capacities are data-dependent and
    # processes cannot agree on them without communication.
    BATCH_WIRE_FORMAT: str = 'packed'
    # Donate staged batch buffers to the consuming train/eval step so
    # XLA may reuse their device memory for intermediates while the
    # staging ring (DEVICE_PREFETCH_BATCHES) holds the next uploads.
    # fit()/evaluate() consume each staged batch exactly once; harnesses
    # that re-feed the same placed arrays across steps must disable this
    # (benchlib.headline_config pins it off).
    DONATE_STAGED_BATCHES: bool = True
    READER_USE_NATIVE: bool = True  # use the C++ tokenizer when available
    # Tokenize the train split once into a binary cache
    # (<data>.train.c2v.tokcache/, ~12 bytes/context on disk) and stream
    # int32 tensors for every later epoch.
    TRAIN_DATA_CACHE: bool = True
    # Use the fused Pallas encode kernel (split-TRANSFORM matmul + tanh +
    # attention scores in one VMEM pass) for the deterministic forward
    # (eval/predict) on the PLANE wire. Off by default: the 2026-07 A/B
    # on an earlier installation measured 0.99x vs XLA at the java14m
    # config (PERF.md); not measured on this chip. A TPU kernel: forced
    # on off a TPU it raises KernelRequiresTPU (ops/_pallas_common.py).
    USE_PALLAS_FUSED_ENCODE: bool = False
    # Run encode + attention straight off the packed wire
    # (ops/pallas_ragged.py): the (D, cap, 3) triples + counts feed a
    # ragged fused encoder — gather, row-split transform, tanh, score,
    # and a FuseMax-style single-pass per-example softmax + weighted sum
    # — so the (B, max_contexts) segment-scatter unpack and every dense
    # (B, C, .) intermediate disappear from the packed train/eval/
    # predict/serving programs. The deterministic forward runs the
    # Pallas kernel when the trainer's mesh is on TPUs and the jnp twin
    # on any other platform — decided once per trainer from the mesh's
    # devices and logged (training/trainer.py); training (dropout,
    # backward) runs the differentiable custom-VJP twin on the same
    # packed layout everywhere, whose recompute backward saves no
    # (B, C, .)/(D, cap, .) residuals. Outputs match the
    # unpack-then-dense path to fp32 rounding
    # (tests/test_pallas_ragged.py); dropout draws its mask over the
    # packed layout (a different seed-keyed stream, the
    # DROPOUT_PRNG_IMPL precedent). ON by default. On the v5e the kernel
    # compiles under Mosaic at every default serving-ladder shape and
    # agrees with the twin to bf16 rounding (PERF.md "Bring-up", PR 21);
    # its speed against the twin and the unfused path is not measured on
    # the chip (ROADMAP A1). --no-ragged-fusion restores the
    # unpack-then-dense (bit-exact vs planes) path.
    USE_PALLAS_RAGGED_FUSION: bool = True
    # Route the packed TRAIN step's forward AND recompute-backward
    # through the Pallas kernel pair
    # (ops/pallas_ragged.py::_ragged_kernel/_bwd_kernel). This is the
    # on-chip train flip the >=2% rule still gates: OFF until
    # scripts/flip_verdict.py reads a capture round
    # (benchmarks/bench_pallas_ragged.py train arms) clearing 1.02x;
    # not measured on the chip (ROADMAP A1). TPU only: forced on off a
    # TPU it raises KernelRequiresTPU. Inert without
    # USE_PALLAS_RAGGED_FUSION (the train step then unpacks).
    RAGGED_TRAIN_KERNEL: bool = False
    # When set, capture a jax.profiler trace of a few training steps into
    # this directory (viewable with TensorBoard/Perfetto) — the step-level
    # profiler the reference lacked (SURVEY.md §5 'Tracing / profiling').
    PROFILE_DIR: Optional[str] = None
    PROFILE_START_STEP: int = 10
    PROFILE_NUM_STEPS: int = 5
    # ---- telemetry (code2vec_tpu/telemetry/, OBSERVABILITY.md) ----
    # Master switch for the step-phase/pipeline telemetry layer: phase
    # timers (batch-wait / h2d / dispatch / sync), throughput counters,
    # staging-ring occupancy, jit-compile tracking, and the JSONL /
    # Prometheus-textfile / console exporters. Off by default: the hot
    # loop then carries only `is None` checks (measured <1% either way,
    # benchmarks/bench_telemetry_overhead.py).
    TELEMETRY: bool = False
    # Where telemetry artifacts (metrics.jsonl, metrics.prom, traces/)
    # land; None resolves next to the model artifacts like the
    # metrics_writer 'summaries' convention (telemetry/stepwatch.py).
    TELEMETRY_DIR: Optional[str] = None
    # Exporter flush cadence, in train steps. Rates (examples/sec) are
    # computed per flush window.
    TELEMETRY_FLUSH_EVERY_STEPS: int = 50
    # Minimum seconds between telemetry console progress lines.
    TELEMETRY_CONSOLE_EVERY_SECS: float = 30.0
    # On-demand jax.profiler capture: start a TELEMETRY_TRACE_NUM_STEPS
    # trace when this global step is reached (-1: disabled; the
    # TELEMETRY_TRACE_AT_STEP env var fills in when the field is unset,
    # and `touch <telemetry_dir>/TRACE_NOW` triggers a capture from a
    # LIVE run with no restart — telemetry/trace.py).
    TELEMETRY_TRACE_AT_STEP: int = -1
    TELEMETRY_TRACE_NUM_STEPS: int = 5
    # ---- per-request serving traces (telemetry/tracing.py) ----
    # Head-based sample rate in [0, 1] for the serving engine's
    # per-request span log (OBSERVABILITY.md "Per-request serving
    # traces"). 0 disables tracing entirely (no spans, no flight
    # recorder); any shed/expired/degraded/split/closed request, and any
    # request slower than TRACING_SLOW_MS, is retained regardless of the
    # rate (tail retention). -1 = UNSET: the TRACING_SAMPLE_RATE
    # environment variable fills in (same convention as
    # TELEMETRY_TRACE_AT_STEP), else the 0.01 default.
    TRACING_SAMPLE_RATE: float = -1.0
    # Tail-retention latency threshold in milliseconds: completed
    # requests slower than this are written to the span log even when
    # head sampling skipped them. 0 disables the latency tail.
    TRACING_SLOW_MS: float = 250.0
    # Flight-recorder ring capacity: the last N completed traces
    # (sampled or not) held for the flight_<event>.jsonl dumps on
    # overload bursts, canary rollback, breaker open, and close().
    TRACING_FLIGHT_TRACES: int = 256
    # ---- device-memory ledger (telemetry/memory.py) ----
    # HBM budget in bytes for the ledger's predictive admission checks:
    # an index attach or serving rollover whose predicted footprint
    # would cross it fails typed (MemoryBudgetExceeded) BEFORE
    # allocating, with a forensic oom_ledger.json dump. -1 = UNSET: the
    # HBM_BUDGET_BYTES environment variable fills in (the
    # TELEMETRY_TRACE_AT_STEP convention), else 0 = unlimited.
    HBM_BUDGET_BYTES: int = -1
    # Write a reconciled device-memory ledger snapshot
    # (memory_report.json, rendered by scripts/memory_report.py) when
    # the run's work completes (--memory-report). Live runs can instead
    # `touch <telemetry_dir>/MEM_NOW` for a snapshot with no restart.
    MEMORY_REPORT: bool = False
    # ---- training goodput plane (telemetry/goodput.py) ----
    # Per-device peak FLOP/s used as the MFU denominator (train/mfu =
    # achieved model FLOP/s over peak x device count). -1 = UNSET: the
    # DEVICE_PEAK_FLOPS environment variable fills in (the
    # TELEMETRY_TRACE_AT_STEP convention), else the device-kind table
    # in telemetry/goodput.py (known TPU generations, a nominal CPU
    # row). A device the table doesn't know is an error at telemetry
    # set-up until this is set — MFU is only as honest as its
    # denominator.
    DEVICE_PEAK_FLOPS: float = -1.0
    # Step-time anomaly watchdog threshold, in robust standard
    # deviations (MAD-scaled) above the per-shape rolling median. A
    # sustained regression past it fires goodput/anomalies_total, dumps
    # flight_step_anomaly.jsonl, and auto-triggers a profiler capture.
    # 0 disables the watchdog.
    GOODPUT_ANOMALY_SIGMA: float = 6.0
    # Minimum seconds between anomaly-triggered profiler captures, so a
    # persistently degraded run produces one trace, not hundreds.
    GOODPUT_AUTOCAPTURE_COOLDOWN_SECS: float = 600.0
    # ---- resilience (code2vec_tpu/resilience/, ROBUSTNESS.md) ----
    # Divergence guard: check the windowed losses for NaN/Inf at each
    # log-window sync (zero extra host syncs — the losses come to host
    # there anyway); on divergence rewind to the newest checkpoint and
    # skip the offending data window. On by default: with no checkpoint
    # to rewind to it degrades to abort-with-diagnostics, which still
    # beats silently training on NaN.
    DIVERGENCE_GUARD: bool = True
    # Rewinds the guard attempts before declaring the run systematically
    # divergent and aborting with a diagnostic dump.
    MAX_DIVERGENCE_REWINDS: int = 3
    # Hang watchdog deadline in seconds for the hot loop's two blocking
    # waits (next staged batch; log-window device sync). Past it the run
    # dumps all thread stacks and hard-aborts (SIGABRT) so a wedged
    # multi-host collective fails loud. 0 disables. Size it well above
    # the slowest legitimate wait — at least first-step jit compile plus
    # a full eval interval on multi-host meshes (minutes, not seconds).
    HANG_WATCHDOG_SECS: float = 0.0
    # Install SIGTERM/SIGINT handlers for the duration of train(): the
    # fit loop then exits at the next step boundary after one final
    # snapshot save, so spot-VM preemption loses at most the current
    # step. No-op when fit runs outside the main thread.
    HANDLE_PREEMPTION_SIGNALS: bool = True
    # Deterministic fault injection spec (resilience/faults.py):
    # comma-separated <point>@<trigger>=<n>, e.g.
    # 'nan_loss@step=120,sigterm@step=50'. None = UNSET, so the
    # FAULT_INJECT environment variable fills in (runs launched by
    # scripts you can't edit, like the TELEMETRY_TRACE_AT_STEP
    # convention); '' = explicitly disabled, overriding the env var
    # (the clean control arm of a fault drill).
    FAULT_INJECT: Optional[str] = None
    # ---- serving (code2vec_tpu/serving/engine.py, SERVING.md) ----
    # Batch buckets of the serving engine's warm program ladder,
    # comma-separated ascending. Every bucket is rounded up to a multiple
    # of the mesh data axis; a request stream is coalesced into the
    # smallest covering bucket. More buckets = less padding waste per
    # dispatch but more programs to pre-compile at load.
    SERVING_BATCH_BUCKETS: str = '8,64,512,1024'
    # Micro-batcher deadline: how long the dispatcher may hold the OLDEST
    # queued request while coalescing followers into one bucket. An
    # upper bound, not a fixed wait: the batch closes earlier as soon as
    # a decode slot is free (fewer batches in flight than
    # SERVING_DECODE_WORKERS) or the largest bucket is full, so the
    # delay is only spent while the engine's own pipeline is busy. 0
    # dispatches every request immediately (still bucketed + warm, just
    # unbatched).
    SERVING_MAX_DELAY_MS: float = 5.0
    # Worker threads for host-side decode (device fetch, top-k word
    # lookup, attention parsing), so device dispatch never waits on
    # Python.
    SERVING_DECODE_WORKERS: int = 2
    # Output tiers warmed at engine load, comma-separated subset of
    # {topk, attention, full, vectors} (training/trainer.py
    # PREDICT_TIERS). Fewer tiers = proportionally fewer eager compiles.
    SERVING_WARM_TIERS: str = 'topk,attention,full'
    # ---- model seam (code2vec_tpu/models/families.py, SERVING.md) ----
    # Which model this configuration names: 'code2vec' (the bag-of-
    # contexts model; DL_FRAMEWORK picks the framework of its equations),
    # 'mellum' (a decoder-only token language model served through the
    # same ServingEngine, tier 'generate'; models/decoder.py) or
    # 'minicpm_sala' (linear-attention and block-sparse layers mixed, the
    # same tier and loop; models/hybrid_decoder.py) or 'mistral4' (latent
    # attention over latent pages, a share of the routed experts and a
    # shared expert; models/latent_decoder.py).
    MODEL_FAMILY: str = 'code2vec'
    # The decoder's published config.json (hidden sizes, layer_types,
    # rope_parameters ...). Weights are the program's own seeded init
    # (LM_PARAM_SEED), made on the device in bfloat16.
    LM_CONFIG_PATH: str = ''
    # Layers of that stack this process holds, named as config.json names
    # it: a pipeline stage's cut (the first n layers). 0 = all of them.
    num_hidden_layers: int = 0
    # ... and where that cut starts in config.json's list of layers (a
    # middle stage; models/hybrid_decoder.py reads it, a mellum holds the
    # first layers).
    first_hidden_layer: int = 0
    # ... and, where the stack's layers are divided over chips, the routed
    # experts of each layer and the rows of the vocabulary this process
    # holds, named as config.json names them (models/latent_decoder.py
    # reads them: the experts from config.json's first_held_expert, the
    # vocabulary's first rows). 0 = all of them.
    n_routed_experts: int = 0
    vocab_size: int = 0
    LM_PARAM_SEED: int = 0
    # Sequences resident at once: decode rows of a step and slots of the
    # sliding layers' ring pool (serving/lm_cache.py).
    LM_MAX_SEQS: int = 16
    # Positions a page of the key/value cache holds, and the pages of the
    # full layers' pool (each page is LM_PAGE_SIZE positions in every
    # full layer).
    LM_PAGE_SIZE: int = 128
    LM_PAGE_POOL_PAGES: int = 1024
    # Longest context (prompt + generated) a generate request may ask.
    LM_MAX_CONTEXT: int = 8192
    # Prompt tokens a step may carry beside its decode rows,
    # comma-separated ascending: one program each, and one with none.
    LM_CHUNK_BUCKETS: str = '256,512,1024,2048'
    # A chunk reaches the sliding layers' attention as sub-sequences of
    # this many queries, each rebased to its own window, so that a long
    # chunk does not read keys its window excludes.
    LM_WINDOW_SUBCHUNK: int = 512
    # ---- serving resilience (SERVING.md "Overload & rollover") ----
    # Default per-request SLO deadline in milliseconds (submit's
    # deadline_ms= overrides per request; 0 = no deadline). A deadlined
    # request is shed at admission when the queue's drain estimate
    # already exceeds it, and expired (typed DeadlineExceeded) if it is
    # still queued when the deadline passes — dead work is never
    # dispatched.
    SERVING_DEADLINE_MS: float = 0.0
    # Admission-controlled front-queue bound, in ROWS queued across all
    # tiers. Submissions beyond it are shed with EngineOverloaded
    # instead of queueing unboundedly. 0 = auto (8x the top batch
    # bucket: a few in-flight bucket fills); -1 = unbounded (the
    # pre-resilience behavior).
    SERVING_QUEUE_BOUND: int = 0
    # Canaried checkpoint rollover (ServingEngine.load_params): live
    # micro-batches shadow-scored against BOTH param sets before the
    # swap decision. 0 = swap immediately, no canary.
    SERVING_CANARY_BATCHES: int = 8
    # Minimum top-1 agreement (new vs serving params, over the canaried
    # rows) for the swap; below it the rollover rolls back.
    SERVING_CANARY_AGREEMENT: float = 0.9
    # An armed canary that has not concluded after this many seconds of
    # dispatches rolls back instead of wedging later rollovers — a
    # mixed-tier engine serving only vectors traffic (submit_neighbors)
    # produces no top-1 comparisons, so without a bound the rollover
    # never decides. 0 disables the timeout.
    SERVING_CANARY_TIMEOUT_SECS: float = 300.0
    # Poll the checkpoint store every this-many seconds for a newer
    # retained step and roll it over through the canary
    # (--serve-follow-checkpoints; 0 disables). On a serving mesh the
    # poller runs at the MESH (one coordinated fleet rollover), never
    # per replica.
    SERVE_FOLLOW_CHECKPOINTS_SECS: float = 0.0
    # ---- serving mesh (code2vec_tpu/serving/mesh.py, SERVING.md) ----
    # Engine replicas behind the ONE shared front queue
    # (--mesh-replicas). 1 keeps single-replica behavior behind the
    # mesh API.
    MESH_REPLICAS: int = 1
    # Shared front-queue admission bound in ROWS across all tiers and
    # replicas (--mesh-queue-bound). 0 = auto (replicas x 8 x the top
    # batch bucket — the fleet's absorbable backlog scales with its
    # size); -1 = unbounded.
    MESH_QUEUE_BOUND: int = 0
    # Per-replica in-flight window: dispatched-but-undecoded
    # micro-batches a replica may hold before its puller stops claiming
    # queue work. The mesh's dispatch weighting knob — a canarying
    # replica runs at half this, a half-open breaker probes with 1.
    MESH_MAX_INFLIGHT: int = 2
    # Replica dispatch circuit breaker: consecutive dispatch failures
    # that weight a replica OUT of queue pulling, and how long it stays
    # out before a single half-open probe batch.
    MESH_BREAKER_THRESHOLD: int = 3
    MESH_BREAKER_COOLDOWN_SECS: float = 10.0
    # Replica placement: 'thread' = in-process engine replicas sharing
    # the trainer's warm programs; 'process' = one spawned worker
    # process per replica speaking the framed dispatch wire over a
    # pipe; 'socket' = the same wire over TCP (workers dial the mesh
    # listener — replicas can live on other machines). Worker modes
    # require a checkpointed model (workers restore params from the
    # store). SERVING.md "Serving mesh" / "Multi-host mesh".
    MESH_REPLICA_MODE: str = 'thread'
    # ---- mesh self-healing (SERVING.md "Multi-host mesh") ----
    # Worker heartbeat period in seconds (liveness DISTINCT from
    # dispatch health: a hung or partitioned worker with nothing in
    # flight is invisible to the breaker; its missing beats are not).
    # 0 disables the liveness monitor. Worker modes only.
    MESH_HEARTBEAT_SECS: float = 2.0
    # Consecutive heartbeat intervals a worker may miss before the
    # mesh marks it dead typed, kills it, and redispatches its
    # in-flight batches.
    MESH_HEARTBEAT_MISSES: int = 3
    # Supervised-restart budget: how many restarts one replica may
    # spend inside MESH_RESTART_WINDOW_SECS before it retires
    # PERMANENTLY (a flapping worker must not restart-storm). 0 =
    # never restart (first death retires).
    MESH_RESTART_LIMIT: int = 3
    MESH_RESTART_WINDOW_SECS: float = 300.0
    # First-restart backoff in seconds; doubles per attempt inside the
    # window (capped at 30s).
    MESH_RESTART_BACKOFF_SECS: float = 0.5
    # Bind address of the socket-mode mesh listener. 127.0.0.1 keeps
    # spawned-local workers loopback-only; a routable address lets
    # workers on other machines dial in (scripts/mesh_worker.py dials
    # it and the mesh ADOPTS the dial-in — SERVING.md "Elastic fleet").
    MESH_SOCKET_HOST: str = '127.0.0.1'
    # ---- elastic fleet (SERVING.md "Elastic fleet") ----
    # Per-replica device placement: partition jax.devices() into
    # disjoint slices of this many devices, one slice per replica, so
    # N replicas on one host stop time-sharing the same chips. Each
    # worker builds its own sub-mesh over its slice — the warm ladder,
    # the ragged kernel's shard_map, and the memory ledger all follow
    # the slice geometry. Must be a multiple of MESH_MODEL_AXIS_SIZE.
    # 0 (default) = off: every replica sees the full device set.
    # Worker modes only ('process'/'socket'): thread replicas share
    # the trainer's programs, which are compiled over the parent mesh.
    MESH_DEVICES_PER_REPLICA: int = 0
    # Internal plumbing for placement: comma-separated indices into
    # jax.devices() this process's mesh is built over (create_mesh).
    # The ServingMesh sets it in per-worker config overrides to pin a
    # worker onto its slice; scripts/mesh_worker.py exposes it as
    # --device-indices for orchestrator-spawned workers. '' = all.
    MESH_DEVICE_INDICES: str = ''
    # ---- SLO-driven autoscaler (serving/autoscaler.py, SERVING.md) ----
    # Fleet-size bounds for the autoscaler control loop. MAX 0
    # (default) keeps the autoscaler OFF — the fleet stays the shape
    # it was built with. MAX > 0 arms the loop: scale-up spawns (or
    # requests via hook) up to MAX, scale-down drains via retire()
    # (never a kill) down to MIN.
    AUTOSCALE_MIN_REPLICAS: int = 1
    AUTOSCALE_MAX_REPLICAS: int = 0
    # Control-loop evaluation period in seconds.
    AUTOSCALE_INTERVAL_SECS: float = 5.0
    # Scale-UP trigger: the front queue's drain estimate (queued rows
    # over the fleet's observed service rate) exceeding this many
    # seconds means the current fleet cannot absorb the backlog.
    AUTOSCALE_UP_QUEUE_SECS: float = 2.0
    # Optional second scale-UP trigger: SLO error-budget burn rate
    # (serving/slo.py) above this on BOTH the fast and slow windows.
    # 0 disables the burn leg (queue-drain only).
    AUTOSCALE_UP_BURN: float = 0.0
    # Scale-DOWN trigger: the fleet must look over-provisioned for
    # this many CONSECUTIVE seconds — the drain estimate recomputed
    # with one fewer replica stays under AUTOSCALE_DOWN_UTILIZATION x
    # AUTOSCALE_UP_QUEUE_SECS and no SLO burn alert is pending.
    AUTOSCALE_DOWN_IDLE_SECS: float = 30.0
    AUTOSCALE_DOWN_UTILIZATION: float = 0.5
    # Per-direction cooldowns: seconds after a scale-up (resp. -down)
    # before the NEXT transition in either direction is considered —
    # a new replica needs its warmup before the signals mean anything.
    AUTOSCALE_UP_COOLDOWN_SECS: float = 10.0
    AUTOSCALE_DOWN_COOLDOWN_SECS: float = 60.0
    # Flap guard: more than AUTOSCALE_FLAP_LIMIT direction REVERSALS
    # inside AUTOSCALE_FLAP_WINDOW_SECS freezes the autoscaler (no
    # transitions, autoscale/flap_freezes_total increments) until the
    # window drains — oscillating demand must not thrash the fleet.
    AUTOSCALE_FLAP_WINDOW_SECS: float = 120.0
    AUTOSCALE_FLAP_LIMIT: int = 2
    # ---- fleet observability (OBSERVABILITY.md "Fleet observability") ----
    # Worker telemetry backhaul: -1 = auto (workers enable telemetry
    # iff the parent process had it enabled at spawn, so the fleet
    # export is one decision), 1 = force on, 0 = off. With it on,
    # each heartbeat ships the worker's registry snapshot + memory-
    # ledger rollup for the replica-labeled fleet merge.
    MESH_TELEMETRY_BACKHAUL: int = -1
    # ---- SLO burn-rate monitor (serving/slo.py, SERVING.md) ----
    # Availability SLO target for the serving mesh (e.g. 0.99: sheds,
    # expiries, and failures burn the 1% error budget). 0 disables the
    # availability leg.
    SERVING_SLO_AVAILABILITY: float = 0.0
    # p99 latency SLO target in ms: delivered requests slower than
    # this burn the fixed 1% latency budget. 0 disables the latency
    # leg.
    SERVING_SLO_P99_MS: float = 0.0
    # Multiwindow burn-rate alerting: an alert needs the budget burn
    # rate over BOTH windows above SERVING_SLO_BURN_THRESHOLD (burn
    # 1.0 = spending budget exactly as fast as the SLO allows). The
    # fast window sets detection latency; the slow window keeps blips
    # from paging.
    SERVING_SLO_FAST_WINDOW_SECS: float = 60.0
    SERVING_SLO_SLOW_WINDOW_SECS: float = 600.0
    SERVING_SLO_BURN_THRESHOLD: float = 10.0
    # ---- memoization tier (serving/memo.py, SERVING.md) ----
    # Exact-tier result cache budget in bytes (--memo-cache-bytes):
    # repeated requests (keyed on the canonicalized path-context bag,
    # per tier and per k) are served at mesh admission — before
    # tokenize, the front queue, and the device. 0 disables the tier.
    # Entries are generation-keyed: a fleet rollover invalidates the
    # whole cache atomically.
    MEMO_CACHE_BYTES: int = 0
    # Semantic tier epsilon: serve a single-row neighbors query from
    # the cached result of a prior query whose code vector is within
    # this cosine distance. 0 (default) keeps the tier OFF — it trades
    # exactness for hit rate and must be rolled out gated on the
    # memo/semantic_agreement metric (SERVING.md "Memoization tier").
    MEMO_SEMANTIC_EPSILON: float = 0.0
    # ---- scenario traffic plane (code2vec_tpu/workloads/, WORKLOADS.md) ----
    # Retrieval-augmented naming blend weight (--blend-neighbor-weight):
    # submit_blended scores a candidate label as
    # (1 - w) * softmax_p + w * neighbor_vote over the union of the
    # softmax head's top-k and the attached index's top-k neighbor
    # labels. 0 short-circuits to the plain softmax path (bit-identical
    # scores); 1 ranks purely on retrieval votes. Must lie in [0, 1].
    BLEND_NEIGHBOR_WEIGHT: float = 0.5
    # ---- extractor bridge hardening (serving/extractor_bridge.py) ----
    # Per-invocation extractor timeout (--extractor-timeout): a wedged
    # JVM/parser fails the call (typed ExtractorCrash, stderr attached)
    # instead of hanging the caller forever. 0 disables the bound.
    EXTRACTOR_TIMEOUT_SECS: float = 30.0
    # ExtractorPool retries per call after a crash-class failure
    # (spawn/exit/timeout — clean "no paths" content errors are never
    # retried), with exponential backoff from EXTRACTOR_BACKOFF_SECS.
    EXTRACTOR_RETRIES: int = 2
    EXTRACTOR_BACKOFF_SECS: float = 0.1
    # Persistent extractor pool worker threads (bounded subprocess
    # concurrency for raw-source serving traffic).
    EXTRACTOR_POOL_WORKERS: int = 2
    # Circuit breaker: consecutive crashed calls (each already retried)
    # that trip it open; while open, calls fail fast with
    # ExtractorUnavailable until the cooldown elapses and a half-open
    # probe succeeds.
    EXTRACTOR_BREAKER_THRESHOLD: int = 3
    EXTRACTOR_BREAKER_COOLDOWN_SECS: float = 30.0
    # ---- embedding index (code2vec_tpu/index/, INDEX.md) ----
    # Storage dtype for exported code vectors AND the index store:
    # 'float16' halves disk + device-resident (HBM) footprint; scores
    # always accumulate in float32 on device, and recall@10 is
    # parity-tested across the two (tests/test_index.py).
    VECTORS_DTYPE: str = 'float32'
    # Index tier: 'exact' is the brute-force matmul + sharded top-k
    # (bit-for-rank exact); 'ivf' adds the k-means coarse quantizer +
    # inverted lists for corpora that outgrow exact search.
    INDEX_KIND: str = 'exact'
    # Similarity metric: 'cosine' (store rows normalized at build) or
    # raw 'dot'.
    INDEX_METRIC: str = 'cosine'
    # IVF: inverted lists probed per query. The recall/latency dial —
    # nprobe/C of the corpus is scanned. 0 picks the default (ivf.py).
    INDEX_NPROBE: int = 8
    # IVF: k-means cluster count; 0 = sqrt(N) heuristic.
    INDEX_CLUSTERS: int = 0
    # Quantized IVF tier (index/quant.py): '' serves full-precision
    # rows at INDEX_KIND; 'int8' / 'pq' store compressed codes on
    # device (int8 = 1/2, PQ = ~1/8 the bytes of f16) with an exact
    # top-R re-rank from the mmap store (INDEX.md "Quantized tier").
    INDEX_QUANT: str = ''
    # Quantized tier: exact re-rank depth R — the recall-recovery dial
    # (0 serves the quantized order raw).
    INDEX_RERANK: int = 128
    # PQ subspace count per vector; 0 = dim/4 clamped to a divisor.
    INDEX_PQ_M: int = 0
    # Live inserts: append-segment page size in rows (each segment is
    # a fixed-shape sidecar probed alongside the base lists).
    INDEX_SEGMENT_ROWS: int = 4096
    # Auto-compaction threshold: fold append segments into the base
    # CSR when their count passes this; 0 = manual compaction only.
    INDEX_COMPACT_SEGMENTS: int = 8
    # Neighbors returned per query by the serving/CLI paths, and the k
    # the index warm-compiles at load.
    INDEX_NEIGHBORS_K: int = 10
    # Model backend: 'flax' (nn.Module) or 'jax' (pure-pytree functional).
    # Mirrors the reference's two swappable backends (keras/tensorflow),
    # selected at runtime (reference code2vec.py:7-13).
    DL_FRAMEWORK: str = 'flax'

    # ---- run-mode flags (filled from CLI; reference config.py:72-87) ----
    PREDICT: bool = False
    # Source file the interactive shell (re)reads each turn. The
    # reference hardcodes Input.java (interactive_predict.py:8); making
    # it a flag lets the SAME REPL serve the C# frontend — the extractor
    # dispatches by file extension, so `--input-file Input.cs` predicts
    # over Roslyn-kind paths with a C#-trained model.
    PREDICT_INPUT_PATH: str = 'Input.java'
    MODEL_SAVE_PATH: Optional[str] = None
    MODEL_LOAD_PATH: Optional[str] = None
    TRAIN_DATA_PATH_PREFIX: Optional[str] = None
    TEST_DATA_PATH: str = ''
    RELEASE: bool = False
    EXPORT_CODE_VECTORS: bool = False
    # Offline corpus embedding (serving/bulk.py): stream this .c2v file
    # through the 'vectors'-tier predict program and write one code
    # vector per kept example to <file>.vectors.
    BULK_VECTORS_PATH: Optional[str] = None
    # Index build source (index/service.py): a .c2v corpus (streamed
    # through the vectors tier, no text round-trip), a .vectors text
    # export, or a word2vec text file (--export_vocab_vectors output —
    # the index then serves nearest-method-NAME queries).
    BUILD_INDEX_FROM: Optional[str] = None
    # Where the index directory lives; None derives <source>.vecindex
    # on build and is required for --query-neighbors.
    INDEX_PATH: Optional[str] = None
    # Batch neighbor queries: stream this .c2v file through the vectors
    # tier + index lookup and write <file>.neighbors.jsonl.
    QUERY_NEIGHBORS_PATH: Optional[str] = None
    SAVE_W2V: Optional[str] = None
    SAVE_T2V: Optional[str] = None
    # One-flag parity export of BOTH vocab embedding tables in word2vec
    # text format: <prefix>.tokens.txt + <prefix>.targets.txt
    # (reference --save_w2v/--save_t2v, model_base.py:176-182).
    EXPORT_VOCAB_VECTORS: Optional[str] = None
    VERBOSE_MODE: int = 1
    LOGS_PATH: Optional[str] = None
    USE_TENSORBOARD: bool = False

    # ---- filled by the model lifecycle (reference config.py:130-132) ----
    NUM_TRAIN_EXAMPLES: int = 0
    NUM_TEST_EXAMPLES: int = 0

    _logger: Optional[logging.Logger] = dataclasses.field(
        default=None, repr=False, compare=False)

    # ------------------------------------------------------------------ CLI
    @classmethod
    def arguments_parser(cls) -> ArgumentParser:
        """CLI surface-compatible with the reference (config.py:11-44)."""
        parser = ArgumentParser(prog='code2vec_tpu')
        parser.add_argument('-d', '--data', dest='data_path', required=False,
                            help='path prefix of the preprocessed dataset')
        parser.add_argument('-te', '--test', dest='test_path', metavar='FILE',
                            required=False, default='',
                            help='path to the test/validation .c2v file')
        parser.add_argument('-s', '--save', dest='save_path', metavar='FILE',
                            required=False, help='path to save the model to')
        parser.add_argument('-w2v', '--save_word2v', dest='save_w2v',
                            metavar='FILE', required=False,
                            help='save token embeddings in word2vec format')
        parser.add_argument('-t2v', '--save_target2v', dest='save_t2v',
                            metavar='FILE', required=False,
                            help='save target embeddings in word2vec format')
        parser.add_argument('-l', '--load', dest='load_path', metavar='FILE',
                            required=False, help='path to load the model from')
        parser.add_argument('--export_code_vectors', action='store_true',
                            help='export code vectors for the given examples')
        parser.add_argument('--release', action='store_true',
                            help='strip optimizer state from a loaded model '
                                 'for a smaller artifact')
        parser.add_argument('--predict', action='store_true',
                            help='run the interactive prediction shell')
        parser.add_argument('--input-file', dest='predict_input_path',
                            default=None, metavar='PATH',
                            help='source file the prediction shell reads '
                                 '(.java or .cs; default Input.java)')
        parser.add_argument('-fw', '--framework', dest='dl_framework',
                            choices=['flax', 'jax'], default='flax',
                            help='model backend to use')
        parser.add_argument('-v', '--verbose', dest='verbose_mode', type=int,
                            default=1, help='verbosity in {0,1,2}')
        parser.add_argument('-lp', '--logs-path', dest='logs_path',
                            metavar='FILE', required=False,
                            help='file to mirror logs into')
        parser.add_argument('-tb', '--tensorboard', dest='use_tensorboard',
                            action='store_true',
                            help='write metric summaries during training')
        parser.add_argument('--dtype', dest='compute_dtype',
                            choices=['bfloat16', 'float32'], default=None,
                            help='compute dtype for the forward/backward pass')
        parser.add_argument('--mesh', dest='mesh', default=None,
                            help='mesh shape as DATAxMODEL, e.g. 4x2')
        parser.add_argument('--batch-size', dest='batch_size', type=int,
                            default=None, help='override TRAIN_BATCH_SIZE')
        parser.add_argument('--epochs', dest='epochs', type=int, default=None,
                            help='override NUM_TRAIN_EPOCHS')
        parser.add_argument('--no-data-cache', dest='no_data_cache',
                            action='store_true',
                            help='disable the binary token cache for the '
                                 'train split')
        parser.add_argument('--profile', dest='profile_dir', default=None,
                            metavar='DIR',
                            help='capture a jax.profiler trace of a few '
                                 'train steps into DIR')
        parser.add_argument('--save-every-steps', dest='save_every_steps',
                            type=int, default=None, metavar='N',
                            help='additionally checkpoint every N train '
                                 'steps (async), bounding preemption loss')
        parser.add_argument('--dropout-prng', dest='dropout_prng_impl',
                            choices=['threefry2x32', 'rbg'], default=None,
                            help='PRNG for the dropout mask; rbg uses the '
                                 'hardware generator (PERF.md)')
        parser.add_argument('--adam-mu-dtype', dest='adam_mu_dtype',
                            choices=['float32', 'bfloat16'], default=None,
                            help='storage dtype for Adam\'s first moment')
        parser.add_argument('--adam-nu-dtype', dest='adam_nu_dtype',
                            choices=['float32', 'bfloat16'], default=None,
                            help='storage dtype for Adam\'s second moment '
                                 '(training/adam_dtypes.py, PERF.md)')
        parser.add_argument('--grads-dtype', dest='grads_dtype',
                            choices=['float32', 'bfloat16'], default=None,
                            help='gradient stream dtype; bfloat16 keeps '
                                 'the table-grad scatters and grad tree '
                                 'in bf16 (fp32 master params + fp32 '
                                 'moment math, PERF.md)')
        parser.add_argument('--fused-ce', dest='fused_ce',
                            action='store_true',
                            help='train-time CE via the flash-style fused '
                                 'Pallas kernel: no (B, V) logits in HBM '
                                 '(ops/pallas_ce.py, PERF.md)')
        parser.add_argument('--ragged-fusion', dest='ragged_fusion',
                            action='store_true',
                            help='fuse encode + attention straight off '
                                 'the packed wire: no device-side '
                                 'unpack, no dense (B, C, .) '
                                 'intermediates (ops/pallas_ragged.py, '
                                 'PERF.md; the default since the '
                                 'custom-VJP backward landed)')
        parser.add_argument('--no-ragged-fusion', dest='no_ragged_fusion',
                            action='store_true',
                            help='restore the unpack-then-dense packed '
                                 'path (bit-exact vs the plane wire)')
        parser.add_argument('--ragged-train-kernel',
                            dest='ragged_train_kernel',
                            action='store_true',
                            help='run the packed TRAIN step through the '
                                 'Pallas forward+backward kernel pair '
                                 'on TPU (pending the >=2% flip '
                                 'verdict, scripts/flip_verdict.py)')
        parser.add_argument('--remat-encode', dest='remat_encode',
                            action='store_true',
                            help='recompute encode activations in the '
                                 'backward (jax.checkpoint) — memory '
                                 'headroom for long-context configs')
        parser.add_argument('--wire-format', dest='wire_format',
                            choices=['planes', 'packed'], default=None,
                            help='host->device batch wire format: packed '
                                 'densifies ragged contexts (~3-5x fewer '
                                 'bytes/batch, bit-identical batches after '
                                 'the device-side unpack; data/packed.py)')
        parser.add_argument('--device-prefetch', dest='device_prefetch',
                            type=int, default=None, metavar='N',
                            help='staging-ring depth: batches placed on '
                                 'device ahead of the consuming step '
                                 '(DEVICE_PREFETCH_BATCHES; 0 disables)')
        parser.add_argument('--telemetry', dest='telemetry',
                            action='store_true',
                            help='enable the telemetry layer: step-phase '
                                 'timers, throughput counters, JSONL + '
                                 'Prometheus exporters (OBSERVABILITY.md)')
        parser.add_argument('--telemetry-dir', dest='telemetry_dir',
                            default=None, metavar='DIR',
                            help='directory for telemetry artifacts '
                                 '(default: next to the model artifacts)')
        parser.add_argument('--trace-at-step', dest='trace_at_step',
                            type=int, default=None, metavar='N',
                            help='capture an on-demand jax.profiler trace '
                                 'when global step N is reached (implies '
                                 '--telemetry; live runs can instead touch '
                                 '<telemetry_dir>/TRACE_NOW)')
        parser.add_argument('--device-peak-flops',
                            dest='device_peak_flops',
                            type=float, default=None, metavar='FLOPS',
                            help='per-device peak FLOP/s used as the '
                                 'MFU denominator (train/mfu); unset '
                                 'falls back to the DEVICE_PEAK_FLOPS '
                                 'env var, then a device-kind table '
                                 '(telemetry/goodput.py)')
        parser.add_argument('--memory-report', dest='memory_report',
                            action='store_true',
                            help='write a reconciled device-memory '
                                 'ledger snapshot (memory_report.json) '
                                 'when the run completes; render with '
                                 'scripts/memory_report.py '
                                 '(OBSERVABILITY.md)')
        parser.add_argument('--hbm-budget-bytes', dest='hbm_budget_bytes',
                            type=int, default=None, metavar='BYTES',
                            help='HBM budget for the memory ledger\'s '
                                 'predictive admission checks: index '
                                 'attaches / serving rollovers that '
                                 'would cross it fail typed before '
                                 'allocating (0 = unlimited; the '
                                 'HBM_BUDGET_BYTES env var fills in '
                                 'when unset)')
        parser.add_argument('--fault-inject', dest='fault_inject',
                            default=None, metavar='SPEC',
                            help='deterministic fault injection: '
                                 'comma-separated <point>@<trigger>=<n> '
                                 '(e.g. nan_loss@step=120); the '
                                 'FAULT_INJECT env var fills in when '
                                 'unset (ROBUSTNESS.md)')
        parser.add_argument('--watchdog-secs', dest='watchdog_secs',
                            type=float, default=None, metavar='S',
                            help='hang-watchdog deadline for the hot '
                                 "loop's blocking waits; past it the run "
                                 'dumps thread stacks and aborts '
                                 '(0 disables; ROBUSTNESS.md)')
        parser.add_argument('--max-divergence-rewinds',
                            dest='max_divergence_rewinds', type=int,
                            default=None, metavar='N',
                            help='rewind budget of the divergence guard '
                                 'before the run aborts with diagnostics')
        parser.add_argument('--no-divergence-guard',
                            dest='no_divergence_guard', action='store_true',
                            help='disable the NaN/Inf loss-window guard')
        parser.add_argument('--serving-buckets', dest='serving_buckets',
                            default=None, metavar='B1,B2,...',
                            help='batch buckets of the serving engine\'s '
                                 'warm program ladder '
                                 '(SERVING_BATCH_BUCKETS; SERVING.md)')
        parser.add_argument('--serving-max-delay-ms',
                            dest='serving_max_delay_ms', type=float,
                            default=None, metavar='MS',
                            help='micro-batcher coalescing deadline: max '
                                 'added latency while batching concurrent '
                                 'requests; a batch closes earlier once a '
                                 'decode worker is free '
                                 '(0 = dispatch immediately)')
        parser.add_argument('--serving-deadline-ms',
                            dest='serving_deadline_ms', type=float,
                            default=None, metavar='MS',
                            help='default per-request SLO deadline: '
                                 'requests are shed at admission when '
                                 'the queue cannot drain in time, and '
                                 'expired instead of dispatched once '
                                 'past it (0 = none; SERVING.md)')
        parser.add_argument('--serving-queue-bound',
                            dest='serving_queue_bound', type=int,
                            default=None, metavar='ROWS',
                            help='admission-controlled front-queue '
                                 'bound in rows; excess submissions '
                                 'are shed with a typed error (0 = '
                                 'auto, -1 = unbounded; SERVING.md)')
        parser.add_argument('--mesh-replicas', dest='mesh_replicas',
                            type=int, default=None, metavar='N',
                            help='serving-engine replicas behind the '
                                 'shared mesh front queue '
                                 '(MESH_REPLICAS; SERVING.md "Serving '
                                 'mesh")')
        parser.add_argument('--mesh-queue-bound', dest='mesh_queue_bound',
                            type=int, default=None, metavar='ROWS',
                            help='shared mesh front-queue admission '
                                 'bound in rows across all replicas '
                                 '(0 = auto: replicas x 8 x top '
                                 'bucket, -1 = unbounded; SERVING.md)')
        parser.add_argument('--memo-cache-bytes', dest='memo_cache_bytes',
                            type=int, default=None, metavar='BYTES',
                            help='exact-tier memoization cache budget '
                                 'in bytes — repeated requests are '
                                 'served before the queue and the '
                                 'device (MEMO_CACHE_BYTES; 0 '
                                 'disables; SERVING.md "Memoization '
                                 'tier")')
        parser.add_argument('--blend-neighbor-weight',
                            dest='blend_neighbor_weight', type=float,
                            default=None, metavar='W',
                            help='retrieval-augmented naming blend '
                                 'weight in [0, 1] — neighbor-vote '
                                 'share in submit_blended scoring '
                                 '(BLEND_NEIGHBOR_WEIGHT; 0 = pure '
                                 'softmax; WORKLOADS.md "Retrieval-'
                                 'augmented naming")')
        parser.add_argument('--mesh-replica-mode',
                            dest='mesh_replica_mode',
                            choices=['thread', 'process', 'socket'],
                            default=None,
                            help='replica placement: in-process engine '
                                 'threads (shared warm programs), one '
                                 'worker process per replica on the '
                                 'framed dispatch wire over a pipe, or '
                                 'the same wire over TCP — workers '
                                 'dial the mesh listener, so replicas '
                                 'can live on other machines '
                                 '(SERVING.md "Multi-host mesh")')
        parser.add_argument('--serve-follow-checkpoints',
                            dest='serve_follow_checkpoints', type=float,
                            default=None, metavar='SECS',
                            help='poll the checkpoint store every SECS '
                                 'for newer steps and roll them into '
                                 'the live serving engine through the '
                                 'canary (zero-downtime rollover; '
                                 'SERVING.md)')
        parser.add_argument('--extractor-timeout',
                            dest='extractor_timeout_secs', type=float,
                            default=None, metavar='SECS',
                            help='per-invocation extractor timeout: a '
                                 'wedged extractor fails the call with '
                                 'its stderr instead of hanging the '
                                 'caller (0 disables; SERVING.md)')
        parser.add_argument('--bulk-vectors', dest='bulk_vectors',
                            default=None, metavar='FILE.c2v',
                            help='stream a whole .c2v corpus through the '
                                 'vectors-only predict program and write '
                                 'FILE.c2v.vectors (offline embedding '
                                 'export; serving/bulk.py)')
        parser.add_argument('--vectors-dtype', dest='vectors_dtype',
                            choices=['float32', 'float16'], default=None,
                            help='storage dtype for exported code vectors '
                                 'and the index store (float16 halves '
                                 'disk + HBM; INDEX.md)')
        parser.add_argument('--export_vocab_vectors',
                            dest='export_vocab_vectors', default=None,
                            metavar='PREFIX',
                            help='write BOTH vocab embedding tables in '
                                 'word2vec text format: PREFIX.tokens.txt '
                                 '+ PREFIX.targets.txt (one-flag parity '
                                 'with --save_w2v/--save_t2v)')
        parser.add_argument('--build-index', dest='build_index',
                            default=None, metavar='SOURCE',
                            help='build a k-NN index from SOURCE: a .c2v '
                                 'corpus (streamed through the vectors '
                                 'tier), a .vectors export, or a word2vec '
                                 'text file (code2vec_tpu/index/, '
                                 'INDEX.md)')
        parser.add_argument('--index-path', dest='index_path',
                            default=None, metavar='DIR',
                            help='index directory (default on build: '
                                 '<source>.vecindex; required for '
                                 '--query-neighbors)')
        parser.add_argument('--query-neighbors', dest='query_neighbors',
                            default=None, metavar='FILE.c2v',
                            help='stream a .c2v file through the vectors '
                                 'tier + index lookup and write '
                                 'FILE.neighbors.jsonl (one query per '
                                 'kept example)')
        parser.add_argument('--index-kind', dest='index_kind',
                            choices=['exact', 'ivf'], default=None,
                            help='index tier: exact brute-force or IVF '
                                 'approximate (INDEX.md)')
        parser.add_argument('--index-metric', dest='index_metric',
                            choices=['cosine', 'dot'], default=None,
                            help='similarity metric of the index store')
        parser.add_argument('--nprobe', dest='index_nprobe', type=int,
                            default=None, metavar='N',
                            help='IVF inverted lists probed per query '
                                 '(the recall/latency dial)')
        parser.add_argument('--index-clusters', dest='index_clusters',
                            type=int, default=None, metavar='C',
                            help='IVF k-means cluster count (0 = sqrt(N))')
        parser.add_argument('--neighbors-k', dest='index_neighbors_k',
                            type=int, default=None, metavar='K',
                            help='neighbors returned per query')
        parser.add_argument('--index-quant', dest='index_quant',
                            choices=['off', 'int8', 'pq'], default=None,
                            help='quantized IVF tier: int8 or product-'
                                 'quantized device codes + exact '
                                 're-rank (INDEX.md "Quantized tier")')
        parser.add_argument('--index-rerank', dest='index_rerank',
                            type=int, default=None, metavar='R',
                            help='exact re-rank depth of the quantized '
                                 'tier (0 = quantized order only)')
        parser.add_argument('--index-pq-m', dest='index_pq_m',
                            type=int, default=None, metavar='M',
                            help='PQ subspaces per vector (0 = dim/4)')
        parser.add_argument('--index-segment-rows',
                            dest='index_segment_rows', type=int,
                            default=None, metavar='N',
                            help='append-segment page size (rows) for '
                                 'live index inserts')
        parser.add_argument('--index-compact-segments',
                            dest='index_compact_segments', type=int,
                            default=None, metavar='S',
                            help='auto-compact after S append segments '
                                 '(0 = manual compaction only)')
        parser.add_argument('--opt-state-sharding',
                            dest='opt_state_sharding',
                            choices=['mirror', 'zero'], default=None,
                            help="Adam moment layout: 'mirror' copies the "
                                 "param sharding (replicated along data), "
                                 "'zero' shards moments over the whole "
                                 'mesh (ZeRO-1-style)')
        return parser

    def load_from_args(self, args=None) -> 'Config':
        parsed = self.arguments_parser().parse_args(args)
        self.PREDICT = parsed.predict
        if parsed.predict_input_path:
            self.PREDICT_INPUT_PATH = parsed.predict_input_path
        self.MODEL_SAVE_PATH = parsed.save_path
        self.MODEL_LOAD_PATH = parsed.load_path
        self.TRAIN_DATA_PATH_PREFIX = parsed.data_path
        self.TEST_DATA_PATH = parsed.test_path or ''
        self.RELEASE = parsed.release
        self.EXPORT_CODE_VECTORS = parsed.export_code_vectors
        self.SAVE_W2V = parsed.save_w2v
        self.SAVE_T2V = parsed.save_t2v
        self.VERBOSE_MODE = parsed.verbose_mode
        self.LOGS_PATH = parsed.logs_path
        self.DL_FRAMEWORK = parsed.dl_framework or 'flax'
        self.USE_TENSORBOARD = parsed.use_tensorboard
        if parsed.compute_dtype:
            self.COMPUTE_DTYPE = parsed.compute_dtype
        if parsed.mesh:
            try:
                data_sz, model_sz = parsed.mesh.lower().split('x')
                self.MESH_DATA_AXIS_SIZE = int(data_sz)
                self.MESH_MODEL_AXIS_SIZE = int(model_sz)
            except ValueError:
                raise ValueError(
                    "--mesh must look like DATAxMODEL (e.g. '4x2'), got %r"
                    % parsed.mesh)
        if parsed.batch_size:
            self.TRAIN_BATCH_SIZE = parsed.batch_size
            self.TEST_BATCH_SIZE = parsed.batch_size
        if parsed.epochs:
            self.NUM_TRAIN_EPOCHS = parsed.epochs
        if parsed.no_data_cache:
            self.TRAIN_DATA_CACHE = False
        if parsed.profile_dir:
            self.PROFILE_DIR = parsed.profile_dir
        if parsed.save_every_steps is not None:
            self.SAVE_EVERY_N_STEPS = parsed.save_every_steps
        if parsed.dropout_prng_impl:
            self.DROPOUT_PRNG_IMPL = parsed.dropout_prng_impl
        if parsed.adam_mu_dtype:
            self.ADAM_MU_DTYPE = parsed.adam_mu_dtype
        if parsed.adam_nu_dtype:
            self.ADAM_NU_DTYPE = parsed.adam_nu_dtype
        if parsed.grads_dtype:
            self.GRADS_DTYPE = parsed.grads_dtype
        if parsed.fused_ce:
            self.USE_PALLAS_FUSED_CE = True
        if parsed.ragged_fusion:
            self.USE_PALLAS_RAGGED_FUSION = True
        if parsed.no_ragged_fusion:
            self.USE_PALLAS_RAGGED_FUSION = False
        if parsed.ragged_train_kernel:
            self.RAGGED_TRAIN_KERNEL = True
        if parsed.remat_encode:
            self.REMAT_ENCODE = True
        if parsed.opt_state_sharding:
            self.OPTIMIZER_STATE_SHARDING = parsed.opt_state_sharding
        if parsed.wire_format:
            self.BATCH_WIRE_FORMAT = parsed.wire_format
        if parsed.device_prefetch is not None:
            self.DEVICE_PREFETCH_BATCHES = parsed.device_prefetch
        if parsed.telemetry:
            self.TELEMETRY = True
        if parsed.telemetry_dir:
            self.TELEMETRY_DIR = parsed.telemetry_dir
        if parsed.trace_at_step is not None:
            self.TELEMETRY_TRACE_AT_STEP = parsed.trace_at_step
            self.TELEMETRY = True  # a trace request implies the layer
        elif self.TELEMETRY_TRACE_AT_STEP < 0:
            # the env var is for runs launched by scripts you can't edit
            # (OBSERVABILITY.md) — so it must imply the telemetry layer
            # exactly like the flag does, or it is silently inert
            try:
                env_step = int(os.environ.get('TELEMETRY_TRACE_AT_STEP',
                                              '-1'))
            except ValueError:
                env_step = -1
            if env_step >= 0:
                self.TELEMETRY_TRACE_AT_STEP = env_step
                self.TELEMETRY = True
        if parsed.device_peak_flops is not None:
            self.DEVICE_PEAK_FLOPS = parsed.device_peak_flops
        if parsed.memory_report:
            self.MEMORY_REPORT = True
        if parsed.hbm_budget_bytes is not None:
            self.HBM_BUDGET_BYTES = parsed.hbm_budget_bytes
        if parsed.fault_inject is not None:
            # an explicit --fault-inject '' DISABLES injection even when
            # the env var is set (the control arm of a drill)
            self.FAULT_INJECT = parsed.fault_inject
        elif self.FAULT_INJECT is None:
            # env-var fallback, same rationale as TELEMETRY_TRACE_AT_STEP:
            # fault drills on runs whose launch scripts you can't edit
            self.FAULT_INJECT = os.environ.get('FAULT_INJECT')
        if parsed.watchdog_secs is not None:
            self.HANG_WATCHDOG_SECS = parsed.watchdog_secs
        if parsed.max_divergence_rewinds is not None:
            self.MAX_DIVERGENCE_REWINDS = parsed.max_divergence_rewinds
        if parsed.no_divergence_guard:
            self.DIVERGENCE_GUARD = False
        if parsed.serving_buckets:
            self.SERVING_BATCH_BUCKETS = parsed.serving_buckets
        if parsed.serving_max_delay_ms is not None:
            self.SERVING_MAX_DELAY_MS = parsed.serving_max_delay_ms
        if parsed.serving_deadline_ms is not None:
            self.SERVING_DEADLINE_MS = parsed.serving_deadline_ms
        if parsed.serving_queue_bound is not None:
            self.SERVING_QUEUE_BOUND = parsed.serving_queue_bound
        if parsed.mesh_replicas is not None:
            self.MESH_REPLICAS = parsed.mesh_replicas
        if parsed.mesh_queue_bound is not None:
            self.MESH_QUEUE_BOUND = parsed.mesh_queue_bound
        if parsed.memo_cache_bytes is not None:
            self.MEMO_CACHE_BYTES = parsed.memo_cache_bytes
        if parsed.blend_neighbor_weight is not None:
            self.BLEND_NEIGHBOR_WEIGHT = parsed.blend_neighbor_weight
        if parsed.mesh_replica_mode:
            self.MESH_REPLICA_MODE = parsed.mesh_replica_mode
        if parsed.serve_follow_checkpoints is not None:
            self.SERVE_FOLLOW_CHECKPOINTS_SECS = \
                parsed.serve_follow_checkpoints
        if parsed.extractor_timeout_secs is not None:
            self.EXTRACTOR_TIMEOUT_SECS = parsed.extractor_timeout_secs
        if parsed.bulk_vectors:
            self.BULK_VECTORS_PATH = parsed.bulk_vectors
        if parsed.vectors_dtype:
            self.VECTORS_DTYPE = parsed.vectors_dtype
        if parsed.export_vocab_vectors:
            self.EXPORT_VOCAB_VECTORS = parsed.export_vocab_vectors
        if parsed.build_index:
            self.BUILD_INDEX_FROM = parsed.build_index
        if parsed.index_path:
            self.INDEX_PATH = parsed.index_path
        if parsed.query_neighbors:
            self.QUERY_NEIGHBORS_PATH = parsed.query_neighbors
        if parsed.index_kind:
            self.INDEX_KIND = parsed.index_kind
        if parsed.index_metric:
            self.INDEX_METRIC = parsed.index_metric
        if parsed.index_nprobe is not None:
            self.INDEX_NPROBE = parsed.index_nprobe
        if parsed.index_clusters is not None:
            self.INDEX_CLUSTERS = parsed.index_clusters
        if parsed.index_neighbors_k is not None:
            self.INDEX_NEIGHBORS_K = parsed.index_neighbors_k
        if parsed.index_quant is not None:
            self.INDEX_QUANT = ('' if parsed.index_quant == 'off'
                                else parsed.index_quant)
        if parsed.index_rerank is not None:
            self.INDEX_RERANK = parsed.index_rerank
        if parsed.index_pq_m is not None:
            self.INDEX_PQ_M = parsed.index_pq_m
        if parsed.index_segment_rows is not None:
            self.INDEX_SEGMENT_ROWS = parsed.index_segment_rows
        if parsed.index_compact_segments is not None:
            self.INDEX_COMPACT_SEGMENTS = parsed.index_compact_segments
        return self

    # ------------------------------------------------------- derived props
    @property
    def context_vector_size(self) -> int:
        """Concatenation of source-token, path and target-token embeddings
        (reference config.py:143-147)."""
        return self.PATH_EMBEDDINGS_SIZE + 2 * self.TOKEN_EMBEDDINGS_SIZE

    @property
    def is_training(self) -> bool:
        return bool(self.TRAIN_DATA_PATH_PREFIX)

    @property
    def is_loading(self) -> bool:
        return bool(self.MODEL_LOAD_PATH)

    @property
    def is_saving(self) -> bool:
        return bool(self.MODEL_SAVE_PATH)

    @property
    def is_testing(self) -> bool:
        return bool(self.TEST_DATA_PATH)

    @property
    def train_steps_per_epoch(self) -> int:
        return (math.ceil(self.NUM_TRAIN_EXAMPLES / self.TRAIN_BATCH_SIZE)
                if self.TRAIN_BATCH_SIZE else 0)

    @property
    def test_steps(self) -> int:
        return (math.ceil(self.NUM_TEST_EXAMPLES / self.TEST_BATCH_SIZE)
                if self.TEST_BATCH_SIZE else 0)

    def data_path(self, is_evaluating: bool = False) -> Optional[str]:
        return self.TEST_DATA_PATH if is_evaluating else self.train_data_path

    def batch_size(self, is_evaluating: bool = False) -> int:
        return self.TEST_BATCH_SIZE if is_evaluating else self.TRAIN_BATCH_SIZE

    @property
    def serving_batch_buckets(self) -> Tuple[int, ...]:
        """Parsed, sorted SERVING_BATCH_BUCKETS (serving/engine.py rounds
        them up to the mesh data axis at engine construction)."""
        try:
            buckets = tuple(sorted(
                int(part) for part in
                str(self.SERVING_BATCH_BUCKETS).split(',') if part.strip()))
        except ValueError:
            raise ValueError(
                'SERVING_BATCH_BUCKETS must be comma-separated ints, got '
                '%r' % self.SERVING_BATCH_BUCKETS)
        if not buckets or any(bucket < 1 for bucket in buckets):
            raise ValueError(
                'SERVING_BATCH_BUCKETS needs at least one bucket >= 1, '
                'got %r' % self.SERVING_BATCH_BUCKETS)
        return buckets

    @property
    def lm_chunk_buckets(self) -> Tuple[int, ...]:
        """Parsed, sorted LM_CHUNK_BUCKETS."""
        try:
            buckets = tuple(sorted(
                int(part) for part in
                str(self.LM_CHUNK_BUCKETS).split(',') if part.strip()))
        except ValueError:
            raise ValueError('LM_CHUNK_BUCKETS must be comma-separated '
                             'ints, got %r' % self.LM_CHUNK_BUCKETS)
        if not buckets or any(bucket < 1 for bucket in buckets):
            raise ValueError('LM_CHUNK_BUCKETS needs at least one bucket '
                             '>= 1, got %r' % self.LM_CHUNK_BUCKETS)
        return buckets

    @property
    def serving_warm_tiers(self) -> Tuple[str, ...]:
        """Parsed SERVING_WARM_TIERS (validated against PREDICT_TIERS in
        verify() and at engine construction)."""
        return tuple(part.strip()
                     for part in str(self.SERVING_WARM_TIERS).split(',')
                     if part.strip())

    @property
    def tracing_sample_rate(self) -> float:
        """Resolved head-sampling rate for per-request serving traces:
        the TRACING_SAMPLE_RATE field when set (>= 0), else the
        environment variable of the same name, else 0.01 — clamped to
        [0, 1]."""
        rate = self.TRACING_SAMPLE_RATE
        if rate < 0:
            try:
                rate = float(os.environ.get('TRACING_SAMPLE_RATE', 0.01))
            except ValueError:
                raise ValueError(
                    'TRACING_SAMPLE_RATE env var must be a float, got %r'
                    % os.environ.get('TRACING_SAMPLE_RATE'))
        return max(0.0, min(1.0, rate))

    def wire_format_for(self, process_count: int) -> str:
        """The EFFECTIVE batch wire format for a run of ``process_count``
        hosts. Multi-host runs always use 'planes': the packed format's
        per-shard capacity is data-dependent per batch, and processes
        cannot agree on one global shape without a host round-trip."""
        if process_count > 1:
            return 'planes'
        return self.BATCH_WIRE_FORMAT

    # -------------------------------------- file-naming contract (parity)
    @property
    def train_data_path(self) -> Optional[str]:
        if not self.is_training:
            return None
        return '{}.train.c2v'.format(self.TRAIN_DATA_PATH_PREFIX)

    @property
    def word_freq_dict_path(self) -> Optional[str]:
        if not self.is_training:
            return None
        return '{}.dict.c2v'.format(self.TRAIN_DATA_PATH_PREFIX)

    @classmethod
    def get_vocabularies_path_from_model_path(cls, model_file_path: str) -> str:
        """``dictionaries.bin`` sidecar next to the model
        (reference config.py:191-194)."""
        return os.path.join(os.path.dirname(model_file_path), 'dictionaries.bin')

    @classmethod
    def get_entire_model_path(cls, model_path: str) -> str:
        return model_path + '__entire-model'

    @classmethod
    def get_model_weights_path(cls, model_path: str) -> str:
        return model_path + '__only-weights'

    @classmethod
    def get_step_snapshots_path(cls, model_path: str) -> str:
        """Step-interval preemption snapshots (SAVE_EVERY_N_STEPS)."""
        return model_path + '__step-snapshots'

    @property
    def model_load_dir(self) -> str:
        return os.path.dirname(self.MODEL_LOAD_PATH)

    @property
    def entire_model_load_path(self) -> Optional[str]:
        return self.get_entire_model_path(self.MODEL_LOAD_PATH) if self.is_loading else None

    @property
    def model_weights_load_path(self) -> Optional[str]:
        return self.get_model_weights_path(self.MODEL_LOAD_PATH) if self.is_loading else None

    @property
    def entire_model_save_path(self) -> Optional[str]:
        return self.get_entire_model_path(self.MODEL_SAVE_PATH) if self.is_saving else None

    @property
    def model_weights_save_path(self) -> Optional[str]:
        return self.get_model_weights_path(self.MODEL_SAVE_PATH) if self.is_saving else None

    # ------------------------------------------------------------- verify
    def verify(self) -> None:
        """Startup sanity checks (reference config.py:232-239)."""
        if not self.is_training and not self.is_loading:
            raise ValueError('Must train or load a model.')
        if self.is_loading and not os.path.isdir(self.model_load_dir):
            raise ValueError('Model load dir `{}` does not exist.'.format(
                self.model_load_dir))
        if self.DL_FRAMEWORK not in {'flax', 'jax'}:
            raise ValueError("config.DL_FRAMEWORK must be in {'flax', 'jax'}.")
        if self.COMPUTE_DTYPE not in {'bfloat16', 'float32'}:
            raise ValueError("config.COMPUTE_DTYPE must be in "
                             "{'bfloat16', 'float32'}.")
        if self.DROPOUT_PRNG_IMPL not in {'threefry2x32', 'rbg'}:
            raise ValueError("config.DROPOUT_PRNG_IMPL must be in "
                             "{'threefry2x32', 'rbg'}.")
        if self.ADAM_MU_DTYPE not in {'float32', 'bfloat16'}:
            raise ValueError("config.ADAM_MU_DTYPE must be in "
                             "{'float32', 'bfloat16'}.")
        if self.ADAM_NU_DTYPE not in {'float32', 'bfloat16'}:
            raise ValueError("config.ADAM_NU_DTYPE must be in "
                             "{'float32', 'bfloat16'}.")
        if self.GRADS_DTYPE not in {'float32', 'bfloat16'}:
            raise ValueError("config.GRADS_DTYPE must be in "
                             "{'float32', 'bfloat16'}.")
        if self.GRADS_DTYPE == 'bfloat16' \
                and self.COMPUTE_DTYPE != 'bfloat16':
            # The knob works by differentiating wrt the PRE-CAST bf16
            # params; that is only value-preserving for the forward when
            # the model would cast params to bf16 anyway. Under fp32
            # compute it would silently bf16-round every weight in the
            # training forward (and diverge from the uncast eval forward).
            raise ValueError(
                "GRADS_DTYPE='bfloat16' requires "
                "COMPUTE_DTYPE='bfloat16' (the bf16 pre-cast must round "
                "exactly where the compute cast already would).")
        if self.TELEMETRY_FLUSH_EVERY_STEPS < 1:
            raise ValueError(
                'config.TELEMETRY_FLUSH_EVERY_STEPS must be >= 1.')
        if self.TELEMETRY_TRACE_NUM_STEPS < 1:
            raise ValueError(
                'config.TELEMETRY_TRACE_NUM_STEPS must be >= 1.')
        if self.TRACING_SAMPLE_RATE > 1.0:
            raise ValueError('config.TRACING_SAMPLE_RATE must be in '
                             '[0, 1] (or < 0 for env/default fallback).')
        if self.TRACING_SLOW_MS < 0:
            raise ValueError('config.TRACING_SLOW_MS must be >= 0 '
                             '(0 disables latency tail retention).')
        if self.TRACING_FLIGHT_TRACES < 1:
            raise ValueError('config.TRACING_FLIGHT_TRACES must be >= 1.')
        if self.HBM_BUDGET_BYTES < -1:
            raise ValueError('config.HBM_BUDGET_BYTES must be >= -1 '
                             '(-1 = env fallback, 0 = unlimited).')
        if self.DEVICE_PEAK_FLOPS != -1.0 and self.DEVICE_PEAK_FLOPS <= 0:
            raise ValueError('config.DEVICE_PEAK_FLOPS must be > 0 '
                             '(-1 = env/device-table fallback).')
        if self.GOODPUT_ANOMALY_SIGMA < 0:
            raise ValueError('config.GOODPUT_ANOMALY_SIGMA must be >= 0 '
                             '(0 disables the anomaly watchdog).')
        if self.GOODPUT_AUTOCAPTURE_COOLDOWN_SECS < 0:
            raise ValueError(
                'config.GOODPUT_AUTOCAPTURE_COOLDOWN_SECS must be >= 0.')
        if self.BATCH_WIRE_FORMAT not in {'planes', 'packed'}:
            raise ValueError("config.BATCH_WIRE_FORMAT must be in "
                             "{'planes', 'packed'}.")
        if self.OPTIMIZER_STATE_SHARDING not in {'mirror', 'zero'}:
            raise ValueError("config.OPTIMIZER_STATE_SHARDING must be in "
                             "{'mirror', 'zero'}.")
        if self.MAX_DIVERGENCE_REWINDS < 0:
            raise ValueError('config.MAX_DIVERGENCE_REWINDS must be >= 0.')
        if self.HANG_WATCHDOG_SECS < 0:
            raise ValueError('config.HANG_WATCHDOG_SECS must be >= 0 '
                             '(0 disables the watchdog).')
        if self.MODEL_FAMILY not in {'code2vec', 'mellum', 'minicpm_sala',
                                     'mistral4'}:
            raise ValueError("config.MODEL_FAMILY must be in "
                             "{'code2vec', 'mellum', 'minicpm_sala', "
                             "'mistral4'}.")
        self.lm_chunk_buckets  # raises on malformed bucket specs
        if min(self.LM_MAX_SEQS, self.LM_PAGE_SIZE, self.LM_PAGE_POOL_PAGES,
               self.LM_MAX_CONTEXT, self.LM_WINDOW_SUBCHUNK) < 1:
            raise ValueError('config.LM_MAX_SEQS, LM_PAGE_SIZE, '
                             'LM_PAGE_POOL_PAGES, LM_MAX_CONTEXT and '
                             'LM_WINDOW_SUBCHUNK must be >= 1.')
        self.serving_batch_buckets  # raises on malformed bucket specs
        if self.SERVING_MAX_DELAY_MS < 0:
            raise ValueError('config.SERVING_MAX_DELAY_MS must be >= 0.')
        if self.SERVING_DECODE_WORKERS < 1:
            raise ValueError('config.SERVING_DECODE_WORKERS must be >= 1.')
        if self.SERVING_DEADLINE_MS < 0:
            raise ValueError('config.SERVING_DEADLINE_MS must be >= 0 '
                             '(0 = no deadline).')
        if self.SERVING_QUEUE_BOUND < -1:
            raise ValueError('config.SERVING_QUEUE_BOUND must be >= -1 '
                             '(0 = auto, -1 = unbounded).')
        if self.MESH_REPLICAS < 1:
            raise ValueError('config.MESH_REPLICAS must be >= 1.')
        if self.MESH_QUEUE_BOUND < -1:
            raise ValueError('config.MESH_QUEUE_BOUND must be >= -1 '
                             '(0 = auto, -1 = unbounded).')
        if self.MEMO_CACHE_BYTES < 0:
            raise ValueError('config.MEMO_CACHE_BYTES must be >= 0 '
                             '(0 disables the memoization tier).')
        if not 0.0 <= self.MEMO_SEMANTIC_EPSILON <= 1.0:
            raise ValueError('config.MEMO_SEMANTIC_EPSILON must be in '
                             '[0, 1] (0 keeps the semantic tier off).')
        if not 0.0 <= self.BLEND_NEIGHBOR_WEIGHT <= 1.0:
            raise ValueError('config.BLEND_NEIGHBOR_WEIGHT must be in '
                             '[0, 1] (0 = pure softmax ranking).')
        if self.MESH_MAX_INFLIGHT < 1:
            raise ValueError('config.MESH_MAX_INFLIGHT must be >= 1.')
        if self.MESH_BREAKER_THRESHOLD < 1:
            raise ValueError('config.MESH_BREAKER_THRESHOLD must be '
                             '>= 1.')
        if self.MESH_BREAKER_COOLDOWN_SECS < 0:
            raise ValueError('config.MESH_BREAKER_COOLDOWN_SECS must '
                             'be >= 0.')
        if self.MESH_REPLICA_MODE not in ('thread', 'process', 'socket'):
            raise ValueError("config.MESH_REPLICA_MODE must be 'thread', "
                             "'process' or 'socket'.")
        if self.MESH_HEARTBEAT_SECS < 0:
            raise ValueError('config.MESH_HEARTBEAT_SECS must be >= 0 '
                             '(0 disables the liveness monitor).')
        if self.MESH_HEARTBEAT_MISSES < 1:
            raise ValueError('config.MESH_HEARTBEAT_MISSES must be '
                             '>= 1.')
        if self.MESH_RESTART_LIMIT < 0:
            raise ValueError('config.MESH_RESTART_LIMIT must be >= 0 '
                             '(0 = never restart).')
        if self.MESH_RESTART_WINDOW_SECS <= 0:
            raise ValueError('config.MESH_RESTART_WINDOW_SECS must be '
                             '> 0 (the restart budget is window-'
                             'scoped).')
        if self.MESH_RESTART_BACKOFF_SECS < 0:
            raise ValueError('config.MESH_RESTART_BACKOFF_SECS must be '
                             '>= 0.')
        if self.MESH_TELEMETRY_BACKHAUL not in (-1, 0, 1):
            raise ValueError('config.MESH_TELEMETRY_BACKHAUL must be '
                             '-1 (auto), 0 (off) or 1 (on).')
        if self.MESH_DEVICES_PER_REPLICA < 0:
            raise ValueError('config.MESH_DEVICES_PER_REPLICA must be '
                             '>= 0 (0 = replicas share the full '
                             'device set).')
        if self.MESH_DEVICES_PER_REPLICA > 0 and \
                self.MESH_DEVICES_PER_REPLICA % max(
                    1, self.MESH_MODEL_AXIS_SIZE) != 0:
            raise ValueError('config.MESH_DEVICES_PER_REPLICA must be a '
                             'multiple of MESH_MODEL_AXIS_SIZE (each '
                             'slice builds its own (data, model) '
                             'sub-mesh).')
        if self.AUTOSCALE_MIN_REPLICAS < 1:
            raise ValueError('config.AUTOSCALE_MIN_REPLICAS must be '
                             '>= 1.')
        if self.AUTOSCALE_MAX_REPLICAS < 0:
            raise ValueError('config.AUTOSCALE_MAX_REPLICAS must be >= 0 '
                             '(0 keeps the autoscaler off).')
        if self.AUTOSCALE_MAX_REPLICAS > 0 and \
                self.AUTOSCALE_MAX_REPLICAS < self.AUTOSCALE_MIN_REPLICAS:
            raise ValueError('config.AUTOSCALE_MAX_REPLICAS must be >= '
                             'AUTOSCALE_MIN_REPLICAS when armed.')
        if self.AUTOSCALE_INTERVAL_SECS <= 0:
            raise ValueError('config.AUTOSCALE_INTERVAL_SECS must be '
                             '> 0.')
        if self.AUTOSCALE_UP_QUEUE_SECS <= 0:
            raise ValueError('config.AUTOSCALE_UP_QUEUE_SECS must be '
                             '> 0.')
        if self.AUTOSCALE_UP_BURN < 0:
            raise ValueError('config.AUTOSCALE_UP_BURN must be >= 0 '
                             '(0 disables the burn leg).')
        if self.AUTOSCALE_DOWN_IDLE_SECS < 0:
            raise ValueError('config.AUTOSCALE_DOWN_IDLE_SECS must be '
                             '>= 0.')
        if not 0.0 < self.AUTOSCALE_DOWN_UTILIZATION <= 1.0:
            raise ValueError('config.AUTOSCALE_DOWN_UTILIZATION must be '
                             'in (0, 1].')
        if self.AUTOSCALE_UP_COOLDOWN_SECS < 0 or \
                self.AUTOSCALE_DOWN_COOLDOWN_SECS < 0:
            raise ValueError('config.AUTOSCALE_*_COOLDOWN_SECS must be '
                             '>= 0.')
        if self.AUTOSCALE_FLAP_WINDOW_SECS <= 0:
            raise ValueError('config.AUTOSCALE_FLAP_WINDOW_SECS must be '
                             '> 0.')
        if self.AUTOSCALE_FLAP_LIMIT < 1:
            raise ValueError('config.AUTOSCALE_FLAP_LIMIT must be >= 1.')
        if not 0.0 <= self.SERVING_SLO_AVAILABILITY < 1.0:
            raise ValueError('config.SERVING_SLO_AVAILABILITY must be '
                             'in [0, 1) (0 disables; 1.0 would leave '
                             'no error budget to burn).')
        if self.SERVING_SLO_P99_MS < 0:
            raise ValueError('config.SERVING_SLO_P99_MS must be >= 0 '
                             '(0 disables the latency leg).')
        if self.SERVING_SLO_FAST_WINDOW_SECS <= 0 or \
                self.SERVING_SLO_SLOW_WINDOW_SECS <= 0:
            raise ValueError('config.SERVING_SLO_*_WINDOW_SECS must be '
                             '> 0.')
        if self.SERVING_SLO_FAST_WINDOW_SECS > \
                self.SERVING_SLO_SLOW_WINDOW_SECS:
            raise ValueError('config.SERVING_SLO_FAST_WINDOW_SECS must '
                             'not exceed SERVING_SLO_SLOW_WINDOW_SECS '
                             '(the fast window detects, the slow one '
                             'confirms).')
        if self.SERVING_SLO_BURN_THRESHOLD <= 0:
            raise ValueError('config.SERVING_SLO_BURN_THRESHOLD must '
                             'be > 0.')
        if self.SERVING_CANARY_BATCHES < 0:
            raise ValueError('config.SERVING_CANARY_BATCHES must be >= 0 '
                             '(0 = swap without canary).')
        if not 0.0 <= self.SERVING_CANARY_AGREEMENT <= 1.0:
            raise ValueError('config.SERVING_CANARY_AGREEMENT must be in '
                             '[0, 1].')
        if self.SERVING_CANARY_TIMEOUT_SECS < 0:
            raise ValueError('config.SERVING_CANARY_TIMEOUT_SECS must be '
                             '>= 0 (0 disables the canary timeout).')
        if self.SERVE_FOLLOW_CHECKPOINTS_SECS < 0:
            raise ValueError('config.SERVE_FOLLOW_CHECKPOINTS_SECS must '
                             'be >= 0 (0 disables).')
        if self.EXTRACTOR_TIMEOUT_SECS < 0:
            raise ValueError('config.EXTRACTOR_TIMEOUT_SECS must be >= 0 '
                             '(0 disables the bound).')
        if self.EXTRACTOR_RETRIES < 0:
            raise ValueError('config.EXTRACTOR_RETRIES must be >= 0.')
        if self.EXTRACTOR_BACKOFF_SECS < 0:
            raise ValueError('config.EXTRACTOR_BACKOFF_SECS must be >= 0.')
        if self.EXTRACTOR_POOL_WORKERS < 1:
            raise ValueError('config.EXTRACTOR_POOL_WORKERS must be >= 1.')
        if self.EXTRACTOR_BREAKER_THRESHOLD < 1:
            raise ValueError('config.EXTRACTOR_BREAKER_THRESHOLD must be '
                             '>= 1.')
        if self.EXTRACTOR_BREAKER_COOLDOWN_SECS < 0:
            raise ValueError('config.EXTRACTOR_BREAKER_COOLDOWN_SECS must '
                             'be >= 0.')
        valid_tiers = {'topk', 'attention', 'full', 'vectors'}
        tiers = self.serving_warm_tiers
        if not tiers or not set(tiers) <= valid_tiers:
            raise ValueError(
                'config.SERVING_WARM_TIERS must be a non-empty '
                'comma-separated subset of %s, got %r'
                % (sorted(valid_tiers), self.SERVING_WARM_TIERS))
        if self.VECTORS_DTYPE not in {'float32', 'float16'}:
            raise ValueError("config.VECTORS_DTYPE must be in "
                             "{'float32', 'float16'}.")
        if self.INDEX_KIND not in {'exact', 'ivf'}:
            raise ValueError("config.INDEX_KIND must be in "
                             "{'exact', 'ivf'}.")
        if self.INDEX_METRIC not in {'cosine', 'dot'}:
            raise ValueError("config.INDEX_METRIC must be in "
                             "{'cosine', 'dot'}.")
        if self.INDEX_NPROBE < 0:
            raise ValueError('config.INDEX_NPROBE must be >= 0 '
                             '(0 = default).')
        if self.INDEX_CLUSTERS < 0:
            raise ValueError('config.INDEX_CLUSTERS must be >= 0 '
                             '(0 = sqrt(N)).')
        if self.INDEX_NEIGHBORS_K < 1:
            raise ValueError('config.INDEX_NEIGHBORS_K must be >= 1.')
        if self.INDEX_QUANT not in {'', 'int8', 'pq'}:
            raise ValueError("config.INDEX_QUANT must be in "
                             "{'', 'int8', 'pq'} ('' = full-precision "
                             "tier).")
        if self.INDEX_RERANK < 0:
            raise ValueError('config.INDEX_RERANK must be >= 0 '
                             '(0 disables the exact re-rank).')
        if self.INDEX_PQ_M < 0:
            raise ValueError('config.INDEX_PQ_M must be >= 0 '
                             '(0 = dim/4).')
        if self.INDEX_SEGMENT_ROWS < 1:
            raise ValueError('config.INDEX_SEGMENT_ROWS must be >= 1.')
        if self.INDEX_COMPACT_SEGMENTS < 0:
            raise ValueError('config.INDEX_COMPACT_SEGMENTS must be '
                             '>= 0 (0 = manual compaction only).')
        if self.QUERY_NEIGHBORS_PATH and not (self.INDEX_PATH
                                              or self.BUILD_INDEX_FROM):
            raise ValueError(
                '--query-neighbors needs an index: pass --index-path '
                'DIR (an existing index) or --build-index SOURCE '
                '(build one first).')
        if self.FAULT_INJECT:
            # a typo'd injection spec must fail at startup, not silently
            # inject nothing (parse_spec raises ValueError with the
            # offending entry and the known fault points)
            from code2vec_tpu.resilience.faults import parse_spec
            parse_spec(self.FAULT_INJECT)

    def __iter__(self) -> Iterator[Tuple[str, Any]]:
        for field in dataclasses.fields(self):
            if field.name.startswith('_'):
                continue
            yield field.name, getattr(self, field.name)

    # ------------------------------------------------------------ logging
    def get_logger(self) -> logging.Logger:
        if self._logger is None:
            logger = logging.getLogger('code2vec_tpu')
            logger.setLevel(logging.INFO)
            logger.handlers = []
            logger.propagate = False
            formatter = logging.Formatter('%(asctime)s %(levelname)-8s %(message)s')
            if self.VERBOSE_MODE >= 1:
                handler = logging.StreamHandler(sys.stdout)
                handler.setLevel(logging.INFO)
                handler.setFormatter(formatter)
                logger.addHandler(handler)
            if self.LOGS_PATH:
                file_handler = logging.FileHandler(self.LOGS_PATH)
                file_handler.setLevel(logging.INFO)
                file_handler.setFormatter(formatter)
                logger.addHandler(file_handler)
            self._logger = logger
        return self._logger

    def log(self, msg: str) -> None:
        self.get_logger().info(msg)
