"""Backend-agnostic training/eval/predict engine.

Replaces the reference's session-based hot loop (tensorflow_model.py:40-112)
and the Keras fit wrapper (keras_model.py:166-193) with three jitted pure
step functions over a device mesh:

- ``train_step``  — loss + grads + Adam update, params donated;
- ``eval_step``   — deterministic forward + device-side top-k;
- ``predict_step``— eval plus attention weights and softmax-normalized
  top-k scores (reference ``normalize_scores=True``,
  tensorflow_model.py:305-306), built in OUTPUT TIERS (``PREDICT_TIERS``)
  so serving pays only for the outputs a caller asked for.

Everything under jit is traced once and reused for every batch; the mesh
placement of params/batches drives XLA's partitioner (DP gradient psum,
sharded-table gathers, sharded softmax) with no collective written by hand.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import os
import signal as signal_lib
import time
from typing import Any, Callable, Iterable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from code2vec_tpu.config import Config
from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.data.reader import Batch
from code2vec_tpu.models import functional
from code2vec_tpu.ops.topk import sharded_top_k
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.resilience import faults
from code2vec_tpu.scopes import scoped
from code2vec_tpu.telemetry import goodput as goodput_lib

# package logger: 'code2vec_tpu.training.trainer' — propagates to the
# 'code2vec_tpu' root logger Config.get_logger configures
logger = logging.getLogger(__name__)

# Output tiers of the predict step — each is a SEPARATE jitted program
# (serving/engine.py pre-compiles them per batch bucket):
#   'topk'      — softmaxed top-k scores + indices only (the cheap
#                 steady-state serving path; no attention/vector D2H)
#   'attention' — topk + per-context attention weights (the REPL contract)
#   'full'      — topk + attention + code vectors (the v1 predict_step)
#   'vectors'   — code vectors ONLY: the (B, V) logits matmul and top-k
#                 are dead-code-eliminated, for bulk embedding export
PREDICT_TIERS = ('topk', 'attention', 'full', 'vectors')


class TrainerState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array     # () int32
    rng: jax.Array      # dropout PRNG root


def _packed(arrays) -> bool:
    """Whether a batch's arrays (host or placed) are the packed wire: 4
    of them, 7 with a training batch's touched rows; planes are 6."""
    return len(arrays) in packed_lib.PACKED_ARITIES


class Trainer:
    def __init__(self, config: Config, backend,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.config = config
        self.backend = backend
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh(config)
        data_size = self.mesh.shape[mesh_lib.DATA_AXIS]
        model_size = self.mesh.shape[mesh_lib.MODEL_AXIS]
        for attr in ('TRAIN_BATCH_SIZE', 'TEST_BATCH_SIZE'):
            if getattr(config, attr) % data_size:
                raise ValueError(
                    '%s=%d must be divisible by the mesh data axis (%d).'
                    % (attr, getattr(config, attr), data_size))
        if config.SHARD_CONTEXTS and config.MAX_CONTEXTS % model_size:
            raise ValueError(
                'SHARD_CONTEXTS requires MAX_CONTEXTS=%d divisible by the '
                'mesh model axis (%d).' % (config.MAX_CONTEXTS, model_size))
        if config.PARAM_ROW_ALIGNMENT % model_size:
            raise ValueError(
                'PARAM_ROW_ALIGNMENT=%d must be divisible by the mesh model '
                'axis (%d) for even table sharding.'
                % (config.PARAM_ROW_ALIGNMENT, model_size))
        self._zero_opt = config.OPTIMIZER_STATE_SHARDING == 'zero'
        if self._zero_opt and config.PARAM_ROW_ALIGNMENT % self.mesh.size:
            raise ValueError(
                "OPTIMIZER_STATE_SHARDING='zero' shards moment-table rows "
                'over the WHOLE mesh: PARAM_ROW_ALIGNMENT=%d must be '
                'divisible by data*model = %d.'
                % (config.PARAM_ROW_ALIGNMENT, self.mesh.size))
        # USE_PALLAS_FUSED_CE on a multi-device mesh routes through the
        # shard_mapped kernel (ops/pallas_ce.py::sharded_fused_weighted_
        # ce_sums): GSPMD cannot partition the opaque pallas_call itself,
        # so the plain kernel would be replicated (full batch + full
        # table on every device) exactly where sharding matters. The
        # PARAM_ROW_ALIGNMENT check above already guarantees the sharded
        # variant's V % model_axis == 0 requirement.
        # Reference uses tf.train.AdamOptimizer() defaults
        # (tensorflow_model.py:232): lr=1e-3, b1=0.9, b2=0.999, eps=1e-8.
        # ADAM_MU_DTYPE / ADAM_NU_DTYPE = 'bfloat16' store the moments in
        # bf16 — HBM-traffic knobs for the HBM-bound dense update (config
        # comments + PERF.md); None keeps optax's param-dtype default.
        mu_dtype = (jnp.bfloat16
                    if config.ADAM_MU_DTYPE == 'bfloat16' else None)
        if (config.ADAM_NU_DTYPE == 'bfloat16'
                or config.GRADS_DTYPE == 'bfloat16'):
            # optax.adam has no nu_dtype; the local transform keeps
            # optax's ScaleByAdamState field names so checkpoints stay
            # field-compatible (training/adam_dtypes.py). It is also
            # mandatory under bf16 grads: its moment math is EXPLICIT
            # fp32, where optax's dtype-promotion rules would let a bf16
            # grad meet a bf16-stored mu and accumulate the EMA in bf16.
            from code2vec_tpu.training import adam_dtypes
            nu_dtype = (jnp.bfloat16
                        if config.ADAM_NU_DTYPE == 'bfloat16' else None)
            self.optimizer = adam_dtypes.adam(
                config.LEARNING_RATE, mu_dtype=mu_dtype,
                nu_dtype=nu_dtype)
        else:
            self.optimizer = optax.adam(config.LEARNING_RATE,
                                        mu_dtype=mu_dtype)
        # Telemetry (OBSERVABILITY.md): None when disabled — every
        # instrumented site below is then a single `is None` check.
        self._telemetry = None
        # Legend of the step programs for profiler captures
        # (telemetry/trace.py::ProgramLegend): None unless a capture can
        # happen (PROFILE_DIR's fixed window, telemetry's on-demand one)
        self._legend = None
        # dispatch shapes already lowered for their AOT step cost
        # (FLOPs/bytes for train/mfu) and the legend's text — first sight
        # only, telemetry and PROFILE_DIR paths only
        self._seen_keys = set()
        if getattr(config, 'TELEMETRY', False):
            from code2vec_tpu.telemetry import StepTelemetry
            self._telemetry = StepTelemetry(
                config, log=config.log,
                process_index=jax.process_index())
        if self._telemetry is not None or config.PROFILE_DIR:
            from code2vec_tpu.telemetry.trace import ProgramLegend
            self._legend = ProgramLegend(dict(self.mesh.shape),
                                         log=config.log)
            if self._telemetry is not None:
                self._telemetry.trace.legend = self._legend
        # Device-memory ledger (telemetry/memory.py, OBSERVABILITY.md):
        # this trainer's state registers under a per-instance key, so
        # restores replace (never double-count) and a garbage-collected
        # trainer auto-releases its entries.
        self._mem_key = 'trainer:%x' % id(self)
        # Resilience (ROBUSTNESS.md): arm the process-global fault plan
        # from config. None = unset -> the env var fills in (launches
        # whose scripts you can't edit); '' = explicitly disabled, so an
        # exported FAULT_INJECT cannot leak into a declared control run.
        # Re-arming per Trainer resets fired state, so each run's
        # injections are deterministic even under process reuse (tests).
        faults.configure(config.FAULT_INJECT
                         if config.FAULT_INJECT is not None
                         else os.environ.get('FAULT_INJECT', ''))
        self._build_steps()

    # ----------------------------------------------------------- jit steps
    def _build_steps(self) -> None:
        backend = self.backend
        optimizer = self.optimizer
        top_k = self.config.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION

        # the mesh only matters to the loss when the fused CE must be
        # shard_mapped; None keeps single-device tracing mesh-free
        loss_mesh = self.mesh if self.mesh.size > 1 else None
        # GRADS_DTYPE='bfloat16': differentiate wrt the PRE-CAST bf16
        # params so the cotangents — above all the two table-grad
        # scatter-adds and the (B, V) logits backward — are produced and
        # streamed through HBM in bf16 instead of fp32 (config comment +
        # PERF.md). Config.verify() pins COMPUTE_DTYPE='bfloat16' with
        # it, which makes the forward bit-identical either way: the
        # model casts every param to bf16 before use, so casting first
        # changes only the dtype the gradients come back in. Master
        # params stay fp32; adam_dtypes upcasts the bf16 grads to fp32
        # before any moment math.
        grads_bf16 = self.config.GRADS_DTYPE == 'bfloat16'

        @scoped('c2v_adam')    # the casts are the walk's
        def cast_for_grads(params):
            return jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

        # Ragged fusion (USE_PALLAS_RAGGED_FUSION, ops/pallas_ragged.py):
        # the packed twins below consume the (D, cap, 3) wire directly —
        # fused gather + encode + single-pass attention softmax, no
        # device-side unpack, no (B, C, .) planes — and the TRAIN step's
        # custom-VJP backward recomputes off the same segments instead
        # of storing per-slot residuals.
        ragged = (self.config.USE_PALLAS_RAGGED_FUSION
                  and hasattr(backend, 'forward_packed'))
        ragged_train = ragged
        # kernel-or-twin for the deterministic packed forward (eval,
        # predict, the serving ladder), decided ONCE here from the
        # platform of the devices this trainer's programs run on — never
        # re-derived at trace time, never by catching a backend error
        platform = mesh_lib.mesh_platform(self.mesh)
        ragged_kernel = ragged and platform == 'tpu'
        if ragged:
            logger.info(
                'ragged fusion: deterministic packed forward runs the %s '
                '(mesh platform %r, %d device(s))',
                'Pallas kernel' if ragged_kernel else 'jnp twin',
                platform, self.mesh.size)

        def make_train_step(loss_call):
            def train_step(state: TrainerState, arrays
                           ) -> Tuple[TrainerState, jax.Array]:
                dropout_rng = jax.random.fold_in(state.rng, state.step)

                def loss_fn(params):
                    loss, _aux = loss_call(params, arrays, dropout_rng)
                    return loss

                diff_params = (cast_for_grads(state.params) if grads_bf16
                               else state.params)
                loss, grads = jax.value_and_grad(loss_fn)(diff_params)
                # the walk over the tables and the dense parameters; the
                # loss's parts are named where they are written
                # (models/functional.py, ops/pallas_ragged.py)
                with jax.named_scope('c2v_adam'):
                    updates, new_opt_state = optimizer.update(
                        grads, state.opt_state, state.params)
                    new_params = optax.apply_updates(state.params, updates)
                new_state = TrainerState(params=new_params,
                                         opt_state=new_opt_state,
                                         step=state.step + 1, rng=state.rng)
                return new_state, loss
            return train_step

        train_step = make_train_step(
            lambda params, arrays, rng:
            backend.loss_fn(params, arrays, rng, mesh=loss_mesh))

        mesh = self.mesh
        # the forward's mesh only matters where the ragged Pallas kernel
        # must be shard_mapped (GSPMD cannot partition a pallas_call);
        # None keeps single-device tracing mesh-free, like loss_mesh
        fwd_mesh = self.mesh if self.mesh.size > 1 else None

        @scoped('c2v_topk')
        def take_top_k(logits):
            # cross-shard merge on model-parallel meshes, plain lax.top_k
            # otherwise — the dispatch lives in sharded_top_k
            return sharded_top_k(logits, top_k, mesh)

        export_vectors = self.config.EXPORT_CODE_VECTORS

        def make_eval_step(forward_call, labels_of):
            def eval_step(params, arrays):
                code_vectors, attention, logits = forward_call(params,
                                                               arrays)
                topk_scores, topk_indices = take_top_k(logits)
                # weighted CE sums (not the mean): exact streaming
                # aggregation across batches and hosts — the reference's
                # Keras backend reports eval loss (keras_model.py:
                # 179-193); padded rows have weight 0 and drop out
                label, weight = labels_of(arrays)
                loss_sum, weight_sum = functional.weighted_ce_sums(
                    logits, label, weight)
                out = {'topk_indices': topk_indices,
                       'topk_scores': topk_scores,
                       'loss_sum': loss_sum,
                       'weight_sum': weight_sum}
                if export_vectors:
                    # only ship (B, D) code vectors to host when
                    # exporting — per-batch device->host traffic
                    # otherwise wasted
                    out['code_vectors'] = code_vectors
                return out
            return eval_step

        eval_step = make_eval_step(backend.forward,
                                   lambda arrays: (arrays[4], arrays[5]))

        # Predict programs come in OUTPUT TIERS (PREDICT_TIERS), each its
        # own jitted program, so the cheap path stops paying for the
        # expensive one: 'topk' ships only the (B, k) indices/scores,
        # 'attention' adds the (B, C) weights, 'full' adds the (B, D)
        # code vectors, and 'vectors' drops the logits matmul + top-k
        # entirely (XLA dead-code-eliminates the whole (B, V) product —
        # the dominant FLOPs at java14m's 261K-target vocab) for bulk
        # embedding export. The serving engine pre-compiles these per
        # batch/capacity bucket (serving/engine.py, SERVING.md).
        def make_predict_step(tier, forward_call):
            with_topk = tier != 'vectors'
            with_attention = tier in ('attention', 'full')
            with_vectors = tier in ('vectors', 'full')

            def predict_step(params, arrays):
                code_vectors, attention, logits = forward_call(params,
                                                               arrays)
                out = {}
                if with_topk:
                    topk_scores, topk_indices = take_top_k(logits)
                    out['topk_indices'] = topk_indices
                    # reference normalize_scores=True
                    # (tensorflow_model.py:305-306)
                    out['topk_scores'] = jax.nn.softmax(topk_scores,
                                                        axis=-1)
                if with_attention:
                    out['attention'] = attention
                if with_vectors:
                    out['code_vectors'] = code_vectors
                return out
            return predict_step

        # Explicit output shardings for the donated state: inference alone
        # re-layouts the zero-partitioned moments back toward the grads'
        # (model-only) sharding after the first update, silently undoing
        # OPTIMIZER_STATE_SHARDING='zero'. _init_opt_state reuses the
        # opt_state field so the initialized and stepped layouts cannot
        # diverge.
        abstract_params = backend.param_shapes()
        abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        self._state_shardings = TrainerState(
            params=mesh_lib.sharding_for_tree(abstract_params, mesh),
            opt_state=mesh_lib.sharding_for_tree(
                abstract_opt, mesh, zero_partition=self._zero_opt),
            step=replicated, rng=replicated)

        # Packed-wire twins. Default: the same step functions behind the
        # jitted device-side unpack (data/packed.py) — the unpack
        # scatters the dense context stream back to the exact (B, C)
        # planes + mask INSIDE the compiled program, so the model sees
        # bit-identical batches and the wire carries 3-5x fewer bytes.
        # With USE_PALLAS_RAGGED_FUSION the twins skip the unpack
        # entirely: the ragged fused encoder (ops/pallas_ragged.py)
        # walks the packed segments directly, matching the
        # unpack-then-dense outputs to fp32 rounding
        # (tests/test_pallas_ragged.py). PAD indices must match the
        # reader's pack-time fill (models/backends.py).
        token_pad = getattr(backend, 'token_pad_index', 0)
        path_pad = getattr(backend, 'path_pad_index', 0)
        max_contexts = self.config.MAX_CONTEXTS

        def unpack(packed_arrays):
            ctx, count, label, weight = packed_arrays[:4]
            source, path, target, mask = packed_lib.unpack_device(
                ctx, count, max_contexts, token_pad, path_pad)
            return (source, path, target, mask, label, weight)

        if ragged_train:
            train_step_packed = make_train_step(
                lambda params, arrays, rng:
                backend.loss_fn_packed(params, arrays, rng,
                                       mesh=loss_mesh))
        else:
            def train_step_packed(state, packed_arrays):
                return train_step(state, unpack(packed_arrays))

        if ragged:
            forward_packed = (lambda params, arrays:
                              backend.forward_packed(
                                  params, arrays, mesh=fwd_mesh,
                                  use_kernel=ragged_kernel))
            eval_step_packed = make_eval_step(
                forward_packed, lambda arrays: (arrays[2], arrays[3]))
        else:
            def eval_step_packed(params, packed_arrays):
                return eval_step(params, unpack(packed_arrays))

        # donate the consumed staging buffers alongside the state: the
        # ring (stage_batches) keeps DEVICE_PREFETCH_BATCHES uploads in
        # flight, so freeing each batch's memory into the step bounds
        # the staging footprint. Harnesses that re-feed placed arrays
        # must disable it (config comment; benchlib pins it off).
        # Backends that cannot alias a given buffer (CPU; int inputs
        # with no matching output) emit jax's "donated buffers were not
        # usable" notice once per compile — expected, deliberately NOT
        # filtered (a global warnings filter would also hide genuinely
        # broken donations in the embedding program).
        donate_train = ((0, 1) if self.config.DONATE_STAGED_BATCHES
                        else (0,))
        donate_eval = (1,) if self.config.DONATE_STAGED_BATCHES else ()
        self._train_step = jax.jit(train_step, donate_argnums=donate_train,
                                   out_shardings=(self._state_shardings,
                                                  replicated))
        self._train_step_packed = jax.jit(
            train_step_packed, donate_argnums=donate_train,
            out_shardings=(self._state_shardings, replicated))
        self._eval_step = jax.jit(eval_step, donate_argnums=donate_eval)
        self._eval_step_packed = jax.jit(eval_step_packed,
                                         donate_argnums=donate_eval)
        # one jitted program per (tier, wire) — never donated: serving
        # re-feeds warm placed buffers and predict batches are tiny
        self._predict_steps = {}
        for tier in PREDICT_TIERS:
            step_fn = make_predict_step(tier, backend.forward)
            self._predict_steps[(tier, 'planes')] = jax.jit(step_fn)
            if ragged:
                # XLA dead-code-eliminates the attention plane scatter
                # for the tiers that never ship attention, exactly as it
                # DCEs the logits matmul for 'vectors'
                packed_fn = make_predict_step(tier, forward_packed)
            else:
                packed_fn = (lambda params, packed_arrays, _fn=step_fn:
                             _fn(params, unpack(packed_arrays)))
            self._predict_steps[(tier, 'packed')] = jax.jit(packed_fn)
        self._predict_step = self._predict_steps[('full', 'planes')]
        self._predict_step_packed = self._predict_steps[('full', 'packed')]
        self._token_pad = token_pad
        self._path_pad = path_pad

    # --------------------------------------------------------------- state
    def register_state_memory(self, params, opt_state=None) -> None:
        """Attribute this trainer's state to the device-memory ledger
        (telemetry/memory.py): called by every allocation owner of the
        training state — fresh init, params load, checkpoint restore
        (model_api) — under ONE per-trainer key, so a restore replaces
        the previous registration instead of double-counting.  Bytes
        are shape-constant across steps, so this is one-time
        bookkeeping, never hot-path work."""
        from code2vec_tpu.telemetry import memory as memory_lib
        led = memory_lib.ledger()
        led.register('params', self._mem_key, params, owner=self)
        if opt_state is not None:
            led.register('opt_state', self._mem_key, opt_state,
                         owner=self)

    def init_state(self, seed: int = 42) -> TrainerState:
        init_rng, train_rng = jax.random.split(jax.random.PRNGKey(seed))
        params = self.backend.init(init_rng)
        params = mesh_lib.shard_params(params, self.mesh)
        opt_state = self._init_opt_state(params)
        self.register_state_memory(params, opt_state)
        return TrainerState(params=params, opt_state=opt_state,
                            step=jnp.zeros((), jnp.int32), rng=train_rng)

    def _init_opt_state(self, params):
        # explicit out_shardings: Adam moments must follow the configured
        # moment layout — jit alone does not propagate input shardings to
        # the opt-state outputs. Single source of truth with the train
        # step's donated-output layout (_build_steps).
        # graftlint: disable=recompile-hazard -- cold path: runs once per init/restore, never per step; the throwaway program is the point
        return jax.jit(self.optimizer.init,
                       out_shardings=self._state_shardings.opt_state)(
                           params)

    def abstract_state(self) -> Tuple[Any, Any]:
        """(abstract_canonical_params, abstract_opt_state) with
        *current-mesh* shardings attached, for checkpoint restore targets —
        nothing is materialized on device (no throwaway init at
        384M-param-scale).

        Params use the CANONICAL checkpoint layout (flat {name: array}
        dict) so checkpoints are loadable under either backend; optimizer
        state keeps the backend-native tree (training resume requires the
        same backend — enforced with a clear error in CheckpointStore)."""
        abstract_params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
            self.backend.param_shapes())
        abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
        canonical = self.backend.named_params(abstract_params)._asdict()
        return (mesh_lib.attach_shardings(canonical, self.mesh),
                mesh_lib.attach_shardings(abstract_opt, self.mesh,
                                          zero_partition=self._zero_opt))

    def state_from_params(self, params, step: int = 0,
                          seed: int = 42) -> TrainerState:
        params = mesh_lib.shard_params(params, self.mesh)
        opt_state = self._init_opt_state(params)
        self.register_state_memory(params, opt_state)
        return TrainerState(params=params, opt_state=opt_state,
                            step=jnp.asarray(step, jnp.int32),
                            rng=jax.random.PRNGKey(seed))

    # --------------------------------------------------------------- steps
    def _check_packed(self, arrays) -> None:
        data_axis = self.mesh.shape[mesh_lib.DATA_AXIS]
        if arrays[0].shape[0] != data_axis:
            raise ValueError(
                'packed batch was built for %d data shard(s) but the mesh '
                'data axis is %d — pack with data_shards=%d '
                '(data/packed.py).'
                % (arrays[0].shape[0], data_axis, data_axis))

    def train_step(self, state: TrainerState, batch: Batch
                   ) -> Tuple[TrainerState, jax.Array]:
        host_arrays = batch.device_arrays()
        if _packed(host_arrays):
            self._check_packed(host_arrays)  # clear error BEFORE placement
        arrays = mesh_lib.shard_batch(host_arrays, self.mesh,
                                      self.config.SHARD_CONTEXTS)
        return self.train_step_placed(state, arrays)

    def stage_batches(self, batches: Iterable[Batch]):
        """The device staging ring: place batches ahead of the step
        consuming them, yielding ``(placed_arrays, batch)`` (the host
        batch rides along for consumers that need its strings/weights,
        e.g. eval decode). Accepts either wire format — a batch is placed
        via its own ``device_arrays()``.

        jax transfers are async, so staging the next batch while the
        current step computes overlaps the host->device copy with device
        work instead of serializing upload -> step -> upload.
        ``DEVICE_PREFETCH_BATCHES`` bounds the ring depth (device memory
        held by staged batches; 0 degenerates to place-then-consume), and
        placement is per-device direct (shard_batch ``direct=True``): each
        data shard's slice transfers straight to its device instead of
        replicate-then-slice. The consuming step donates the buffers back
        (DONATE_STAGED_BATCHES), so the ring's footprint stays ~depth
        batches."""
        depth = max(0, self.config.DEVICE_PREFETCH_BATCHES)
        if mesh_lib.mesh_platform(self.mesh) == 'cpu':
            # XLA:CPU's in-process collectives can deadlock their 40s
            # rendezvous when extra async placements are in flight next to
            # a sharded program on starved hosts (observed as SIGABRT on a
            # 1-core 8-virtual-device mesh). Host==device memory on CPU, so
            # lookahead buys nothing there anyway.
            depth = 0
        shard_contexts = self.config.SHARD_CONTEXTS
        staged = collections.deque()
        tele = self._telemetry
        # staging-bucket ledger accounting (telemetry/memory.py) rides
        # the telemetry gate: register on placement, release at pop —
        # metadata-only (.nbytes), zero host syncs; the plain path
        # carries nothing
        led = None
        mem_keys: collections.deque = collections.deque()
        mem_seq = 0
        if tele is not None:
            from code2vec_tpu.telemetry import memory as memory_lib
            led = memory_lib.ledger()
            tele.registry.gauge('staging/ring_depth').set(depth)
        try:
            for batch in batches:
                if tele is not None:
                    # the DISPATCH cost of the async per-device placement —
                    # jax transfers complete in the background, so a spike
                    # here means host-side slicing/copy, not wire time
                    with jax.profiler.TraceAnnotation('host/h2d_place'), \
                            tele.h2d.time():
                        placed = mesh_lib.shard_batch(batch.device_arrays(),
                                                      self.mesh,
                                                      shard_contexts,
                                                      direct=True)
                    tele.ring_occupancy.set(len(staged) + 1)
                    key = '%s/%d' % (self._mem_key, mem_seq)
                    mem_seq += 1
                    led.register('staging', key,
                                 sum(int(a.nbytes) for a in placed))
                    mem_keys.append(key)
                else:
                    placed = mesh_lib.shard_batch(batch.device_arrays(),
                                                  self.mesh, shard_contexts,
                                                  direct=True)
                staged.append((placed, batch))
                if len(staged) > depth:
                    if led is not None:
                        led.release('staging', mem_keys.popleft())
                    yield staged.popleft()
            while staged:
                if tele is not None:
                    tele.ring_occupancy.set(len(staged) - 1)
                if led is not None:
                    led.release('staging', mem_keys.popleft())
                yield staged.popleft()
        finally:
            # an abandoned generator (early break, exception) must not
            # leave phantom staging entries in the ledger
            if led is not None:
                while mem_keys:
                    led.release('staging', mem_keys.popleft())

    def train_step_placed(self, state: TrainerState, arrays
                          ) -> Tuple[TrainerState, jax.Array]:
        """train_step over arrays already placed by ``stage_batches`` —
        either wire format, dispatched on the tuple's arity (packed = 4
        arrays, 7 with a training batch's touched rows; planes = 6)."""
        if _packed(arrays):
            self._check_packed(arrays)
            return self._train_step_packed(state, arrays)
        return self._train_step(state, arrays)

    def eval_step_placed(self, params, arrays) -> dict:
        """eval_step over arrays already placed by ``stage_batches``."""
        if _packed(arrays):
            self._check_packed(arrays)
            return self._eval_step_packed(params, arrays)
        return self._eval_step(params, arrays)

    def eval_step(self, params, batch: Batch) -> dict:
        arrays = mesh_lib.shard_batch(batch.device_arrays(), self.mesh,
                                      self.config.SHARD_CONTEXTS)
        return self.eval_step_placed(params, arrays)

    def predict_step_placed(self, params, arrays, tier: str = 'full'
                            ) -> dict:
        """Tiered predict over arrays already placed on the mesh — either
        wire format, dispatched on the tuple's arity like the other
        ``*_placed`` entry points. ``tier`` selects the output tier's
        pre-built jitted program (PREDICT_TIERS)."""
        if tier not in PREDICT_TIERS:
            raise ValueError('tier must be one of %s, got %r'
                             % (PREDICT_TIERS, tier))
        if _packed(arrays):
            self._check_packed(arrays)
            return self._predict_steps[(tier, 'packed')](params, arrays)
        return self._predict_steps[(tier, 'planes')](params, arrays)

    def predict_program_memory(self, params, arrays, tier: str = 'full'
                               ) -> Optional[dict]:
        """AOT memory analysis of ONE warm predict program (the shapes
        of ``arrays``): generated-code/temp/argument/output bytes, for
        the ledger's executables bucket (telemetry/memory.py).  Costs
        one extra XLA compile, so the serving engine only calls it at
        warmup with telemetry enabled; returns None where the backend
        has no memory analysis."""
        wire = 'packed' if _packed(arrays) else 'planes'
        return self._program_memory(self._predict_steps[(tier, wire)],
                                    params, arrays)

    @staticmethod
    def _program_memory(fn, *args) -> Optional[dict]:
        """One jitted program's AOT memory record — the single
        definition of the record shape shared by the serving ledger
        (predict) and the bench A/B (train)."""
        try:
            analysis = fn.lower(*args).compile().memory_analysis()
            return {
                'generated_code_bytes':
                    int(analysis.generated_code_size_in_bytes),
                'temp_bytes': int(analysis.temp_size_in_bytes),
                'argument_bytes': int(analysis.argument_size_in_bytes),
                'output_bytes': int(analysis.output_size_in_bytes),
            }
        except Exception:
            return None

    @staticmethod
    def _program_cost(fn, *args) -> Optional[dict]:
        """One jitted program's AOT cost record: logical FLOPs + bytes
        accessed from ``Lowered.cost_analysis()`` — analysis of the
        lowered (pre-partitioning) module, so it costs one trace +
        lowering but NO extra backend compile.  None where the
        version/backend has no cost analysis."""
        try:
            return Trainer._lowered_cost(fn.lower(*args))
        except Exception:
            return None

    @staticmethod
    def _lowered_cost(lowered) -> Optional[dict]:
        try:
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            flops = float(cost.get('flops', 0.0))
            if flops <= 0:
                return None
            return {'flops': flops,
                    'bytes_accessed': float(cost.get('bytes accessed', 0.0))}
        except Exception:
            return None

    @staticmethod
    def _shape_key(arrays) -> Tuple[str, tuple]:
        """(the dispatch shape's key, its sizes): each NEW packed capacity
        is one more jit specialization of the whole step program, and so
        is each new touched-row capacity."""
        if _packed(arrays):
            shapes = (int(arrays[0].shape[1]),) + tuple(
                int(a.size) for a in arrays[4:6])
            return 'packed:' + ':'.join(map(str, shapes)), shapes
        return 'planes:%d' % int(arrays[0].shape[0]), ()

    def _first_sight(self, shape_key: str, state, arrays) -> None:
        """First sight of a dispatch shape, on the telemetry and
        PROFILE_DIR paths only: ONE lowering of its train-step program
        gives the AOT FLOPs/bytes for the goodput ledger (the
        MFU/roofline numerator, OBSERVABILITY.md "Training goodput";
        telemetry) and the compiled text for the captures' legend. The
        legend's compile is the build the first dispatch would have made,
        or its cache hit: the dispatch that follows shares this lowering
        and finds the executable on it, so a run counts the programs it
        counted (56 of 56 on the chip), here in warm-up: no capture and
        no steady window holds a compile."""
        if shape_key in self._seen_keys:
            return
        self._seen_keys.add(shape_key)
        fn = (self._train_step_packed if _packed(arrays)
              else self._train_step)
        lowered = fn.lower(state, arrays)
        if self._telemetry is not None:
            cost = self._lowered_cost(lowered)
            if cost is not None:
                self._telemetry.goodput.set_step_cost(
                    shape_key, cost['flops'], cost['bytes_accessed'])
        self._legend.add(shape_key, lowered, state)

    def train_program_memory(self, state: TrainerState, arrays
                             ) -> Optional[dict]:
        """AOT memory analysis of the train-step program for the shapes
        of ``arrays`` (either wire) — same record shape as
        ``predict_program_memory``. ``temp_bytes`` is the axis the
        ragged custom-VJP backward moves: the recompute schedule holds
        no (D, cap, .) residuals across the loss tail, so the fused
        train executable's temporary allocation drops against the
        autodiff twin's (benchmarks/bench_pallas_ragged.py records the
        per-arm value). Costs one extra XLA compile — bench/offline use
        only, never the hot path."""
        fn = (self._train_step_packed if _packed(arrays)
              else self._train_step)
        return self._program_memory(fn, state, arrays)

    def predict_step(self, params, batch: Batch, tier: str = 'full'
                     ) -> dict:
        """Predict over a host batch. Plane batches follow the configured
        wire format: under 'packed' the batch is packed here (the REPL
        keeps its plane/strings view) so prediction exercises the same
        wire + device-unpack path as training."""
        if isinstance(batch, Batch) and \
                self.config.wire_format_for(jax.process_count()) == 'packed':
            batch = packed_lib.pack_batch(
                batch, self._token_pad, self._path_pad,
                data_shards=self.mesh.shape[mesh_lib.DATA_AXIS])
        arrays = mesh_lib.shard_batch(batch.device_arrays(), self.mesh,
                                      self.config.SHARD_CONTEXTS)
        return self.predict_step_placed(params, arrays, tier=tier)

    # ----------------------------------------------------------- main loop
    def fit(self, state: TrainerState,
            epoch_batches: Callable[[int], Iterable[Batch]],
            start_epoch: int = 0,
            on_epoch_end: Optional[Callable[[int, TrainerState, int],
                                            None]] = None,
            on_log: Optional[Callable[[int, float, float], None]] = None,
            on_eval_interval: Optional[Callable[[int, TrainerState],
                                                None]] = None,
            on_save_interval: Optional[Callable[[int, int, TrainerState],
                                                None]] = None,
            on_epoch_time: Optional[Callable[[int, int, float],
                                             None]] = None,
            preemption=None,
            on_preempt: Optional[Callable[[int, int, TrainerState],
                                          None]] = None,
            on_divergence: Optional[Callable[[int],
                                             Optional[TrainerState]]] = None
            ) -> TrainerState:
        """Epoch-driven loop with the reference's windowed throughput trace
        (tensorflow_model.py:74-101, 424-430).

        ``on_epoch_time(epoch, batch_num, seconds)`` receives each epoch's
        training wall time (the loop over its batches, including interval
        evals; excluding ``on_epoch_end``'s eval/save) — model_api routes
        it into the metrics writer.

        Resilience hooks (ROBUSTNESS.md): ``preemption`` is a
        ``PreemptionHandler`` polled at step boundaries — when it has a
        pending signal the loop runs ``on_preempt(epoch, batch_num,
        state)`` (the final snapshot save) and returns cleanly.
        ``on_divergence(last_good_step)`` restores the newest checkpoint
        at or before that step for the divergence guard, returning a
        ``TrainerState`` or None."""
        config = self.config
        log_every = config.NUM_BATCHES_TO_LOG_PROGRESS
        # resumed runs continue the step axis instead of restarting at 0
        # (metric streams are append-mode)
        batch_num = start_epoch * config.train_steps_per_epoch
        window_losses = []  # device arrays: no per-step host sync, the
        window_examples = 0  # host only blocks once per log window
        window_start = time.time()
        guard = None
        watchdog = None
        if config.DIVERGENCE_GUARD:
            from code2vec_tpu.resilience.guard import DivergenceGuard
            from code2vec_tpu.telemetry.stepwatch import telemetry_dir
            guard = DivergenceGuard(
                config.MAX_DIVERGENCE_REWINDS, restore=on_divergence,
                dump_dir=telemetry_dir(config), log=config.log,
                telemetry=self._telemetry)
        if config.HANG_WATCHDOG_SECS > 0:
            from code2vec_tpu.resilience.watchdog import HangWatchdog
            from code2vec_tpu.telemetry.stepwatch import telemetry_dir
            tele = self._telemetry
            watchdog = HangWatchdog(
                config.HANG_WATCHDOG_SECS,
                dump_dir=telemetry_dir(config), log=config.log,
                # metrics.jsonl must record the run's last healthy state
                # before the abort
                on_expire=((lambda: tele.flush_now(
                    getattr(self, '_last_batch_num', 0)))
                    if tele is not None else None))
        try:
            state = self._fit_loop(
                state, epoch_batches, start_epoch, on_epoch_end, on_log,
                on_eval_interval, on_save_interval, batch_num, window_losses,
                window_examples, window_start, log_every, on_epoch_time,
                guard=guard, watchdog=watchdog, preemption=preemption,
                on_preempt=on_preempt)
        except Exception as exc:
            # OOM forensics (telemetry/memory.py): a RESOURCE_EXHAUSTED
            # surfacing anywhere in the hot loop — dispatch or the
            # blocking window sync — dumps the attribution ledger
            # before the run dies with an otherwise bare XLA error
            from code2vec_tpu.telemetry import memory as memory_lib
            memory_lib.ledger().note_oom(exc, 'trainer.fit')
            raise
        finally:
            if watchdog is not None:
                watchdog.shutdown()
            if getattr(self, '_profiling', False):
                jax.profiler.stop_trace()
                self._legend.write(config.PROFILE_DIR)
                self._profiling = False
            if self._telemetry is not None:
                # final flush + stop any live on-demand capture, so a
                # crashing run still leaves metrics.jsonl current
                self._telemetry.shutdown(getattr(self, '_last_batch_num', 0))
        return state

    @staticmethod
    def _num_valid_contexts(host_batch) -> int:
        """Contexts a batch feeds the step: retained slots for the packed
        wire (count), mask-valid slots for planes. Telemetry-path only.
        NB: on plane batches ``.count`` resolves to the tuple METHOD, so
        probe by array-ness, not truthiness."""
        count = getattr(host_batch, 'count', None)
        if isinstance(count, np.ndarray):
            return int(count.sum())
        return int(host_batch.mask.sum())

    def _fit_loop(self, state, epoch_batches, start_epoch, on_epoch_end,
                  on_log, on_eval_interval, on_save_interval, batch_num,
                  window_losses, window_examples, window_start, log_every,
                  on_epoch_time=None, guard=None, watchdog=None,
                  preemption=None, on_preempt=None):
        config = self.config
        tele = self._telemetry
        if watchdog is None:
            # the shared nullcontext is stateless and reusable; taking
            # (and discarding) the label args keeps the disabled path
            # free of any per-batch string formatting
            null_ctx = contextlib.nullcontext()

            def watched(label_fmt, step):
                return null_ctx
        else:
            def watched(label_fmt, step):
                return watchdog.watch(label_fmt % step)
        host_batch = None

        def rewind(losses_host):
            """Divergence-guard rewind over the current window — reads
            the loop's batch_num/host_batch/state at call time; raises
            DivergenceError when the guard is out of options.  step_now
            keys the rewind ceiling in state.step units (after an
            earlier rewind they lag batch_num, and checkpoints are
            keyed by state.step)."""
            step_before = int(state.step)
            with goodput_lib.interval(goodput_lib.KIND_REWIND):
                new_state = guard.handle(batch_num,
                                         [float(x) for x in losses_host],
                                         host_batch,
                                         step_now=step_before)
            if tele is not None:
                # the steps from the restored checkpoint back to the
                # rewind point re-train lost progress: badput, not
                # productive (goodput ledger bills them as they run)
                tele.goodput.mark_replay(step_before
                                         - int(new_state.step))
            return new_state
        if tele is not None:
            tele.resume()  # shutdown() in fit's finally disables globally
        self._profiling = False
        profile_done = False
        # profile window is relative to THIS run's first batch so resumed
        # runs (batch_num starts past 0) still capture a trace
        first_batch = batch_num
        profile_start = first_batch + config.PROFILE_START_STEP
        profile_stop_step = profile_start + config.PROFILE_NUM_STEPS
        for epoch in range(start_epoch, config.NUM_TRAIN_EPOCHS):
            epoch_start = time.time()
            staged = iter(self.stage_batches(epoch_batches(epoch)))
            while True:
                # batch-wait: host time blocked on the input pipeline for
                # the next staged batch (the starvation signal). The
                # generator's h2d placement runs INSIDE this next() and is
                # timed separately (stage_batches) — subtract it so wait
                # measures pipeline starvation, not placement.
                if tele is not None:
                    h2d_before = tele.h2d.total
                    iter_t0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation('host/batch_wait'), \
                            watched('next staged batch (batch %d)',
                                    batch_num):
                        item = next(staged, None)
                    wait_s = max(
                        0.0, (time.perf_counter() - iter_t0)
                        - (tele.h2d.total - h2d_before))
                    tele.batch_wait.record(wait_s)
                    # iteration-start mark for the goodput ledger; wait
                    # beyond the pipeline's steady poll cost is badput
                    tele.goodput.note_input_wait(wait_s)
                else:
                    with watched('next staged batch (batch %d)', batch_num):
                        item = next(staged, None)
                if item is None:
                    break
                # preemption (ROBUSTNESS.md pillar 2): the signal handler
                # only sets a flag; the exit happens HERE, at a step
                # boundary, so the saved state is a completed step and
                # resume loses at most the batch just pulled
                if preemption is not None and preemption.requested:
                    config.log(
                        'Preemption (%s): leaving the fit loop at step '
                        'boundary %d for a final snapshot save.'
                        % (preemption.signal_name, batch_num))
                    if on_preempt is not None:
                        with goodput_lib.interval(goodput_lib.KIND_PREEMPT):
                            on_preempt(epoch, batch_num, state)
                    if tele is not None:
                        tele.goodput.run_end(batch_num, reason='preempt')
                    return state
                arrays, host_batch = item
                # step-interval checkpointing fires at the TOP of the next
                # iteration (state reflects batch_num completed steps): an
                # interval landing on an epoch's final step must not
                # pre-empt on_epoch_end's save, which records the completed
                # epoch for resume. Async, so it costs one device->host
                # copy, not a persistence stall.
                if on_save_interval is not None and batch_num > 0 and \
                        config.SAVE_EVERY_N_STEPS > 0 and \
                        batch_num % config.SAVE_EVERY_N_STEPS == 0:
                    with goodput_lib.interval(goodput_lib.KIND_CHECKPOINT):
                        on_save_interval(epoch, batch_num, state)
                if config.PROFILE_DIR and not profile_done:
                    # jax.profiler cannot nest: the fixed window must also
                    # yield to a live on-demand capture (the controller
                    # already yields to _profiling — both directions)
                    on_demand_active = (tele is not None
                                        and tele.trace.active)
                    if batch_num >= profile_start and not self._profiling \
                            and not on_demand_active:
                        jax.profiler.start_trace(config.PROFILE_DIR)
                        self._profiling = True
                    elif batch_num >= profile_stop_step and self._profiling:
                        jax.block_until_ready(state.params)
                        jax.profiler.stop_trace()
                        self._legend.write(config.PROFILE_DIR)
                        self._profiling = False
                        profile_done = True
                        config.log('Profiler trace written to `%s`.'
                                   % config.PROFILE_DIR)
                    if tele is None:
                        # the legend needs the program with PROFILE_DIR
                        # alone too (under telemetry: below)
                        shape_key, _ = self._shape_key(arrays)
                        self._first_sight(shape_key, state, arrays)
                        if self._profiling:
                            self._legend.ran(shape_key)
                if tele is not None:
                    if not self._profiling:
                        # on-demand capture (TELEMETRY_TRACE_AT_STEP /
                        # touch file); inert while PROFILE_DIR's fixed
                        # window holds the profiler
                        tele.trace.maybe_update(batch_num,
                                                sync_tree=state.params)
                    shape_key, shapes = self._shape_key(arrays)
                    if shapes:
                        tele.capacity.observe(shapes[0], batch_num,
                                              rows=shapes[1:])
                    # first sight of a dispatch shape: AOT step FLOPs/
                    # bytes for the MFU gauges and the text for the
                    # captures' legend, from one lowering
                    self._first_sight(shape_key, state, arrays)
                    if self._profiling or tele.trace.active:
                        self._legend.ran(shape_key)
                    with jax.profiler.StepTraceAnnotation(
                            'train', step_num=batch_num), \
                            tele.dispatch.time():
                        state, loss = self.train_step_placed(state, arrays)
                else:
                    state, loss = self.train_step_placed(state, arrays)
                if faults.maybe_fire('slow_step', step=batch_num):
                    # a sustained per-step stall shaped like a degraded
                    # input stage or a throttled device — the step-time
                    # anomaly watchdog's drill (OBSERVABILITY.md)
                    time.sleep(faults.SLOW_STEP_SECONDS)
                if faults.maybe_fire('nan_loss', step=batch_num):
                    # poison on device: keeps the real loss's dtype and
                    # sharding, so the window sync path is exercised
                    # exactly as a genuine divergence would
                    loss = loss + float('nan')
                batch_num += 1
                if faults.maybe_fire('sigterm', step=batch_num):
                    os.kill(os.getpid(), signal_lib.SIGTERM)
                window_losses.append(loss)
                n_valid = host_batch.num_valid_examples
                window_examples += n_valid
                if tele is not None:
                    tele.count_batch(n_valid,
                                     self._num_valid_contexts(host_batch))
                if batch_num % log_every == 0:
                    # device_get, not eager jnp ops: stacking mesh-sharded
                    # scalars eagerly aborts in jaxlib on CPU meshes
                    if tele is not None:
                        sync_t0 = time.perf_counter()
                        with jax.profiler.TraceAnnotation('host/sync'), \
                                watched('log-window device sync (batch %d)',
                                        batch_num):
                            losses = jax.device_get(window_losses)
                        tele.sync.record(time.perf_counter() - sync_t0)
                    else:
                        with watched('log-window device sync (batch %d)',
                                     batch_num):
                            losses = jax.device_get(window_losses)
                    sum_loss = float(np.sum(losses))
                    # divergence guard (ROBUSTNESS.md pillar 1): the sum
                    # is non-finite iff any loss in the window is, so the
                    # check piggybacks on this sync at zero extra host
                    # round-trips
                    if guard is not None and not np.isfinite(sum_loss):
                        state = rewind(losses)
                        window_losses = []
                        window_examples = 0
                        window_start = time.time()
                        continue
                    elapsed = time.time() - window_start
                    throughput = window_examples / max(elapsed, 1e-9)
                    config.log(
                        'Average loss at batch %d: %f, \tthroughput: %d '
                        'samples/sec' % (batch_num,
                                         sum_loss / len(window_losses),
                                         throughput))
                    if on_log is not None:
                        on_log(batch_num, sum_loss / len(window_losses),
                               throughput)
                    window_losses = []
                    window_examples = 0
                    window_start = time.time()
                # mid-epoch evaluation (the reference Keras backend's
                # ModelEvaluationCallback every NUM_TRAIN_BATCHES_TO_EVALUATE
                # batches, keras_model.py:326-345, config.py:53)
                if on_eval_interval is not None and \
                        config.NUM_TRAIN_BATCHES_TO_EVALUATE > 0 and \
                        batch_num % config.NUM_TRAIN_BATCHES_TO_EVALUATE == 0:
                    # the reset below DISCARDS the partial window, so the
                    # guard must check it first or a NaN between log
                    # boundaries slips through unexamined (and the eval
                    # would run — and log — on a possibly-diverged state)
                    if guard is not None and window_losses:
                        with watched('eval-interval window sync (batch %d)',
                                     batch_num):
                            losses = jax.device_get(window_losses)
                        if not np.isfinite(float(np.sum(losses))):
                            state = rewind(losses)
                            window_losses = []
                            window_examples = 0
                            window_start = time.time()
                            continue
                    with goodput_lib.interval(goodput_lib.KIND_EVAL):
                        on_eval_interval(batch_num, state)
                    # restart the throughput window completely: a partial
                    # window timed from post-eval would overstate samples/sec
                    window_losses = []
                    window_examples = 0
                    window_start = time.time()
                if tele is not None:
                    iter_secs = time.perf_counter() - iter_t0
                    tele.step_total.record(iter_secs)
                    # goodput: clean step seconds = iteration minus the
                    # badput accrued inside it; compile-free samples feed
                    # the step-time anomaly watchdog
                    clean_s, had_compile = tele.goodput.step_done(
                        batch_num, iter_secs, shape_key)
                    if not had_compile:
                        tele.anomaly.observe(shape_key, clean_s, batch_num)
                    tele.after_step(batch_num)
                    self._last_batch_num = batch_num
            if (tele is not None or guard is not None) and window_losses:
                # short epochs (steps/epoch < log_every) may never hit a
                # log window: sync the partial window here so step/sync_ms
                # is recorded at least once per epoch AND the divergence
                # guard examines every epoch's losses — without this a
                # NaN in a short run is never detected (the losses stay
                # in the window; this sync does not consume them). With
                # telemetry off the guard pays this one extra device_get
                # per EPOCH, not per step.
                sync_t0 = time.perf_counter()
                with watched('epoch-end window sync (batch %d)', batch_num):
                    losses = jax.device_get(window_losses)
                if tele is not None:
                    sync_s = time.perf_counter() - sync_t0
                    tele.sync.record(sync_s)
                    # this sync drains dispatched device work — real
                    # training progress outside any iteration's seconds
                    tele.goodput.note_productive(sync_s)
                if guard is not None and \
                        not np.isfinite(float(np.sum(losses))):
                    state = rewind(losses)
                    window_losses = []
                    window_examples = 0
            epoch_wall = time.time() - epoch_start
            if tele is not None:
                tele.registry.gauge('train/epoch_wall_time_s').set(
                    epoch_wall)
            if on_epoch_time is not None:
                on_epoch_time(epoch, batch_num, epoch_wall)
            if on_epoch_end is not None:
                # pass the ACTUAL global batch number: estimates from the
                # unfiltered line count would put eval metrics on a
                # different (non-monotonic) step axis than interval evals
                on_epoch_end(epoch, state, batch_num)
                window_start = time.time()  # don't bill eval/save time
        return state


def as_numpy(tree):
    """Fetch a pytree of device arrays to host numpy."""
    return jax.tree_util.tree_map(np.asarray, tree)
