"""Experimental Pallas TPU kernel: fused softmax cross-entropy over the
target vocabulary ("flash CE").

The training loss needs only ``logsumexp(logits)`` and ``logits[label]``
per example (models/functional.py::weighted_ce_sums), yet the XLA path
materializes the full (B, V) logits matrix in HBM to get them — at the
java14m configuration (B=1024, V=261K) that is ~1.07 GB written + read in
the forward and another ~1.07 GB of d(logits) written + read twice in the
backward, ~4.3 GB of the step's 20.6 GB HBM traffic (PERF.md). The
reference pays the same cost on GPU via
``sparse_softmax_cross_entropy_with_logits`` over materialized logits
(reference tensorflow_model.py:226-230).

This kernel streams the target-embedding table through VMEM in vocab
blocks instead, the way flash attention streams keys:

  forward:  online (max, sumexp) accumulation per block -> lse, plus the
            label's logit picked with a block-local one-hot dot; logits
            never leave VMEM.
  backward: recompute each logits block from (code, W_block, lse) and
            contract it immediately: dW_j = dlogits_j^T @ code written
            per block, dcode accumulated in VMEM scratch. d(logits) never
            exists in HBM either.

Multi-device meshes route through :func:`sharded_fused_weighted_ce_sums`,
which shard_maps the kernel: the target table stays row-sharded over the
``model`` axis (each shard streams only its V/m rows), the batch stays
sharded over ``data``, and the per-shard online-softmax stats are merged
with pmax/psum over ICI — the same candidates-only traffic philosophy as
ops/topk.py::sharded_top_k. GSPMD alone cannot do this: a pallas_call is
opaque to the partitioner, so under plain jit it would be replicated
(full batch + full table on every device), negating the sharding.

OFF by default (``Config.USE_PALLAS_FUSED_CE``) until the on-chip A/B
(benchmarks/bench_fused_ce.py) records a win; correctness is tested in
interpreter mode on CPU against the jnp path (tests/test_pallas_ce.py),
including gradients and the sharded variant on a (4, 2) mesh.
Eval/predict keep the materialized-logits path — they need the full
matrix for top-k anyway.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code2vec_tpu.ops._pallas_common import resolve_interpret
from code2vec_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

# vocab columns per grid step. VMEM at java14m shapes (B=1024, D=384,
# tile 1024): fwd ~8 MB, bwd ~11 MB incl. the f32 dlogits block, double-
# buffered weight blocks and the dcode accumulator — comfortably under the
# ~16 MB/core budget; 2048 would put the backward at ~18 MB.
# PALLAS_CE_VOCAB_TILE overrides it (VERDICT r3 #4 contingency: if Mosaic
# compile stalls at java14m shapes inside a capture window, the bench
# harness retries with smaller tiles unattended).
_DEFAULT_VOCAB_TILE = 1024


def _parse_vocab_tile(raw: str) -> int:
    """Validate the PALLAS_CE_VOCAB_TILE override instead of letting a bad
    value crash every import (including CPU-only paths) or silently pick a
    tile the kernel can't run: must be a positive multiple of 128 (the TPU
    lane width); above 1024 the backward pass blows the ~16 MB VMEM budget
    (see above), so warn and proceed — Mosaic gives the real verdict."""
    import warnings
    try:
        tile = int(raw)
    except (TypeError, ValueError):
        warnings.warn(
            'PALLAS_CE_VOCAB_TILE=%r is not an integer; using the default '
            '%d' % (raw, _DEFAULT_VOCAB_TILE))
        return _DEFAULT_VOCAB_TILE
    if tile <= 0 or tile % 128:
        warnings.warn(
            'PALLAS_CE_VOCAB_TILE=%d must be a positive multiple of 128; '
            'using the default %d' % (tile, _DEFAULT_VOCAB_TILE))
        return _DEFAULT_VOCAB_TILE
    if tile > 1024:
        warnings.warn(
            'PALLAS_CE_VOCAB_TILE=%d exceeds 1024: the backward pass '
            'likely exceeds the ~16 MB VMEM budget at java14m shapes'
            % tile)
    return tile


VOCAB_TILE = _parse_vocab_tile(
    os.environ.get('PALLAS_CE_VOCAB_TILE', str(_DEFAULT_VOCAB_TILE)))
_NEG = -1e30        # finite -inf stand-in (denormal-safe, like _MASK_MIN)


def _fwd_kernel(precision, code_ref, w_ref, label_ref, nv_ref,
                lse_ref, picked_ref, m_ref, s_ref, p_ref):
    j = pl.program_id(0)
    block = w_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        s_ref[:] = jnp.zeros_like(s_ref)
        p_ref[:] = jnp.zeros_like(p_ref)

    logits = jnp.dot(code_ref[:], w_ref[:].T, precision=precision,
                     preferred_element_type=jnp.float32)      # (B, VB)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + j * block
    # num_valid arrives as a (1, 1) block so it can be a traced, shard-
    # local value under shard_map (a static closure value could not be)
    valid = col < nv_ref[:]
    logits = jnp.where(valid, logits, _NEG)

    # label pick: at most one VALID column matches per row across ALL
    # blocks. The valid gate matters under shard_map: a label owned by the
    # NEXT shard can collide with this shard's tile-pad window (columns
    # [vshard, padded_vshard)) — ungated, that match would add the _NEG
    # sentinel into the psum-merged pick and explode the loss.
    onehot = jnp.where((col == label_ref[:]) & valid, 1.0, 0.0)
    p_ref[:] += jnp.sum(logits * onehot, axis=1, keepdims=True)

    m_old = m_ref[:]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
    s_ref[:] = (s_ref[:] * jnp.exp(m_old - m_new)
                + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_ref[:] = m_new

    @pl.when(j == pl.num_programs(0) - 1)
    def _finish():
        lse_ref[:] = m_ref[:] + jnp.log(s_ref[:])
        picked_ref[:] = p_ref[:]


def _bwd_kernel(precision, code_ref, w_ref, label_ref, nv_ref, lse_ref,
                dlse_ref, dpicked_ref, dw_ref, dcode_ref, acc_ref):
    j = pl.program_id(0)
    block = w_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    logits = jnp.dot(code_ref[:], w_ref[:].T, precision=precision,
                     preferred_element_type=jnp.float32)      # (B, VB)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + j * block
    valid = col < nv_ref[:]
    softmax = jnp.where(valid, jnp.exp(logits - lse_ref[:]), 0.0)
    # the valid mask keeps the vjp the true linearization even for a
    # label in the masked range: the forward picks the _NEG constant
    # there, which has zero dependence on w and code
    onehot = jnp.where((col == label_ref[:]) & valid, 1.0, 0.0)
    dlogits = dlse_ref[:] * softmax + dpicked_ref[:] * onehot  # (B, VB) f32

    compute_dtype = code_ref.dtype
    dw_ref[:] = jnp.dot(dlogits.astype(compute_dtype).T, code_ref[:],
                        precision=precision,
                        preferred_element_type=jnp.float32)    # (VB, D)
    acc_ref[:] += jnp.dot(dlogits.astype(compute_dtype), w_ref[:],
                          precision=precision,
                          preferred_element_type=jnp.float32)  # (B, D)

    @pl.when(j == pl.num_programs(0) - 1)
    def _finish():
        dcode_ref[:] = acc_ref[:]


def _pad_vocab(w: jax.Array) -> jax.Array:
    v = w.shape[0]
    padded = -(-v // VOCAB_TILE) * VOCAB_TILE
    if padded != v:
        w = jnp.pad(w, ((0, padded - v), (0, 0)))
    return w


def _precision(dtype) -> jax.lax.Precision:
    """Mirror compute_logits: fp32 asks for true-fp32 MXU passes (TPU f32
    matmuls otherwise lower to bf16 passes), bf16 uses the fast path."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _nv_block(num_valid) -> jax.Array:
    """num_valid as the (1, 1) int32 block the kernels read. Accepts a
    static int or a traced scalar (the shard-local clip under shard_map)."""
    return jnp.full((1, 1), num_valid, jnp.int32)


def _forward(code, w, label, num_valid, interpret):
    batch, dim = code.shape
    w = _pad_vocab(w)
    grid = (w.shape[0] // VOCAB_TILE,)
    label2d = label.astype(jnp.int32).reshape(batch, 1)
    kernel = functools.partial(_fwd_kernel, _precision(code.dtype))
    lse, picked = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch, dim), lambda j: (0, 0)),        # code
            pl.BlockSpec((VOCAB_TILE, dim), lambda j: (j, 0)),   # w block
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),          # label
            pl.BlockSpec((1, 1), lambda j: (0, 0)),              # num_valid
        ],
        out_specs=[
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((batch, 1), jnp.float32),   # running max
            pltpu.VMEM((batch, 1), jnp.float32),   # running sumexp
            pltpu.VMEM((batch, 1), jnp.float32),   # picked accumulator
        ],
        interpret=interpret,
    )(code, w, label2d, _nv_block(num_valid))
    return lse[:, 0], picked[:, 0]


def _backward(code, w, label, lse, dlse, dpicked, num_valid, interpret
              ) -> Tuple[jax.Array, jax.Array]:
    """(dw (V, D) f32, dcode (B, D) f32) from the saved lse — logits are
    recomputed blockwise, d(logits) never exists in HBM."""
    batch, dim = code.shape
    v = w.shape[0]
    w_padded = _pad_vocab(w)
    grid = (w_padded.shape[0] // VOCAB_TILE,)
    label2d = label.astype(jnp.int32).reshape(batch, 1)
    kernel = functools.partial(_bwd_kernel, _precision(code.dtype))
    dw, dcode = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch, dim), lambda j: (0, 0)),        # code
            pl.BlockSpec((VOCAB_TILE, dim), lambda j: (j, 0)),   # w block
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),          # label
            pl.BlockSpec((1, 1), lambda j: (0, 0)),              # num_valid
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),          # lse
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),          # dlse
            pl.BlockSpec((batch, 1), lambda j: (0, 0)),          # dpicked
        ],
        out_specs=[
            pl.BlockSpec((VOCAB_TILE, dim), lambda j: (j, 0)),   # dw block
            pl.BlockSpec((batch, dim), lambda j: (0, 0)),        # dcode
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_padded.shape[0], dim), jnp.float32),
            jax.ShapeDtypeStruct((batch, dim), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((batch, dim), jnp.float32),  # dcode accumulator
        ],
        interpret=interpret,
    )(code, w_padded, label2d, _nv_block(num_valid),
      lse.reshape(batch, 1),
      dlse.reshape(batch, 1).astype(jnp.float32),
      dpicked.reshape(batch, 1).astype(jnp.float32))
    return dw[:v], dcode


# ------------------------------------------------------- single device
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_lse_and_pick(code: jax.Array, w: jax.Array, label: jax.Array,
                       num_valid: int, interpret: bool
                       ) -> Tuple[jax.Array, jax.Array]:
    """(lse (B,), picked (B,)) of ``code @ w.T`` without materializing the
    (B, V) logits in HBM. ``num_valid`` masks padded vocab columns;
    ``label`` out-of-range rows pick 0 (they must carry weight 0, exactly
    like the XLA path's padded rows)."""
    lse, picked = _forward(code, w, label, num_valid, interpret)
    return lse, picked


def _vjp_fwd(code, w, label, num_valid, interpret):
    lse, picked = _forward(code, w, label, num_valid, interpret)
    return (lse, picked), (code, w, label, lse)


def _vjp_bwd(num_valid, interpret, residuals, cotangents):
    code, w, label, lse = residuals
    dlse, dpicked = cotangents
    dw, dcode = _backward(code, w, label, lse, dlse, dpicked,
                          num_valid, interpret)
    return (dcode.astype(code.dtype), dw.astype(w.dtype), None)


fused_lse_and_pick.defvjp(_vjp_fwd, _vjp_bwd)


def fused_weighted_ce_sums(params_target: jax.Array, code_vectors: jax.Array,
                           label: jax.Array, weight: jax.Array,
                           num_valid_targets: int,
                           dtype: jnp.dtype = jnp.float32,
                           interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for compute_logits + weighted_ce_sums in the TRAIN path:
    (weighted CE sum, weight sum) with no (B, V) HBM intermediate.

    ``dtype`` is the MXU compute dtype, mirroring compute_logits: the
    matmuls run in ``dtype`` with fp32 accumulation, reductions stay fp32.
    Compiled on a TPU; elsewhere ``KernelRequiresTPU`` unless a test
    passes ``interpret=True`` (ops/_pallas_common.py).
    """
    interpret = resolve_interpret(interpret, 'fused CE')
    lse, picked = fused_lse_and_pick(
        code_vectors.astype(dtype), params_target.astype(dtype),
        label, num_valid_targets, interpret)
    ce = lse - picked
    return (ce * weight).sum(), weight.sum()


# ------------------------------------------------ sharded (multi-device)
def _shard_offset(vocab_per_shard: int) -> jax.Array:
    return (jax.lax.axis_index(MODEL_AXIS) * vocab_per_shard).astype(
        jnp.int32)


def _sharded_forward(code, w, label, num_valid, mesh, interpret):
    vshard = w.shape[0] // mesh.shape[MODEL_AXIS]

    def local(code_blk, w_blk, label_blk):
        offset = _shard_offset(vshard)
        # labels owned by another shard fall out of [0, vshard) and match
        # no column; a shard whose rows are ALL allocation padding gets
        # local_valid == 0, every column masked to _NEG, and its
        # exp(lse - m) underflows to exactly 0 in the merge below
        lse_l, picked_l = _forward(
            code_blk, w_blk, label_blk.astype(jnp.int32) - offset,
            jnp.clip(num_valid - offset, 0, vshard), interpret)
        m = jax.lax.pmax(lse_l, MODEL_AXIS)
        lse = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), MODEL_AXIS))
        picked = jax.lax.psum(picked_l, MODEL_AXIS)
        return lse, picked

    # check_vma=False: outputs ARE replicated along 'model' after the
    # psum/pmax merge, but the static checker can't prove it (same as
    # ops/topk.py::sharded_top_k)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(MODEL_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False)(code, w, label)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def sharded_fused_lse_and_pick(code: jax.Array, w: jax.Array,
                               label: jax.Array, num_valid: int, mesh: Mesh,
                               interpret: bool
                               ) -> Tuple[jax.Array, jax.Array]:
    """fused_lse_and_pick over a (data, model) mesh: ``w`` row-sharded over
    ``model``, ``code``/``label`` sharded over ``data``. Per-shard online
    stats merge over ICI; cross-shard traffic is O(B) scalars per merge,
    never logits. The vjp is explicit (a second shard_map) rather than
    relying on collective transposition through the forward."""
    return _sharded_forward(code, w, label, num_valid, mesh, interpret)


def _sharded_vjp_fwd(code, w, label, num_valid, mesh, interpret):
    lse, picked = _sharded_forward(code, w, label, num_valid, mesh,
                                   interpret)
    return (lse, picked), (code, w, label, lse)


def _sharded_vjp_bwd(num_valid, mesh, interpret, residuals, cotangents):
    code, w, label, lse = residuals
    dlse, dpicked = cotangents
    vshard = w.shape[0] // mesh.shape[MODEL_AXIS]

    def local(code_blk, w_blk, label_blk, lse_blk, dlse_blk, dpicked_blk):
        offset = _shard_offset(vshard)
        # the GLOBAL lse is the residual, so each shard's recomputed
        # softmax block is already globally normalized; dw stays local to
        # the shard's rows, dcode sums contributions from every shard
        dw_l, dcode_p = _backward(
            code_blk, w_blk, label_blk.astype(jnp.int32) - offset, lse_blk,
            dlse_blk, dpicked_blk,
            jnp.clip(num_valid - offset, 0, vshard), interpret)
        # each partial is complete along its OWN axis only: dcode_p saw
        # just this shard's vocab rows (psum over model), dw_l saw just
        # this shard's batch rows (psum over data — the DP grad reduction
        # GSPMD would otherwise insert outside the shard_map)
        return (jax.lax.psum(dcode_p, MODEL_AXIS),
                jax.lax.psum(dw_l, DATA_AXIS))

    dcode, dw = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(MODEL_AXIS, None), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS, None), P(MODEL_AXIS, None)),
        check_vma=False)(code, w, label, lse,
                         dlse.astype(jnp.float32),
                         dpicked.astype(jnp.float32))
    return (dcode.astype(code.dtype), dw.astype(w.dtype), None)


sharded_fused_lse_and_pick.defvjp(_sharded_vjp_fwd, _sharded_vjp_bwd)


def sharded_fused_weighted_ce_sums(params_target: jax.Array,
                                   code_vectors: jax.Array,
                                   label: jax.Array, weight: jax.Array,
                                   num_valid_targets: int, mesh: Mesh,
                                   dtype: jnp.dtype = jnp.float32,
                                   interpret: bool = False
                                   ) -> Tuple[jax.Array, jax.Array]:
    """Multi-device drop-in for fused_weighted_ce_sums. Requires the
    padded target vocab divisible by the model axis (the trainer's
    PARAM_ROW_ALIGNMENT check guarantees it); per-shard rows that are not
    a VOCAB_TILE multiple still work via the kernel's own pad, at the cost
    of a per-step copy of the local shard (backends align the allocation
    to avoid this)."""
    interpret = resolve_interpret(interpret, 'sharded fused CE', mesh)
    lse, picked = sharded_fused_lse_and_pick(
        code_vectors.astype(dtype), params_target.astype(dtype),
        label, num_valid_targets, mesh, interpret)
    ce = lse - picked
    return (ce * weight).sum(), weight.sum()
