"""Ragged fused encode + attention straight off the packed wire.

The packed wire format (data/packed.py) ships each batch as per-shard
dense ``(data_shards, capacity, 3)`` context triples plus per-example
``count``s. Until now every consumer paid a full ``(B, max_contexts)``
segment-scatter (``unpack_device``) back to plane layout BEFORE the
encoder ran — at the java14m fill rate (contexts/method p50 28 of 200)
that materializes ~4x more context slots than the batch actually holds,
and the dense encode then spends FLOPs and HBM traffic on every one of
them. This module walks the packed segments directly instead:

  per slot t of the packed stream (slots past a shard's total and
  interior all-PAD holes are masked OUT, matching the dense path's
  ``log(1e-30)`` masking to fp32 rounding):

    e_t = [tok[src_t] ; path[pth_t] ; tok[tgt_t]]            gather
    x_t = tanh(e_t @ TRANSFORM)        (row-split, no concat) encode
    s_t = x_t . ATTENTION                                     score

  per example i (a SEGMENT of the stream, delimited by ``count``):

    m_i  = max_t s_t                 \\  single-pass max-sum softmax
    z_i  = sum_t exp(s_t - m_i)       |  (FuseMax, arxiv 2406.10491):
    c_i  = sum_t exp(s_t - m_i) x_t  /   one walk, no separate sweeps
    code_i = c_i / z_i

Two interchangeable implementations produce the same ``(scores, m, z,
acc)`` statistics:

- ``_stats_jnp`` — the reference twin: plain jnp segment ops (scatter
  max/add over the shard-structured stream), fully differentiable, runs
  everywhere and partitions under GSPMD (leading data_shards axis, like
  ``unpack_device``).
- ``_stats_pallas`` — the Pallas TPU kernel: one grid walk over slot
  tiles with the per-example running ``(m, z, acc)`` resident in VMEM,
  segment membership resolved per tile with an indicator matrix so the
  reductions ride the MXU/VPU (the FuseMax single pass — later tiles
  rescale earlier sums by ``exp(m_old - m_new)``). On multi-device
  meshes it must be ``shard_map``-ped over the data axis — a
  ``pallas_call`` is opaque to GSPMD and would otherwise be replicated
  (same reasoning as ``ops/pallas_ce.py``).

TRAIN path (``ragged_encode_code``, the custom VJP): the code-vector
encode is wrapped in ``jax.custom_vjp`` so the backward never stores a
per-slot residual. The forward saves only the per-example softmax stats
``(m, z)``, the ``(B, D)`` code vectors, and the inputs it was handed
(indices + params + the dropout PRNG key); the backward re-gathers the
embeddings, re-draws the SAME dropout mask from the threaded key, and
recomputes ``x``/``scores``/``w`` per slot tile — the FuseMax
recompute-over-store schedule — before emitting exact softmax-backward
gradients: TRANSFORM/ATTENTION densely (per-tile MXU accumulation) and
the token/path table gradients as segment scatter-adds over the packed
index stream. The ``(D, cap, 3d)`` gathered context
embeddings and the ``(D, cap, D)`` activations exist only transiently
inside each pass, never as residuals between them — the autodiff twin
saved all of them (tests/test_pallas_ragged.py asserts the residual set
via the vjp closure). Like the forward, the backward has two
implementations sharing one contract: a jnp twin (CPU/fallback — the
residual win applies there too) and a second Pallas kernel walking the
same packed ``(D, cap, 3)`` segments (``_bwd_kernel``), gated on-chip by
``Config.RAGGED_TRAIN_KERNEL`` pending the >=2% flip rule
(scripts/flip_verdict.py). Dropout now rides BOTH implementations: the
keep mask is drawn over the packed ``(shards, cap, 3d)`` layout outside
the kernels and applied to their embedding inputs, so the fused train
draw bit-matches the jnp twin's draw by construction.

VMEM at java14m serving shapes (per-shard segments Bs=1024, D=384,
SLOT_TILE=512, d=128): tile inputs ~0.8 MB, weights ~0.6 MB resident,
the (T, Bs) indicator + its two masked copies ~6 MB, the (D, Bs) f32
accumulator 1.5 MB — comfortably under the ~16 MB/core budget, and
independent of capacity (the grid scales instead).

Dense-path parity (``tests/test_pallas_ragged.py``): the dense encode
gives masked slots attention ``~e-30`` — zero at fp32 resolution — so
excluding them here matches to fp32 rounding; the one real divergence,
rows with ``count == 0`` (static-shape padding, weight 0), is fixed up
analytically (uniform ``1/C`` attention, ``code = x_pad``) to match the
dense path's finite-uniform behavior exactly. Dropout draws its keep
mask over the PACKED ``(shards, cap, 3d)`` layout rather than the dense
``(B, C, 3d)`` one — same keep probability, a different (still
deterministic, seed-keyed) stream, the ``DROPOUT_PRNG_IMPL='rbg'``
precedent.

Gated by ``Config.USE_PALLAS_RAGGED_FUSION`` (threaded through
models/backends.py and training/trainer.py). Kernel-or-twin is the
trainer's decision, made once from its mesh's platform and passed down
as ``use_kernel``; a kernel asked for off a TPU raises
``KernelRequiresTPU`` (ops/_pallas_common.py) — it never falls back to
the twin or the interpreter.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code2vec_tpu.ops._pallas_common import resolve_interpret
from code2vec_tpu.parallel.mesh import DATA_AXIS
from code2vec_tpu.scopes import scoped

SLOT_TILE = 512     # packed slots per grid step; capacity pads to a multiple
_NEG = -1e30        # finite -inf stand-in (denormal-safe, like pallas_ce)


def _precision(dtype) -> jax.lax.Precision:
    """Mirror the dense encode: fp32 asks for true-fp32 MXU passes, bf16
    uses the fast path (models/functional.py::encode)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


# ------------------------------------------------------------ jnp twin
def _stats_jnp(src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path, w_tgt,
               attn_vec, per_shard: int, precision):
    """Reference twin of the kernel: (scores, m, z, acc) via jnp segment
    ops on the shard-structured stream. Differentiable (the segment max
    is stop-gradiented — softmax is shift-invariant, so the gradient is
    exact) and GSPMD-partitionable along the leading shards axis."""
    shards, cap = seg.shape
    x = jnp.tanh(jnp.matmul(src_e, w_src, precision=precision)
                 + jnp.matmul(pth_e, w_path, precision=precision)
                 + jnp.matmul(tgt_e, w_tgt, precision=precision))
    scores = jnp.matmul(x, attn_vec,
                        precision=precision)[..., 0]         # (D, cap)
    scores = jnp.where(slot_valid, scores.astype(jnp.float32), _NEG)
    shard_idx = jnp.broadcast_to(
        jnp.arange(shards, dtype=jnp.int32)[:, None], (shards, cap))
    m = jnp.full((shards, per_shard), _NEG, jnp.float32)
    m = m.at[shard_idx, seg].max(scores, mode='drop')
    m = jax.lax.stop_gradient(m)
    p = jnp.exp(scores - jnp.take_along_axis(m, seg, axis=1))
    p = jnp.where(slot_valid, p, 0.0)                        # (D, cap)
    z = jnp.zeros((shards, per_shard), jnp.float32)
    z = z.at[shard_idx, seg].add(p, mode='drop')
    acc = jnp.zeros((shards, per_shard, x.shape[-1]), jnp.float32)
    acc = acc.at[shard_idx, seg].add(
        p[..., None] * x.astype(jnp.float32), mode='drop')
    return scores, m, z, acc


# -------------------------------------------------------- pallas kernel
def _ragged_kernel(precision, src_ref, pth_ref, tgt_ref, seg_ref, valid_ref,
                   wsrc_ref, wpath_ref, wtgt_ref, attn_ref,
                   scores_ref, m_out_ref, z_out_ref, acc_out_ref,
                   m_ref, z_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        z_ref[:] = jnp.zeros_like(z_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # encode: row-split transform + tanh + score, fp32 accumulation
    x = jnp.dot(src_ref[:], wsrc_ref[:], precision=precision,
                preferred_element_type=jnp.float32)
    x += jnp.dot(pth_ref[:], wpath_ref[:], precision=precision,
                 preferred_element_type=jnp.float32)
    x += jnp.dot(tgt_ref[:], wtgt_ref[:], precision=precision,
                 preferred_element_type=jnp.float32)
    x = jnp.tanh(x)                                          # (T, D) f32
    sc = jnp.dot(x, attn_ref[:], precision=precision,
                 preferred_element_type=jnp.float32)         # (T, 1)
    valid = valid_ref[:] > 0.0                               # (T, 1)
    sc = jnp.where(valid, sc, _NEG)
    scores_ref[:] = sc

    # segment membership for this tile: a (T, n_seg) indicator so every
    # per-example reduction is one masked reduce / one MXU contraction
    n_seg = m_ref.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (sc.shape[0], n_seg), 1)
    onehot_b = (seg_ref[:] == lanes) & valid                 # (T, n_seg)
    onehot = onehot_b.astype(jnp.float32)

    # FuseMax single pass: fold this tile's per-segment max into the
    # running max, rescale the running sums, accumulate the tile
    m_tile = jnp.max(jnp.where(onehot_b, sc, _NEG),
                     axis=0, keepdims=True)                  # (1, n_seg)
    m_new = jnp.maximum(m_ref[:], m_tile)
    corr = jnp.exp(m_ref[:] - m_new)                         # (1, n_seg)
    m_ref[:] = m_new
    m_slot = jnp.sum(onehot * m_new, axis=1, keepdims=True)  # (T, 1)
    p = jnp.where(valid, jnp.exp(sc - m_slot), 0.0)          # (T, 1)
    pz = onehot * p                                          # (T, n_seg)
    z_ref[:] = z_ref[:] * corr + jnp.sum(pz, axis=0, keepdims=True)
    # acc lives (D, n_seg) so the rescale broadcasts along rows and the
    # tile contraction is a single dot_general over the slot axis
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        x, pz, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)                  # (D, n_seg)

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        m_out_ref[:] = m_ref[:]
        z_out_ref[:] = z_ref[:]
        acc_out_ref[:] = acc_ref[:]


def _stats_pallas(src_e, pth_e, tgt_e, seg, valid, w_src, w_path, w_tgt,
                  attn_vec, n_seg: int, interpret: bool, precision):
    """One shard's flat packed stream ``(cap, d)`` -> ``(scores (cap,),
    m (n_seg,), z (n_seg,), acc (n_seg, D))`` via the fused kernel."""
    cap, token_dim = src_e.shape
    path_dim = pth_e.shape[1]
    code_dim = w_src.shape[1]
    padded = -(-cap // SLOT_TILE) * SLOT_TILE
    pad = padded - cap
    if pad:
        src_e = jnp.pad(src_e, ((0, pad), (0, 0)))
        pth_e = jnp.pad(pth_e, ((0, pad), (0, 0)))
        tgt_e = jnp.pad(tgt_e, ((0, pad), (0, 0)))
        seg = jnp.pad(seg, (0, pad))
        valid = jnp.pad(valid, (0, pad))     # False: pad slots are inert
    seg2 = seg.reshape(padded, 1).astype(jnp.int32)
    valid2 = valid.reshape(padded, 1).astype(jnp.float32)
    grid = (padded // SLOT_TILE,)
    row_block = lambda dim: pl.BlockSpec((SLOT_TILE, dim),
                                         lambda i: (i, 0))
    full_block = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    kernel = functools.partial(_ragged_kernel, precision)
    scores, m, z, acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row_block(token_dim), row_block(path_dim), row_block(token_dim),
            row_block(1), row_block(1),
            full_block(w_src.shape), full_block(w_path.shape),
            full_block(w_tgt.shape), full_block(attn_vec.shape),
        ],
        out_specs=[
            row_block(1),
            full_block((1, n_seg)), full_block((1, n_seg)),
            full_block((code_dim, n_seg)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, n_seg), jnp.float32),
            jax.ShapeDtypeStruct((1, n_seg), jnp.float32),
            jax.ShapeDtypeStruct((code_dim, n_seg), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, n_seg), jnp.float32),       # running max
            pltpu.VMEM((1, n_seg), jnp.float32),       # running sumexp
            pltpu.VMEM((code_dim, n_seg), jnp.float32),  # weighted sum
        ],
        interpret=interpret,
    )(src_e, pth_e, tgt_e, seg2, valid2, w_src, w_path, w_tgt, attn_vec)
    return scores[:cap, 0], m[0], z[0], acc.T


def _stats_kernel_path(src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                       w_tgt, attn_vec, per_shard: int, mesh,
                       interpret: bool, precision):
    """Kernel stats over the shard-structured stream. With a multi-device
    mesh the per-shard kernel is shard_mapped over the data axis (a
    pallas_call is opaque to GSPMD); otherwise the shards collapse into
    one flat stream with globally-offset segment ids — one kernel call,
    one set of scratch accumulators."""
    shards, cap = seg.shape

    def one_shard(src_l, pth_l, tgt_l, seg_l, valid_l, ws, wp, wt, av):
        sc, m, z, acc = _stats_pallas(
            src_l[0], pth_l[0], tgt_l[0], seg_l[0], valid_l[0],
            ws, wp, wt, av, per_shard, interpret, precision)
        return (sc[None], m[None], z[None], acc[None])

    if mesh is not None and mesh.size > 1:
        # check_vma=False: outputs follow the data axis exactly like the
        # inputs, but the static checker can't see through the kernel
        # (same as ops/pallas_ce.py::_sharded_forward)
        return jax.shard_map(
            one_shard, mesh=mesh,
            in_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                      P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                      P(DATA_AXIS, None), P(None, None), P(None, None),
                      P(None, None), P(None, None)),
            out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None),
                       P(DATA_AXIS, None), P(DATA_AXIS, None, None)),
            check_vma=False)(src_e, pth_e, tgt_e, seg, slot_valid,
                             w_src, w_path, w_tgt, attn_vec)
    # single device: one flat stream, segment ids offset per shard
    flat = shards * cap
    offsets = (jnp.arange(shards, dtype=jnp.int32) * per_shard)[:, None]
    seg_flat = (seg + offsets).reshape(flat)
    sc, m, z, acc = _stats_pallas(
        src_e.reshape(flat, -1), pth_e.reshape(flat, -1),
        tgt_e.reshape(flat, -1), seg_flat, slot_valid.reshape(flat),
        w_src, w_path, w_tgt, attn_vec, shards * per_shard, interpret,
        precision)
    return (sc.reshape(shards, cap), m.reshape(shards, per_shard),
            z.reshape(shards, per_shard),
            acc.reshape(shards, per_shard, -1))


# ------------------------------------------------------------- finish
def _code_from_stats(z, acc, count2, x_pad):
    """(z, acc) stats -> (D, Bs, Dc) fp32 code vectors, with the
    count == 0 analytic fixup (code = x_pad)."""
    nonempty = count2 > 0                                    # (D, Bs)
    # guard empty segments' 0/0 (the fixup below overwrites them). NOT
    # jnp.maximum(z, 1.0): a single-valid-slot segment has z == 1.0
    # exactly (its max slot contributes exp(0)), and jax halves the
    # gradient of maximum at ties — which would silently halve those
    # rows' softmax-normalization gradient
    z_safe = jnp.where(nonempty, z, 1.0)
    code = acc / z_safe[..., None]
    return jnp.where(nonempty[..., None], code,
                     x_pad.astype(jnp.float32)[None, None, :])


def _finish(scores, m, z, acc, seg, pos, slot_valid, count2, x_pad,
            max_contexts: int):
    """(stats, segment structure) -> (code_vectors (B, D) fp32, attention
    planes (B, C) fp32). The count == 0 fixups reproduce the dense
    path's finite-uniform behavior for all-padding rows exactly."""
    shards, per_shard = count2.shape
    cap = seg.shape[1]
    nonempty = count2 > 0                                    # (D, Bs)
    z_safe = jnp.where(nonempty, z, 1.0)
    code = _code_from_stats(z, acc, count2, x_pad)
    p = jnp.exp(scores - jnp.take_along_axis(m, seg, axis=1))
    w = jnp.where(slot_valid,
                  p / jnp.take_along_axis(z_safe, seg, axis=1), 0.0)
    shard_idx = jnp.broadcast_to(
        jnp.arange(shards, dtype=jnp.int32)[:, None], (shards, cap))
    attn = jnp.zeros((shards, per_shard, max_contexts), jnp.float32)
    # capacity-pad slots carry w == 0 and positions past their example's
    # count, so add-with-drop can only write zeros onto tail columns
    attn = attn.at[shard_idx, seg, pos].add(w, mode='drop')
    attn = jnp.where(nonempty[..., None], attn, 1.0 / max_contexts)
    batch = shards * per_shard
    return code.reshape(batch, -1), attn.reshape(batch, max_contexts)


# ------------------------------------------------- shared preparation
def _segment_inputs(ctx, count, token_pad: int, path_pad: int):
    """Packed wire arrays -> the segment structure + index planes every
    pass (forward AND recompute-backward) derives identically."""
    from code2vec_tpu.data.packed import segment_structure
    shards, cap, _ = ctx.shape
    per_shard = count.shape[0] // shards
    count2 = count.reshape(shards, per_shard).astype(jnp.int32)
    seg, pos, in_range = segment_structure(count2, cap)
    src, pth, tgt = ctx[..., 0], ctx[..., 1], ctx[..., 2]
    # the reader.context_valid_mask predicate, applied on the packed
    # stream: interior holes (all three parts PAD) drop out here exactly
    # as the dense path's log-mask drops them out of its softmax
    slot_valid = in_range & ((src != token_pad) | (tgt != token_pad)
                             | (pth != path_pad))            # (D, cap)
    return count2, seg, pos, slot_valid, src, pth, tgt


def _dropout_parts(dropout_rng, dropout_keep_rate: float,
                   dropout_prng_impl: str, shards: int, cap: int,
                   token_dim: int, path_dim: int):
    """The packed-layout keep mask, split per embedding part — THE one
    draw both the forward and the recompute backward make from the
    threaded key, so fused-vs-twin and fwd-vs-bwd masks bit-match by
    construction (models/functional.py::dropout_keep_mask routing)."""
    from code2vec_tpu.models.functional import dropout_keep_mask
    keep = dropout_keep_mask(dropout_rng, dropout_keep_rate,
                             (shards, cap, 2 * token_dim + path_dim),
                             dropout_prng_impl)
    return (keep[..., :token_dim],
            keep[..., token_dim:token_dim + path_dim],
            keep[..., token_dim + path_dim:])


def _apply_keep(e, keep, keep_rate: float):
    return jnp.where(keep, e / keep_rate, jnp.zeros_like(e))


def _split_weights(transform, attention, token_dim: int, path_dim: int,
                   dtype):
    t = transform.astype(dtype)
    return (t[:token_dim], t[token_dim:token_dim + path_dim],
            t[token_dim + path_dim:], attention.astype(dtype))


def _pad_forward(token_embedding, path_embedding, transform,
                 token_pad: int, path_pad: int, dtype, precision):
    """(pad_ctx (3d,), x_pad (Dc,)) — the dense path's value for every
    all-PAD slot, the analytic stand-in for count == 0 rows. No dropout
    (such rows carry weight 0, so dropout on them is loss-invisible)."""
    pad_ctx = jnp.concatenate([
        token_embedding[token_pad], path_embedding[path_pad],
        token_embedding[token_pad]]).astype(dtype)
    x_pad = jnp.tanh(jnp.matmul(pad_ctx[None, :], transform.astype(dtype),
                                precision=precision))[0]     # (Dc,)
    return pad_ctx, x_pad


# --------------------------------------------------------------- entry
def ragged_encode(token_embedding: jax.Array, path_embedding: jax.Array,
                  transform: jax.Array, attention: jax.Array,
                  ctx: jax.Array, count: jax.Array, *,
                  max_contexts: int, token_pad: int, path_pad: int,
                  dtype: jnp.dtype = jnp.float32,
                  dropout_rng: Optional[jax.Array] = None,
                  dropout_keep_rate: float = 1.0,
                  dropout_prng_impl: str = 'threefry2x32',
                  use_kernel: bool = False,
                  interpret: bool = False,
                  mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Packed wire arrays -> (code_vectors (B, D) fp32, attention planes
    (B, C) fp32), with no ``(B, C, .)`` intermediate anywhere.

    ``use_kernel`` False runs the jnp twin; True runs the Pallas kernel,
    compiled — off a TPU that raises ``KernelRequiresTPU`` unless a test
    passes ``interpret=True``. The caller decides (the trainer, from its
    mesh's platform); nothing here looks for a device to choose a path.
    Dropout (the fused TRAIN
    draw) rides either implementation: the packed-layout keep mask is
    applied to the gathered embeddings BEFORE the stats pass, so the
    kernel and the twin consume bit-identical inputs. NB the kernel
    itself is still not reverse-differentiable — training routes
    through :func:`ragged_encode_code`, whose custom VJP recomputes.
    ``mesh`` shard_maps the kernel over the data axis on multi-device
    meshes; the twin ignores it (its segment ops partition under GSPMD
    by the leading shards axis).
    """
    shards, cap, _ = ctx.shape
    batch = count.shape[0]
    per_shard = batch // shards
    # THE segment arithmetic, shared with unpack_device (data/packed.py)
    # so the parity-critical slot->example mapping has one definition
    count2, seg, pos, slot_valid, src, pth, tgt = _segment_inputs(
        ctx, count, token_pad, path_pad)

    apply_dropout = dropout_rng is not None and dropout_keep_rate < 1.0
    if use_kernel:
        interpret = resolve_interpret(interpret, 'ragged fused encode',
                                      mesh)

    src_e = jnp.take(token_embedding, src, axis=0).astype(dtype)  # (D, cap, d)
    pth_e = jnp.take(path_embedding, pth, axis=0).astype(dtype)
    tgt_e = jnp.take(token_embedding, tgt, axis=0).astype(dtype)
    token_dim = src_e.shape[-1]
    path_dim = pth_e.shape[-1]

    if apply_dropout:
        # THE shared PRNG routing (models/functional.py::
        # dropout_keep_mask via _dropout_parts — lazy import;
        # functional's import of this module is deferred, so there is
        # no cycle). The draw is over retained slots only: the packed
        # layout also SHRINKS the mask draw by the fill factor
        keep_src, keep_pth, keep_tgt = _dropout_parts(
            dropout_rng, dropout_keep_rate, dropout_prng_impl,
            shards, cap, token_dim, path_dim)
        src_e = _apply_keep(src_e, keep_src, dropout_keep_rate)
        pth_e = _apply_keep(pth_e, keep_pth, dropout_keep_rate)
        tgt_e = _apply_keep(tgt_e, keep_tgt, dropout_keep_rate)

    w_src, w_path, w_tgt, attn_vec = _split_weights(
        transform, attention, token_dim, path_dim, dtype)
    precision = _precision(dtype)
    _pad_ctx, x_pad = _pad_forward(token_embedding, path_embedding,
                                   transform, token_pad, path_pad, dtype,
                                   precision)

    if use_kernel:
        scores, m, z, acc = _stats_kernel_path(
            src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path, w_tgt,
            attn_vec, per_shard, mesh, interpret, precision)
    else:
        scores, m, z, acc = _stats_jnp(
            src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path, w_tgt,
            attn_vec, per_shard, precision)
    return _finish(scores, m, z, acc, seg, pos, slot_valid, count2,
                   x_pad, max_contexts)


# ------------------------------------------------- recompute backward
def _bwd_kernel(precision, src_ref, pth_ref, tgt_ref, seg_ref, valid_ref,
                wsrc_ref, wpath_ref, wtgt_ref, attn_row_ref,
                m_ref, z_ref, gc_ref, g_ref,
                de_src_ref, de_pth_ref, de_tgt_ref,
                dw_src_ref, dw_pth_ref, dw_tgt_ref, dattn_ref):
    """The second Pallas kernel: exact softmax-backward gradients off
    the SAME packed slot tiles the forward walked, with the per-slot
    state (x, scores, softmax weights) RECOMPUTED from the saved
    per-example ``(m, z)`` — recompute-over-store, so the forward never
    banks a ``(D, cap, .)`` residual. Per-slot cotangent streams
    (``de_*``) are emitted per tile; the dense TRANSFORM/ATTENTION
    gradients accumulate in the output blocks across grid steps (same
    index map every step keeps them VMEM-resident)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_src_ref[:] = jnp.zeros_like(dw_src_ref)
        dw_pth_ref[:] = jnp.zeros_like(dw_pth_ref)
        dw_tgt_ref[:] = jnp.zeros_like(dw_tgt_ref)
        dattn_ref[:] = jnp.zeros_like(dattn_ref)

    # recompute this tile's forward state
    x = jnp.dot(src_ref[:], wsrc_ref[:], precision=precision,
                preferred_element_type=jnp.float32)
    x += jnp.dot(pth_ref[:], wpath_ref[:], precision=precision,
                 preferred_element_type=jnp.float32)
    x += jnp.dot(tgt_ref[:], wtgt_ref[:], precision=precision,
                 preferred_element_type=jnp.float32)
    x = jnp.tanh(x)                                          # (T, Dc) f32
    attn_row = attn_row_ref[:]                               # (1, Dc)
    sc = jax.lax.dot_general(x, attn_row, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=jnp.float32)  # (T, 1)
    valid = valid_ref[:] > 0.0                               # (T, 1)
    n_seg = m_ref.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (sc.shape[0], n_seg), 1)
    onehot = ((seg_ref[:] == lanes) & valid).astype(jnp.float32)
    # per-slot views of the per-example stats/cotangents, via the same
    # indicator contraction the forward used (MXU/VPU, no gathers)
    m_slot = jnp.sum(onehot * m_ref[:], axis=1, keepdims=True)
    z_slot = jnp.sum(onehot * z_ref[:], axis=1, keepdims=True)
    gc_slot = jnp.sum(onehot * gc_ref[:], axis=1, keepdims=True)
    g_slot = jnp.dot(onehot, g_ref[:],
                     preferred_element_type=jnp.float32)     # (T, Dc)
    p = jnp.where(valid, jnp.exp(sc - m_slot), 0.0)
    w = p / jnp.where(z_slot > 0.0, z_slot, 1.0)             # (T, 1)
    # exact softmax backward (the stop-gradiented running max drops out:
    # softmax is shift-invariant)
    gdot = jnp.sum(x * g_slot, axis=1, keepdims=True)        # (T, 1)
    ds = w * (gdot - gc_slot)                                # (T, 1)
    dx = w * g_slot + ds * attn_row.astype(jnp.float32)
    du = (1.0 - x * x) * dx                                  # (T, Dc) f32
    de_src_ref[:] = jax.lax.dot_general(
        du, wsrc_ref[:], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    de_pth_ref[:] = jax.lax.dot_general(
        du, wpath_ref[:], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    de_tgt_ref[:] = jax.lax.dot_general(
        du, wtgt_ref[:], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    dw_src_ref[:] += jax.lax.dot_general(
        src_ref[:], du, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    dw_pth_ref[:] += jax.lax.dot_general(
        pth_ref[:], du, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    dw_tgt_ref[:] += jax.lax.dot_general(
        tgt_ref[:], du, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    dattn_ref[:] += jax.lax.dot_general(
        x, ds, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)                  # (Dc, 1)


def _grads_pallas(src_e, pth_e, tgt_e, seg, valid, w_src, w_path, w_tgt,
                  attn_vec, m, z, gc, g, n_seg: int, interpret: bool,
                  precision):
    """One shard's flat packed stream + saved ``(m, z)`` stats +
    per-example cotangents ``g`` (n_seg, Dc) / ``gc`` (n_seg,) ->
    (de_src/de_pth/de_tgt (cap, d) f32, dw_src/dw_pth/dw_tgt (d, Dc)
    f32, d_attn (Dc, 1) f32) via the recompute backward kernel."""
    cap, token_dim = src_e.shape
    path_dim = pth_e.shape[1]
    code_dim = w_src.shape[1]
    padded = -(-cap // SLOT_TILE) * SLOT_TILE
    pad = padded - cap
    if pad:
        src_e = jnp.pad(src_e, ((0, pad), (0, 0)))
        pth_e = jnp.pad(pth_e, ((0, pad), (0, 0)))
        tgt_e = jnp.pad(tgt_e, ((0, pad), (0, 0)))
        seg = jnp.pad(seg, (0, pad))
        valid = jnp.pad(valid, (0, pad))     # False: pad slots are inert
    seg2 = seg.reshape(padded, 1).astype(jnp.int32)
    valid2 = valid.reshape(padded, 1).astype(jnp.float32)
    attn_row = attn_vec.reshape(1, code_dim)
    grid = (padded // SLOT_TILE,)
    row_block = lambda dim: pl.BlockSpec((SLOT_TILE, dim),
                                         lambda i: (i, 0))
    full_block = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    kernel = functools.partial(_bwd_kernel, precision)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row_block(token_dim), row_block(path_dim), row_block(token_dim),
            row_block(1), row_block(1),
            full_block(w_src.shape), full_block(w_path.shape),
            full_block(w_tgt.shape), full_block((1, code_dim)),
            full_block((1, n_seg)), full_block((1, n_seg)),
            full_block((1, n_seg)), full_block((n_seg, code_dim)),
        ],
        out_specs=[
            row_block(token_dim), row_block(path_dim), row_block(token_dim),
            full_block((token_dim, code_dim)),
            full_block((path_dim, code_dim)),
            full_block((token_dim, code_dim)),
            full_block((code_dim, 1)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, token_dim), jnp.float32),
            jax.ShapeDtypeStruct((padded, path_dim), jnp.float32),
            jax.ShapeDtypeStruct((padded, token_dim), jnp.float32),
            jax.ShapeDtypeStruct((token_dim, code_dim), jnp.float32),
            jax.ShapeDtypeStruct((path_dim, code_dim), jnp.float32),
            jax.ShapeDtypeStruct((token_dim, code_dim), jnp.float32),
            jax.ShapeDtypeStruct((code_dim, 1), jnp.float32),
        ],
        interpret=interpret,
    )(src_e, pth_e, tgt_e, seg2, valid2, w_src, w_path, w_tgt, attn_row,
      m.reshape(1, n_seg).astype(jnp.float32),
      z.reshape(1, n_seg).astype(jnp.float32),
      gc.reshape(1, n_seg).astype(jnp.float32),
      g.astype(jnp.float32))
    de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt, d_attn = outs
    return (de_src[:cap], de_pth[:cap], de_tgt[:cap],
            dw_src, dw_pth, dw_tgt, d_attn)


def _grads_kernel_path(src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                       w_tgt, attn_vec, m, z, gc, g2, per_shard: int, mesh,
                       interpret: bool, precision):
    """Kernel backward over the shard-structured stream — the
    _stats_kernel_path discipline: shard_mapped over the data axis on
    multi-device meshes (pallas_call is opaque to GSPMD), one flat
    stream with offset segment ids on a single device. Returns
    (de_src/de_pth/de_tgt (D, cap, d) f32, dw parts (d, Dc) f32,
    d_attn (Dc, 1) f32), the dense parts summed over shards."""
    shards, cap = seg.shape

    def one_shard(src_l, pth_l, tgt_l, seg_l, valid_l, m_l, z_l, gc_l,
                  g_l, ws, wp, wt, av):
        outs = _grads_pallas(src_l[0], pth_l[0], tgt_l[0], seg_l[0],
                             valid_l[0], ws, wp, wt, av, m_l[0], z_l[0],
                             gc_l[0], g_l[0], per_shard, interpret,
                             precision)
        return tuple(o[None] for o in outs)

    if mesh is not None and mesh.size > 1:
        # check_vma=False: same reasoning as the forward kernel route
        outs = jax.shard_map(
            one_shard, mesh=mesh,
            in_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                      P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                      P(DATA_AXIS, None), P(DATA_AXIS, None),
                      P(DATA_AXIS, None), P(DATA_AXIS, None),
                      P(DATA_AXIS, None, None), P(None, None),
                      P(None, None), P(None, None), P(None, None)),
            out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                       P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                       P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                       P(DATA_AXIS, None, None)),
            check_vma=False)(src_e, pth_e, tgt_e, seg, slot_valid,
                             m, z, gc, g2, w_src, w_path, w_tgt, attn_vec)
        de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt, d_attn = outs
        return (de_src, de_pth, de_tgt, dw_src.sum(axis=0),
                dw_pth.sum(axis=0), dw_tgt.sum(axis=0),
                d_attn.sum(axis=0))
    flat = shards * cap
    n_seg = shards * per_shard
    offsets = (jnp.arange(shards, dtype=jnp.int32) * per_shard)[:, None]
    seg_flat = (seg + offsets).reshape(flat)
    outs = _grads_pallas(
        src_e.reshape(flat, -1), pth_e.reshape(flat, -1),
        tgt_e.reshape(flat, -1), seg_flat, slot_valid.reshape(flat),
        w_src, w_path, w_tgt, attn_vec, m.reshape(n_seg),
        z.reshape(n_seg), gc.reshape(n_seg), g2.reshape(n_seg, -1),
        n_seg, interpret, precision)
    de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt, d_attn = outs
    return (de_src.reshape(shards, cap, -1),
            de_pth.reshape(shards, cap, -1),
            de_tgt.reshape(shards, cap, -1),
            dw_src, dw_pth, dw_tgt, d_attn)


def _grads_jnp(src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path, w_tgt,
               attn_vec, m, z, gc, g2, precision):
    """jnp twin of the backward kernel — the CPU/fallback recompute
    backward (the residual win applies there too: under the custom VJP
    these per-slot tensors are transients of THIS function, not saved
    forward state). ``g2`` (D, Bs, Dc) f32 per-example cotangents,
    ``gc`` (D, Bs) f32 = sum(g2 * code2). Returns the same tuple as
    _grads_kernel_path."""
    x = jnp.tanh(jnp.matmul(src_e, w_src, precision=precision)
                 + jnp.matmul(pth_e, w_path, precision=precision)
                 + jnp.matmul(tgt_e, w_tgt, precision=precision))
    scores = jnp.matmul(x, attn_vec,
                        precision=precision)[..., 0].astype(jnp.float32)
    m_slot = jnp.take_along_axis(m, seg, axis=1)
    z_slot = jnp.take_along_axis(z, seg, axis=1)
    p = jnp.where(slot_valid, jnp.exp(scores - m_slot), 0.0)
    w = p / jnp.where(z_slot > 0.0, z_slot, 1.0)             # (D, cap)
    g_slot = jnp.take_along_axis(g2, seg[..., None], axis=1)  # (D,cap,Dc)
    gc_slot = jnp.take_along_axis(gc, seg, axis=1)            # (D, cap)
    xf = x.astype(jnp.float32)
    gdot = jnp.sum(xf * g_slot, axis=-1)                      # (D, cap)
    ds = w * (gdot - gc_slot)                                 # (D, cap)
    dx = (w[..., None] * g_slot
          + ds[..., None] * attn_vec[:, 0].astype(jnp.float32))
    du = (1.0 - xf * xf) * dx                                 # (D,cap,Dc)
    d_attn = jnp.einsum('sc,scd->d', ds, xf,
                        precision=precision)[:, None]         # (Dc, 1)
    f32 = jnp.float32
    dw_src = jnp.einsum('sci,scj->ij', src_e.astype(f32), du,
                        precision=precision)
    dw_pth = jnp.einsum('sci,scj->ij', pth_e.astype(f32), du,
                        precision=precision)
    dw_tgt = jnp.einsum('sci,scj->ij', tgt_e.astype(f32), du,
                        precision=precision)
    de_src = jnp.matmul(du, w_src.astype(f32).T, precision=precision)
    de_pth = jnp.matmul(du, w_path.astype(f32).T, precision=precision)
    de_tgt = jnp.matmul(du, w_tgt.astype(f32).T, precision=precision)
    return de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt, d_attn


def _rows_table_grad(table_rows: int, rows, inv, cot, mesh):
    """One embedding table's gradient, reduced over the rows the step
    touched and not over the table (data/packed.py has the wire):
    ``rows`` (D, U / D) int32 the step's distinct rows, ascending, padded
    past the table's end, in D equal runs; ``inv`` (D, S) each slot's
    position in that set; ``cot`` (D, S, d) the slots' cotangents.

    Each shard sums its slots into a (U, d) buffer in the step's row
    space: the same row-updates as the dense scatter-add, into a
    destination that stays on its chip. The D buffers are summed across
    ``data`` (the one collective: an all-reduce of U rows, where the
    dense form all-reduces the table), and ONE scatter writes the sums
    into zeros. The set's rows are unique and sorted, which the scatter
    is told, and its destination is known to be zeros: the form whose
    zero-fill the TPU compiler fuses into the scatter and runs at the
    gathers' rate (a scatter into a live table reads, adds and writes
    every row at four times that, PERF.md). The padding is out of bounds
    and dropped. Same sums as the dense form, reassociated, in ``cot``'s
    dtype throughout.

    One shard (D = 1) is the same code with nothing to sum and no
    collective: the ``vmap`` and the sum over one shard lower to the
    plain scatters."""
    def held(x, spec):
        """``x`` under a sharding constraint, where there is a mesh."""
        return x if mesh is None else jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    dim = cot.shape[-1]
    rows = held(rows, P()).reshape(-1)      # the set made whole: U ids
    capacity = rows.shape[0]
    compact = held(jax.vmap(
        lambda i, c: jnp.zeros((capacity, dim), cot.dtype).at[i].add(c))(
            inv, cot), P(DATA_AXIS))                        # (D, U, d)
    sums = held(compact.sum(axis=0), P())                   # (U, d)
    return jnp.zeros((table_rows, dim), cot.dtype).at[rows].add(
        sums, unique_indices=True, indices_are_sorted=True, mode='drop')


# ------------------------------------------------- custom-VJP train path
def ragged_encode_code(token_embedding: jax.Array,
                       path_embedding: jax.Array, transform: jax.Array,
                       attention: jax.Array, ctx: jax.Array,
                       count: jax.Array, *, token_pad: int, path_pad: int,
                       dtype: jnp.dtype = jnp.float32,
                       dropout_rng: Optional[jax.Array] = None,
                       dropout_keep_rate: float = 1.0,
                       dropout_prng_impl: str = 'threefry2x32',
                       use_kernel: bool = False,
                       interpret: bool = False,
                       mesh=None, custom_vjp: bool = True,
                       rows: tuple = ()) -> jax.Array:
    """The TRAIN-path encode: packed wire arrays -> code vectors
    ``(B, D) fp32`` under a ``jax.custom_vjp`` whose backward RECOMPUTES
    the per-slot state instead of storing it (module docstring). Only
    the four encoder params are differentiable; ``ctx``/``count``/the
    PRNG key/``rows`` get ``None`` cotangents.

    ``rows`` = ``(tok_rows, path_rows, inv)`` where the batch names the
    rows it touches (a training stream, data/packed.py): the backward
    then builds the two table gradients over those rows
    (``_rows_table_grad``); the forward never reads them.

    ``use_kernel`` routes BOTH passes: False runs the jnp twin pair,
    True the Pallas pair (``Config.RAGGED_TRAIN_KERNEL``; off a TPU that
    raises ``KernelRequiresTPU`` unless a test passes
    ``interpret=True``). ``custom_vjp=False`` is the autodiff reference
    — the twin differentiated by jax, storing its residuals — kept for
    the parity/residual tests."""
    apply_dropout = dropout_rng is not None and dropout_keep_rate < 1.0
    if use_kernel and custom_vjp:
        interpret = resolve_interpret(interpret, 'ragged train kernel pair',
                                      mesh)
    if not custom_vjp:
        if use_kernel:
            raise ValueError(
                'custom_vjp=False differentiates the jnp twin via '
                'autodiff; the Pallas kernels have no autodiff rule '
                '(pass use_kernel=False)')
        # max_contexts only shapes the attention output, discarded here
        return ragged_encode(
            token_embedding, path_embedding, transform, attention, ctx,
            count, max_contexts=1, token_pad=token_pad, path_pad=path_pad,
            dtype=dtype, dropout_rng=dropout_rng,
            dropout_keep_rate=dropout_keep_rate,
            dropout_prng_impl=dropout_prng_impl, use_kernel=False,
            mesh=mesh)[0]

    precision = _precision(dtype)

    @scoped('c2v_encode')
    def _fwd_compute(tok_t, path_t, trans, attn, ctx_, count_, rng_):
        count2, seg, _pos, slot_valid, src, pth, tgt = _segment_inputs(
            ctx_, count_, token_pad, path_pad)
        shards, cap = seg.shape
        per_shard = count2.shape[1]
        token_dim = tok_t.shape[1]
        path_dim = path_t.shape[1]
        src_e = jnp.take(tok_t, src, axis=0).astype(dtype)
        pth_e = jnp.take(path_t, pth, axis=0).astype(dtype)
        tgt_e = jnp.take(tok_t, tgt, axis=0).astype(dtype)
        if apply_dropout:
            keep_src, keep_pth, keep_tgt = _dropout_parts(
                rng_, dropout_keep_rate, dropout_prng_impl, shards, cap,
                token_dim, path_dim)
            src_e = _apply_keep(src_e, keep_src, dropout_keep_rate)
            pth_e = _apply_keep(pth_e, keep_pth, dropout_keep_rate)
            tgt_e = _apply_keep(tgt_e, keep_tgt, dropout_keep_rate)
        w_src, w_path, w_tgt, attn_vec = _split_weights(
            trans, attn, token_dim, path_dim, dtype)
        _pad_ctx, x_pad = _pad_forward(tok_t, path_t, trans, token_pad,
                                       path_pad, dtype, precision)
        if use_kernel:
            _scores, m, z, acc = _stats_kernel_path(
                src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                w_tgt, attn_vec, per_shard, mesh, interpret, precision)
        else:
            _scores, m, z, acc = _stats_jnp(
                src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                w_tgt, attn_vec, per_shard, precision)
        code = _code_from_stats(z, acc, count2, x_pad)
        return code.reshape(count_.shape[0], -1), m, z

    @scoped('c2v_encode')   # autodiff names no custom VJP's backward
    def _bwd_compute(tok_t, path_t, trans, attn, ctx_, count_, rng_,
                     rows_, m, z, code, g):
        count2, seg, _pos, slot_valid, src, pth, tgt = _segment_inputs(
            ctx_, count_, token_pad, path_pad)
        shards, cap = seg.shape
        per_shard = count2.shape[1]
        token_dim = tok_t.shape[1]
        path_dim = path_t.shape[1]
        # recompute: re-gather the embeddings and re-draw the SAME keep
        # mask from the threaded key — nothing per-slot was saved
        src_e = jnp.take(tok_t, src, axis=0).astype(dtype)
        pth_e = jnp.take(path_t, pth, axis=0).astype(dtype)
        tgt_e = jnp.take(tok_t, tgt, axis=0).astype(dtype)
        keep_parts = None
        if apply_dropout:
            keep_parts = _dropout_parts(
                rng_, dropout_keep_rate, dropout_prng_impl, shards, cap,
                token_dim, path_dim)
            src_e = _apply_keep(src_e, keep_parts[0], dropout_keep_rate)
            pth_e = _apply_keep(pth_e, keep_parts[1], dropout_keep_rate)
            tgt_e = _apply_keep(tgt_e, keep_parts[2], dropout_keep_rate)
        w_src, w_path, w_tgt, attn_vec = _split_weights(
            trans, attn, token_dim, path_dim, dtype)
        g32 = g.astype(jnp.float32)
        g2 = g32.reshape(shards, per_shard, -1)
        code2 = code.reshape(shards, per_shard, -1)
        gc = jnp.sum(g2 * code2, axis=-1)                    # (D, Bs)
        if use_kernel:
            (de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt,
             d_attn) = _grads_kernel_path(
                src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                w_tgt, attn_vec, m, z, gc, g2, per_shard, mesh,
                interpret, precision)
        else:
            (de_src, de_pth, de_tgt, dw_src, dw_pth, dw_tgt,
             d_attn) = _grads_jnp(
                src_e, pth_e, tgt_e, seg, slot_valid, w_src, w_path,
                w_tgt, attn_vec, m, z, gc, g2, precision)
        if apply_dropout:
            # inverted-dropout backward: same mask, same 1/keep scale
            de_src = _apply_keep(de_src, keep_parts[0], dropout_keep_rate)
            de_pth = _apply_keep(de_pth, keep_parts[1], dropout_keep_rate)
            de_tgt = _apply_keep(de_tgt, keep_parts[2], dropout_keep_rate)
        # count == 0 rows took code = x_pad = tanh(pad_ctx @ W): route
        # their cotangent through that expression. Zero in training
        # (weight-0 rows get zero loss cotangent) but exact for any
        # caller, matching the autodiff twin.
        nonempty = count2 > 0
        g_empty = jnp.where(nonempty[..., None], 0.0,
                            g2).sum(axis=(0, 1))             # (Dc,)
        pad_ctx, x_pad = _pad_forward(tok_t, path_t, trans, token_pad,
                                      path_pad, dtype, precision)
        x_pad32 = x_pad.astype(jnp.float32)
        du_pad = (1.0 - x_pad32 * x_pad32) * g_empty         # (Dc,)
        dw_pad = (pad_ctx.astype(jnp.float32)[:, None]
                  * du_pad[None, :])                         # (3d, Dc)
        de_pad = jnp.matmul(trans.astype(jnp.float32), du_pad,
                            precision=precision)             # (3d,)
        d_trans = (jnp.concatenate([dw_src, dw_pth, dw_tgt], axis=0)
                   + dw_pad).astype(trans.dtype)
        # table grads as scatter-adds over the packed index stream
        # (duplicate indices accumulate), or, where the batch names its
        # rows, reduced over those and not over the tables. Token table,
        # its PAD term, then the path table: the order the dense form's
        # operands have always been built in (its lowered text is pinned)
        with jax.named_scope('c2v_table_grad'):
            if rows_:
                tok_rows, path_rows, inv = rows_
                d_tok = _rows_table_grad(
                    tok_t.shape[0], tok_rows,
                    jnp.concatenate([inv[..., 0], inv[..., 2]], axis=1),
                    jnp.concatenate([de_src, de_tgt],
                                    axis=1).astype(tok_t.dtype), mesh)
            else:
                tok_idx = jnp.concatenate([src.reshape(-1), tgt.reshape(-1)])
                tok_cot = jnp.concatenate([de_src.reshape(-1, token_dim),
                                           de_tgt.reshape(-1, token_dim)])
                d_tok = jnp.zeros((tok_t.shape[0], token_dim),
                                  tok_t.dtype).at[tok_idx].add(
                                      tok_cot.astype(tok_t.dtype))
            d_tok = d_tok.at[token_pad].add(
                (de_pad[:token_dim]
                 + de_pad[token_dim + path_dim:]).astype(tok_t.dtype))
            if rows_:
                d_path = _rows_table_grad(
                    path_t.shape[0], path_rows, inv[..., 1],
                    de_pth.astype(path_t.dtype), mesh)
            else:
                pth_cot = de_pth.reshape(-1, path_dim)
                pth_idx = pth.reshape(-1)
                d_path = jnp.zeros((path_t.shape[0], path_dim),
                                   path_t.dtype).at[pth_idx].add(
                                       pth_cot.astype(path_t.dtype))
            d_path = d_path.at[path_pad].add(
                de_pad[token_dim:token_dim + path_dim].astype(path_t.dtype))
        return d_tok, d_path, d_trans, d_attn.astype(attn.dtype)

    @jax.custom_vjp
    def encode_code(tok_t, path_t, trans, attn, ctx_, count_, rng_, rows_):
        return _fwd_compute(tok_t, path_t, trans, attn, ctx_, count_,
                            rng_)[0]

    def fwd(tok_t, path_t, trans, attn, ctx_, count_, rng_, rows_):
        code, m, z = _fwd_compute(tok_t, path_t, trans, attn, ctx_,
                                  count_, rng_)
        # residuals: the inputs (live anyway) + per-example (m, z) +
        # the (B, D) code — NO per-slot tensor
        return code, (tok_t, path_t, trans, attn, ctx_, count_, rng_,
                      rows_, m, z, code)

    def bwd(res, g):
        (tok_t, path_t, trans, attn, ctx_, count_, rng_, rows_, m, z,
         code) = res
        grads = _bwd_compute(tok_t, path_t, trans, attn, ctx_, count_,
                             rng_, rows_, m, z, code, g)
        return grads + (None, None, None, None)

    encode_code.defvjp(fwd, bwd)
    rng_arg = (dropout_rng if apply_dropout
               else jnp.zeros((0,), jnp.uint32))
    return encode_code(token_embedding, path_embedding, transform,
                       attention, ctx, count, rng_arg, tuple(rows))
