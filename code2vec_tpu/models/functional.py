"""The code2vec model as pure functions over an explicit parameter pytree.

This is the single source of truth for the model math; both backends (the
raw-pytree 'jax' backend and the flax.linen module) call into it. The
reference implemented this math three times — train graph, test graph and
predict graph (tensorflow_model.py:197-234, 267-309) plus a second full copy
in Keras (keras_model.py:37-95); here it is one pure ``encode`` traced by XLA
once per entry point.

Forward pass (mirrors ``_calculate_weighted_contexts``,
tensorflow_model.py:236-265):

    ctx   = concat(tok[source], path[path], tok[target])      (B, C, 3d)
    ctx   = dropout(ctx)                                      train only
    x     = tanh(ctx @ TRANSFORM)                             (B, C, D)
    score = x @ ATTENTION + log(mask)                         (B, C)
    attn  = softmax(score, axis=contexts)
    code  = sum(attn * x, axis=contexts)                      (B, D)
    logit = code @ TARGET_EMB.T                               (B, Vy)

TPU-first details with no reference counterpart:

- optional bfloat16 compute: the gathered embeddings and both matmuls run in
  bf16 for the MXU; attention softmax and the final cross-entropy stay fp32;
- rows with zero valid contexts (static-shape padding) produce a *finite*
  uniform attention instead of NaN, and are excluded from the loss via the
  per-example ``weight`` (the reference filtered such rows dynamically,
  path_context_reader.py:153-177 — dynamic shapes don't fly under XLA).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.scopes import scoped

# Floor for the additive log-mask so fully-masked rows stay finite (vs the
# reference's log(0) = -inf which NaNs an all-invalid row,
# tensorflow_model.py:257). Must be a NORMAL fp32 (XLA flushes denormals to
# zero, turning log back into -inf); log(1e-30) ~ -69, giving invalid
# contexts attention ~e-30 — zero at fp32 resolution.
_MASK_MIN = 1e-30


class Code2VecParams(NamedTuple):
    """The five trainable arrays (reference tensorflow_model.py:206-220,
    249-250). ``attention`` keeps the reference's (D, 1) shape."""
    token_embedding: jax.Array    # (Vt, d_tok)  WORDS_VOCAB
    path_embedding: jax.Array     # (Vp, d_path) PATHS_VOCAB
    target_embedding: jax.Array   # (Vy, D)      TARGET_WORDS_VOCAB
    transform: jax.Array          # (2*d_tok+d_path, D) TRANSFORM
    attention: jax.Array          # (D, 1)       ATTENTION


def param_shapes(*, token_vocab_size: int, path_vocab_size: int,
                 target_vocab_size: int, token_dim: int, path_dim: int,
                 code_dim: int) -> Code2VecParams:
    """Shapes-only pytree (for sharding specs / checkpoint restore)."""
    context_dim = 2 * token_dim + path_dim
    return Code2VecParams(
        token_embedding=jax.ShapeDtypeStruct((token_vocab_size, token_dim),
                                             jnp.float32),
        path_embedding=jax.ShapeDtypeStruct((path_vocab_size, path_dim),
                                            jnp.float32),
        target_embedding=jax.ShapeDtypeStruct((target_vocab_size, code_dim),
                                              jnp.float32),
        transform=jax.ShapeDtypeStruct((context_dim, code_dim), jnp.float32),
        attention=jax.ShapeDtypeStruct((code_dim, 1), jnp.float32),
    )


def init_params(rng: jax.Array, *, token_vocab_size: int,
                path_vocab_size: int, target_vocab_size: int,
                token_dim: int, path_dim: int, code_dim: int
                ) -> Code2VecParams:
    """Reference initialization: embeddings use
    variance_scaling(1.0, fan_out, uniform) (tensorflow_model.py:209-220);
    TRANSFORM and ATTENTION use TF1's default glorot_uniform (:214-216,
    249-250)."""
    k_tok, k_path, k_tgt, k_trans, k_attn = jax.random.split(rng, 5)
    context_dim = 2 * token_dim + path_dim
    fan_out_uniform = jax.nn.initializers.variance_scaling(
        1.0, 'fan_out', 'uniform')
    glorot = jax.nn.initializers.glorot_uniform()
    return Code2VecParams(
        token_embedding=fan_out_uniform(
            k_tok, (token_vocab_size, token_dim), jnp.float32),
        path_embedding=fan_out_uniform(
            k_path, (path_vocab_size, path_dim), jnp.float32),
        target_embedding=fan_out_uniform(
            k_tgt, (target_vocab_size, code_dim), jnp.float32),
        transform=glorot(k_trans, (context_dim, code_dim), jnp.float32),
        attention=glorot(k_attn, (code_dim, 1), jnp.float32),
    )


def dropout_keep_mask(dropout_rng: jax.Array, keep_rate: float, shape,
                      prng_impl: str) -> jax.Array:
    """Bernoulli keep mask for inverted dropout — THE single definition
    of the PRNG routing shared by the dense encode below and the ragged
    packed encoder (ops/pallas_ragged.py). ``prng_impl='rbg'`` rewraps
    onto the hardware RngBitGenerator: the incoming (checkpoint-portable)
    threefry key seeds 4 words of rbg state, so the big mask draw costs
    hardware RNG throughput instead of per-element threefry rounds."""
    if prng_impl == 'rbg':
        dropout_rng = jax.random.wrap_key_data(
            jax.random.bits(dropout_rng, (4,), jnp.uint32), impl='rbg')
    return jax.random.bernoulli(dropout_rng, keep_rate, shape)


@scoped('c2v_encode')
def encode(params: Code2VecParams, source: jax.Array, path: jax.Array,
           target: jax.Array, mask: jax.Array, *,
           dropout_rng: Optional[jax.Array] = None,
           dropout_keep_rate: float = 1.0,
           dropout_prng_impl: str = 'threefry2x32',
           dtype: jnp.dtype = jnp.float32,
           use_pallas: bool = False
           ) -> Tuple[jax.Array, jax.Array]:
    """Bag-of-contexts → (code_vectors (B, D) fp32, attention (B, C) fp32).

    ``dtype`` is the MXU compute dtype; attention softmax runs fp32.
    Dropout is applied iff ``dropout_rng`` is given and keep < 1
    (reference applies it only in the train graph,
    tensorflow_model.py:245-246). ``use_pallas`` routes the deterministic
    forward through the experimental fused kernel
    (ops/pallas_encode.py; a TPU kernel — off a TPU it raises
    ``KernelRequiresTPU``); the dropout path always uses plain jnp.
    """
    source_embed = jnp.take(params.token_embedding, source,
                            axis=0).astype(dtype)                 # (B, C, d)
    path_embed = jnp.take(params.path_embedding, path,
                          axis=0).astype(dtype)                   # (B, C, d)
    target_embed = jnp.take(params.token_embedding, target,
                            axis=0).astype(dtype)                 # (B, C, d)

    apply_dropout = dropout_rng is not None and dropout_keep_rate < 1.0
    if use_pallas and not apply_dropout:
        from code2vec_tpu.ops.pallas_encode import fused_context_transform
        batch, contexts = source.shape
        # inputs stay in the compute dtype (bf16 ships half the bytes into
        # VMEM); the kernel accumulates fp32 via preferred_element_type
        x_flat, scores_flat = fused_context_transform(
            source_embed.reshape(batch * contexts, -1),
            path_embed.reshape(batch * contexts, -1),
            target_embed.reshape(batch * contexts, -1),
            params.transform.astype(dtype), params.attention.astype(dtype))
        x = x_flat.reshape(batch, contexts, -1)
        scores = scores_flat.reshape(batch, contexts)
    else:
        context_embed = jnp.concatenate(
            [source_embed, path_embed, target_embed], axis=-1)  # (B, C, 3d)
        if apply_dropout:
            keep_mask = dropout_keep_mask(dropout_rng, dropout_keep_rate,
                                          context_embed.shape,
                                          dropout_prng_impl)
            context_embed = jnp.where(
                keep_mask, context_embed / dropout_keep_rate,
                jnp.zeros_like(context_embed))
        # fp32 compute asks for true-fp32 MXU passes (TPU fp32 matmuls
        # default to lower precision); bf16 uses the native fast path.
        precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        x = jnp.tanh(jnp.matmul(context_embed,
                                params.transform.astype(dtype),
                                precision=precision))             # (B, C, D)
        scores = jnp.matmul(x, params.attention.astype(dtype),
                            precision=precision)[..., 0]          # (B, C)
    scores = scores.astype(jnp.float32) + jnp.log(
        jnp.maximum(mask.astype(jnp.float32), _MASK_MIN))
    attention_weights = jax.nn.softmax(scores, axis=1)            # (B, C)

    if x.dtype == jnp.float32:
        code_vectors = jnp.einsum(
            'bc,bcd->bd', attention_weights, x,
            precision=jax.lax.Precision.HIGHEST)                  # (B, D)
    else:
        # bf16 compute mode: keep the weighted sum on the MXU fast path
        # with fp32 accumulation instead of round-tripping a full
        # (B, C, D) fp32 copy of the activations through HBM (~315 MB at
        # the java14m configuration). Softmax itself stays fp32 above.
        code_vectors = jnp.einsum(
            'bc,bcd->bd', attention_weights.astype(x.dtype), x,
            preferred_element_type=jnp.float32)                   # (B, D)
    return code_vectors, attention_weights


@scoped('c2v_encode')
def encode_packed(params: Code2VecParams, ctx: jax.Array, count: jax.Array,
                  *, max_contexts: int, token_pad: int, path_pad: int,
                  dropout_rng: Optional[jax.Array] = None,
                  dropout_keep_rate: float = 1.0,
                  dropout_prng_impl: str = 'threefry2x32',
                  dtype: jnp.dtype = jnp.float32,
                  use_kernel: bool = False,
                  interpret: bool = False,
                  mesh=None) -> Tuple[jax.Array, jax.Array]:
    """``encode`` straight off the packed wire (data/packed.py): consumes
    the ``(data_shards, capacity, 3)`` triples + per-example counts and
    produces the same ``(code_vectors (B, D) fp32, attention (B, C)
    fp32)`` outputs to fp32 rounding — without ever materializing the
    ``(B, C)`` index planes or the ``(B, C, 3d)`` context embeddings the
    unpack-then-dense path pays for (ops/pallas_ragged.py; gated by
    ``Config.USE_PALLAS_RAGGED_FUSION``). ``use_kernel`` selects the
    fused Pallas kernel (dropout, when given, is drawn over the packed
    layout outside the kernel and applied to its inputs) over the
    differentiable jnp twin; the trainer sets it once from its mesh's
    platform, and a kernel asked for off a TPU raises
    ``KernelRequiresTPU``. Training differentiates through
    ``loss_and_aux_packed``'s custom-VJP route, not this one."""
    from code2vec_tpu.ops import pallas_ragged
    return pallas_ragged.ragged_encode(
        params.token_embedding, params.path_embedding, params.transform,
        params.attention, ctx, count, max_contexts=max_contexts,
        token_pad=token_pad, path_pad=path_pad, dtype=dtype,
        dropout_rng=dropout_rng, dropout_keep_rate=dropout_keep_rate,
        dropout_prng_impl=dropout_prng_impl,
        use_kernel=use_kernel, interpret=interpret, mesh=mesh)


@scoped('c2v_logits')
def compute_logits(params: Code2VecParams, code_vectors: jax.Array,
                   dtype: jnp.dtype = jnp.float32,
                   num_valid_targets: Optional[int] = None) -> jax.Array:
    """code vectors → target-vocab logits, fp32 out
    (reference tensorflow_model.py:226, 297).

    ``num_valid_targets``: true target-vocab size when the table is padded
    for even sharding — padded columns are masked to a large negative so
    they drop out of both the softmax partition function and top-k."""
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    logits = jnp.matmul(code_vectors.astype(dtype),
                        params.target_embedding.astype(dtype).T,
                        precision=precision)
    logits = logits.astype(jnp.float32)
    padded = params.target_embedding.shape[0]
    if num_valid_targets is not None and num_valid_targets < padded:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(col < num_valid_targets, logits, -1e9)
    return logits


def weighted_ce_sums(logits: jax.Array, label: jax.Array,
                     weight: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(weighted CE sum, weight sum) — the single definition of the
    cross-entropy used by both the training loss and the streaming eval
    loss (which aggregates the sums exactly across batches and hosts).

    Written as ``logsumexp(logits) - logits[label]`` rather than indexing
    into ``log_softmax(logits)``: mathematically identical, but it reduces
    to per-example scalars without materializing a second (B, target_vocab)
    fp32 array — at java14m scale that intermediate is ~1 GB of HBM
    round-trip per step."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)          # (B,)
    picked = jnp.take_along_axis(logits, label[:, None], axis=1)[:, 0]
    ce = lse - picked
    return (ce * weight).sum(), weight.sum()


def loss_and_aux(params: Code2VecParams, source: jax.Array, path: jax.Array,
                 target: jax.Array, mask: jax.Array, label: jax.Array,
                 weight: jax.Array, *,
                 dropout_rng: Optional[jax.Array] = None,
                 dropout_keep_rate: float = 1.0,
                 dropout_prng_impl: str = 'threefry2x32',
                 dtype: jnp.dtype = jnp.float32,
                 num_valid_targets: Optional[int] = None,
                 use_fused_ce: bool = False,
                 fused_ce_mesh=None,
                 remat_encode: bool = False):
    """Weighted mean sparse softmax CE (reference tensorflow_model.py:226-230
    divides the CE sum by the dynamic batch size; with static shapes the
    per-example weight plays that role: padded rows have weight 0).

    ``use_fused_ce`` routes the CE through the flash-style Pallas kernel
    (ops/pallas_ce.py): no (B, V) logits in HBM, forward or backward. On a
    multi-device mesh the kernel must be shard_mapped (GSPMD would
    replicate the opaque pallas_call), so callers pass ``fused_ce_mesh``;
    a 1-device mesh or None uses the plain kernel.

    ``remat_encode`` wraps the encode block in ``jax.checkpoint``: the
    (B, C, 3d)-sized activations (gathered context embeddings, dropout
    output, tanh input) are recomputed in the backward instead of living
    in HBM across the whole loss — the classic FLOPs-for-memory trade for
    long-context (large MAX_CONTEXTS) configurations. Numerics unchanged
    (same fp ops, same dropout PRNG draws in the replay).
    """
    def _encode(params_, source_, path_, target_, mask_, rng_):
        return encode(
            params_, source_, path_, target_, mask_, dropout_rng=rng_,
            dropout_keep_rate=dropout_keep_rate,
            dropout_prng_impl=dropout_prng_impl, dtype=dtype)[0]

    if remat_encode:
        _encode = jax.checkpoint(_encode)
    code_vectors = _encode(params, source, path, target, mask, dropout_rng)
    return _loss_from_code(params, code_vectors, label, weight, dtype,
                           num_valid_targets, use_fused_ce, fused_ce_mesh)


@scoped('c2v_ce')
def _loss_from_code(params, code_vectors, label, weight, dtype,
                    num_valid_targets, use_fused_ce, fused_ce_mesh):
    """The loss tail shared by the plane and packed wires: code vectors
    -> weighted-mean CE, via materialized logits or the fused CE kernel
    (the wires differ only in how ``code_vectors`` was encoded)."""
    if use_fused_ce:
        from code2vec_tpu.ops import pallas_ce
        num_valid = (num_valid_targets if num_valid_targets is not None
                     else params.target_embedding.shape[0])
        if fused_ce_mesh is not None and fused_ce_mesh.size > 1:
            ce_sum, weight_sum = pallas_ce.sharded_fused_weighted_ce_sums(
                params.target_embedding, code_vectors, label, weight,
                num_valid, fused_ce_mesh, dtype=dtype)
        else:
            ce_sum, weight_sum = pallas_ce.fused_weighted_ce_sums(
                params.target_embedding, code_vectors, label, weight,
                num_valid, dtype=dtype)
    else:
        logits = compute_logits(params, code_vectors, dtype=dtype,
                                num_valid_targets=num_valid_targets)
        ce_sum, weight_sum = weighted_ce_sums(logits, label, weight)
    loss = ce_sum / jnp.maximum(weight_sum, 1.0)
    return loss, {'code_vectors': code_vectors,
                  'num_valid': weight_sum}


def loss_and_aux_packed(params: Code2VecParams, ctx: jax.Array,
                        count: jax.Array, label: jax.Array,
                        weight: jax.Array, *,
                        max_contexts: int, token_pad: int, path_pad: int,
                        dropout_rng: Optional[jax.Array] = None,
                        dropout_keep_rate: float = 1.0,
                        dropout_prng_impl: str = 'threefry2x32',
                        dtype: jnp.dtype = jnp.float32,
                        num_valid_targets: Optional[int] = None,
                        use_fused_ce: bool = False,
                        fused_ce_mesh=None,
                        remat_encode: bool = False,
                        use_ragged_kernel: bool = False,
                        ragged_mesh=None,
                        ragged_custom_vjp: bool = True,
                        rows: tuple = ()):
    """``loss_and_aux`` straight off the packed wire: the ragged fused
    encoder replaces unpack + dense encode (USE_PALLAS_RAGGED_FUSION;
    ops/pallas_ragged.py), the CE tail is shared with the plane path.

    The encode runs under :func:`pallas_ragged.ragged_encode_code`'s
    custom VJP: the backward recomputes the per-slot state off the
    packed segments instead of storing the (D, cap, 3d) gathered
    embeddings / (D, cap, D) activations as residuals, and emits the
    token/path table gradients as packed-stream scatter-adds.
    ``use_ragged_kernel`` routes both passes through the Pallas pair
    (Config.RAGGED_TRAIN_KERNEL; TPU only); False = the jnp twin pair,
    the default on every platform.
    ``max_contexts`` only shapes the attention planes the loss never
    reads; it stays in the signature so the packed twins share one call
    shape. ``ragged_custom_vjp=False`` keeps the autodiff twin — the
    residual-storing reference the tests compare against. ``rows`` are
    the batch's touched-row arrays where it carries them
    (data/packed.py): the backward reduces the table gradients over
    them."""
    del max_contexts  # loss consumes code vectors only
    from code2vec_tpu.ops import pallas_ragged

    def _encode(params_, ctx_, count_, rng_):
        return pallas_ragged.ragged_encode_code(
            params_.token_embedding, params_.path_embedding,
            params_.transform, params_.attention, ctx_, count_,
            token_pad=token_pad, path_pad=path_pad, dropout_rng=rng_,
            dropout_keep_rate=dropout_keep_rate,
            dropout_prng_impl=dropout_prng_impl, dtype=dtype,
            use_kernel=use_ragged_kernel, mesh=ragged_mesh,
            custom_vjp=ragged_custom_vjp, rows=rows)

    if remat_encode:
        _encode = jax.checkpoint(_encode)
    code_vectors = _encode(params, ctx, count, dropout_rng)
    return _loss_from_code(params, code_vectors, label, weight, dtype,
                           num_valid_targets, use_fused_ce, fused_ce_mesh)
