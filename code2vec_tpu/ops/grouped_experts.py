"""The expert layer of a sparse decoder as two grouped matrix products.

Every token goes to its ``top_k`` experts and no token is dropped: the
``tokens x top_k`` assignments are sorted by expert, each expert multiplies
its own contiguous rows (``gate`` and ``up`` fused in one product, then
``down``), and the rows go back to their tokens weighted by the router's
probabilities.  An expert nobody chose is never read, so a decode step of a
few tokens reads the experts it touched and a prefill chunk reads each
expert once.

On a TPU the grouped product is JAX's Pallas kernel (``megablox.gmm``) with
the whole contraction in one tile, so that consecutive row tiles of one
expert reuse its weights in fast memory; elsewhere ``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from code2vec_tpu.ops.lm_attention import on_tpu

#: rows a tile: the MXU's height.  A tile that straddles two experts is
#: visited once for each, so a smaller tile wastes fewer rows
ROW_TILE = 128


def route(h, router_w, top_k: int, normalize: bool):
    """The router in float32: ``softmax(h W_r)`` over all experts, the
    ``top_k`` largest and their probabilities (renormalised to sum 1 where
    the configuration says so).  ``h`` is float32."""
    logits = jnp.dot(h, router_w.astype(jnp.float32), precision='highest')
    probs = jax.nn.softmax(logits, axis=-1)
    picked, experts = jax.lax.top_k(probs, top_k)
    if normalize:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked, experts.astype(jnp.int32)


def _grouped_dot(lhs, rhs, group_sizes, out_dtype):
    """``lhs[rows of group g] @ rhs[g]``; ``lhs`` rows sorted by group and
    padded to a multiple of ``ROW_TILE`` (rows past the groups' total come
    back unspecified)."""
    if not on_tpu():
        return jax.lax.ragged_dot(
            lhs, rhs.astype(lhs.dtype), group_sizes, precision='highest',
            preferred_element_type=jnp.float32).astype(out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    k, n = rhs.shape[1], rhs.shape[2]
    # the contraction whole (one k tile: the expert's block stays put while
    # its row tiles pass), the output in halves where that is lane-aligned
    tile_n = n // 2 if (n // 2) % 128 == 0 else n
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=(ROW_TILE, k, tile_n))


def expert_ffn(x, probs, experts, w_gate_up, w_down, valid=None):
    """``sum_e p_e W_down,e (silu(W_gate,e x) * W_up,e x)`` over each
    token's chosen experts.

    ``x`` [tokens, hidden] in the compute dtype; ``probs``/``experts``
    [tokens, top_k] from ``route``; ``w_gate_up`` [experts, hidden, 2 x
    width] (gate columns first); ``w_down`` [experts, width, hidden].
    Returns float32 [tokens, hidden] and the tokens each expert received
    ([experts] int32; padding rows, ``valid`` false, are routed like any
    row but not counted)."""
    tokens, top_k = experts.shape
    n_experts, _, width2 = w_gate_up.shape
    width = width2 // 2
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    rows = tokens * top_k
    padded = -(-rows // ROW_TILE) * ROW_TILE
    sorted_x = x[order // top_k]
    if padded != rows:
        sorted_x = jnp.pad(sorted_x, ((0, padded - rows), (0, 0)))
    gate_up = _grouped_dot(sorted_x, w_gate_up, sizes, x.dtype)
    gate = gate_up[:, :width].astype(jnp.float32)
    up = gate_up[:, width:].astype(jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    down = _grouped_dot(hidden, w_down, sizes, x.dtype)
    # back to the tokens' order: row i*top_k + j is token i's j-th expert
    back = jnp.argsort(order)
    per_choice = down[back].astype(jnp.float32).reshape(tokens, top_k, -1)
    out = jnp.sum(per_choice * probs[..., None], axis=1)
    if valid is None:
        counted = sizes
    else:
        counted = jnp.zeros((n_experts,), jnp.int32).at[flat].add(
            jnp.repeat(valid.astype(jnp.int32), top_k))
    return out, counted
