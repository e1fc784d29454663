"""The expert layer of a sparse decoder as two grouped matrix products.

Every token goes to its ``top_k`` experts and no token is dropped: the
``tokens x top_k`` assignments are sorted by expert, each expert multiplies
its own contiguous rows (``gate`` and ``up`` fused in one product, then
``down``), and the rows go back to their tokens weighted by the router's
probabilities.  An expert nobody chose is never read, so a decode step of a
few tokens reads the experts it touched and a prefill chunk reads each
expert once.

The layer may hold a **share** of the experts, as a chip does where each
layer's experts are divided over chips (expert parallelism): a contiguous
range of them, told by the id of the first (``first``).  The router still
scores every expert and picks its ``top_k``; a choice that falls on an
expert held here is multiplied, one that falls elsewhere adds nothing here
and is not multiplied (that part of the result is another chip's).  With
``first`` left out every expert is held: the same program as before shares
existed.  A **shared expert**, which every token takes whole and every
chip of such a deployment computes alike, is ``shared_expert``.

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
#: bytes of one expert's block of weights in fast memory at most: two of
#: them (the next one's copy runs while this one is multiplied) and the row
#: tiles stay inside the kernel's 16 MiB of scoped VMEM
WEIGHT_TILE_BYTES = 4 * 1024 * 1024


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
    # its row tiles pass), the output in halves where that is lane-aligned,
    # and halved again while a block would crowd fast memory
    tile_n = n // 2 if (n // 2) % 128 == 0 else n
    while k * tile_n * rhs.dtype.itemsize > WEIGHT_TILE_BYTES and \
            (tile_n // 2) % 128 == 0:
        tile_n //= 2
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=(ROW_TILE, k, tile_n))


def expert_ffn(x, probs, experts, w_gate_up, w_down, valid=None, first=None):
    """``sum_e p_e W_down,e (silu(W_gate,e x) * W_up,e x)`` over each
    token's chosen experts that are held.

    ``x`` [tokens, hidden] in the compute dtype; ``probs``/``experts``
    [tokens, top_k] from ``route``; ``w_gate_up`` [held, hidden, 2 x
    width] (gate columns first); ``w_down`` [held, width, hidden]; ``first``
    the router's id of the first expert held (None: every expert is held).
    Returns float32 [tokens, hidden] and the tokens each held expert
    received ([held] int32; padding rows, ``valid`` false, are routed like
    any row but not counted)."""
    tokens, top_k = experts.shape
    n_experts, _, width2 = w_gate_up.shape
    width = width2 // 2
    flat = experts.reshape(-1)
    held, mode = None, None
    if first is not None:
        local = flat - first
        held = (local >= 0) & (local < n_experts)
        # a choice not held sorts after every held expert's rows: rows past
        # the groups' total are not multiplied
        flat = jnp.where(held, local, n_experts)
        mode = 'drop'
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1, mode=mode)
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
    if held is not None:
        # those rows came back unspecified: nothing of them is read
        per_choice = jnp.where(held.reshape(tokens, top_k, 1), per_choice,
                               0.0)
    out = jnp.sum(per_choice * probs[..., None], axis=1)
    if valid is None:
        counted = sizes
    else:
        counted = jnp.zeros((n_experts,), jnp.int32).at[flat].add(
            jnp.repeat(valid.astype(jnp.int32), top_k), mode=mode)
    return out, counted


def shared_expert(x, w_gate_up, w_down):
    """``W_down (silu(W_gate x) * W_up x)`` of every token: an expert that
    every token takes whole, as two plain products.  ``x`` [tokens,
    hidden] in the compute dtype, ``w_gate_up`` [hidden, 2 x width] (gate
    columns first), ``w_down`` [width, hidden].  Returns float32 [tokens,
    hidden]."""
    precision = 'highest' if x.dtype == jnp.float32 else None
    width = w_gate_up.shape[1] // 2
    gate_up = jnp.dot(x, w_gate_up.astype(x.dtype), precision=precision,
                      preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]).astype(
        x.dtype)
    return jnp.dot(hidden, w_down.astype(x.dtype), precision=precision,
                   preferred_element_type=jnp.float32)
