"""Experimental Pallas TPU kernel: fused context transform.

Computes, for N = batch·max_contexts context rows at once,

    x      = tanh(src_e @ W_src + path_e @ W_path + tgt_e @ W_tgt)   (N, D)
    scores = x @ attention                                            (N,)

in one pass over row tiles: the three embedding slices multiply against the
row-split TRANSFORM (reference tensorflow_model.py:249-252 concatenates
first — materializing an (N, 3d) intermediate in HBM), the add/tanh/score
matvec all stay in VMEM, and the transform weights are resident in VMEM for
the whole grid.

OFF by default (``Config.USE_PALLAS_FUSED_ENCODE``; the on-chip A/B
measured it 0.99x vs XLA at the java14m bag size — PERF.md "Pallas
fused-encode kernel"). This kernel consumes DENSE ``(N, d)`` rows, i.e. it
runs after the packed wire has already been scattered back to plane
layout, and it stops at the attention scores — the softmax and weighted
sum stay in XLA. Its successor ``ops/pallas_ragged.py``
(``Config.USE_PALLAS_RAGGED_FUSION``) subsumes both limitations for
packed-wire batches: it walks the packed segments directly (no dense
materialization at all) and carries the fusion through the per-example
attention softmax + reduction in the same pass. This module remains the
plane-wire fallback and the minimal staging ground for row-tile encode
experiments. Correctness is tested in interpreter mode on CPU; numerics
match the jnp path to fp32 rounding.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from code2vec_tpu.ops._pallas_common import resolve_interpret

ROW_TILE = 512  # context rows per grid step; N is padded to a multiple


def _kernel(src_ref, path_ref, tgt_ref, w_src_ref, w_path_ref, w_tgt_ref,
            attn_ref, x_ref, scores_ref):
    x = jnp.dot(src_ref[:], w_src_ref[:],
                preferred_element_type=jnp.float32)
    x += jnp.dot(path_ref[:], w_path_ref[:],
                 preferred_element_type=jnp.float32)
    x += jnp.dot(tgt_ref[:], w_tgt_ref[:],
                 preferred_element_type=jnp.float32)
    x = jnp.tanh(x)
    x_ref[:] = x
    scores_ref[:] = jnp.dot(x, attn_ref[:],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=('interpret',))
def fused_context_transform(src_e: jax.Array, path_e: jax.Array,
                            tgt_e: jax.Array, transform: jax.Array,
                            attention: jax.Array,
                            interpret: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """(N, d)-shaped gathered embeddings → (x (N, D), scores (N, 1)).

    ``transform`` is the full (2·d_tok + d_path, D) TRANSFORM matrix; it is
    row-split here to skip the concat. ``attention`` is (D, 1).
    Runs compiled on a TPU; elsewhere it raises ``KernelRequiresTPU``
    unless a test passes ``interpret=True``.
    """
    interpret = resolve_interpret(interpret, 'fused context transform')
    n, token_dim = src_e.shape
    path_dim = path_e.shape[1]
    code_dim = transform.shape[1]
    w_src = transform[:token_dim]
    w_path = transform[token_dim:token_dim + path_dim]
    w_tgt = transform[token_dim + path_dim:]

    padded_n = -(-n // ROW_TILE) * ROW_TILE
    pad = padded_n - n
    if pad:
        src_e = jnp.pad(src_e, ((0, pad), (0, 0)))
        path_e = jnp.pad(path_e, ((0, pad), (0, 0)))
        tgt_e = jnp.pad(tgt_e, ((0, pad), (0, 0)))

    grid = (padded_n // ROW_TILE,)
    row_block = lambda dim: pl.BlockSpec((ROW_TILE, dim),
                                         lambda i: (i, 0))
    full_block = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    x, scores = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            row_block(token_dim), row_block(path_dim), row_block(token_dim),
            full_block(w_src.shape), full_block(w_path.shape),
            full_block(w_tgt.shape), full_block(attention.shape),
        ],
        out_specs=[row_block(code_dim), row_block(1)],
        out_shape=[
            jax.ShapeDtypeStruct((padded_n, code_dim), jnp.float32),
            jax.ShapeDtypeStruct((padded_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(src_e, path_e, tgt_e, w_src, w_path, w_tgt, attention)
    return x[:n], scores[:n]
