"""Attention of a decoder over a paged key/value cache: one call for a
step's mixed batch of prefill chunks and single decode tokens.

The queries of a step are a flat ``[tokens, heads, head_dim]``; which
sequence a token belongs to, how long that sequence's keys are and where
they live is metadata (``cu_q_lens``, ``kv_lens``, ``page_indices``), so
one compiled program serves every mix of lengths.  Keys and values of a
layer live in pages ``[pages, page_size, 2 * kv_heads, head_dim]`` with a
page's K and V heads interleaved (K at even, V at odd combined heads).

The same call serves both kinds of layer:

- a **full** layer's sequence lists its pages in order and grows;
- a **window** layer's sequence is a ring of a fixed number of pages that
  the page table walks circularly: the caller *rebases* each sequence to
  the page that holds the first key inside the window
  (``serving/lm_cache.py::window_view``), so the blocks before the window
  are never read, whatever the context's length, and a long prefill chunk
  is handed over as several short sequences for the same reason.

On a TPU this is JAX's own Pallas kernel (``jax.experimental.pallas.ops.
tpu.ragged_paged_attention``: flash attention over the page table, softmax
in float32); elsewhere (the CPU tests and rehearsals) the plain
``jax.numpy`` form below, which materialises every sequence's keys and is
only fit for tiny sizes.  ``segment softmax`` of ``ops/pallas_ragged.py``
is the single-query, unordered case of the same reduction.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: keys per flash block / queries per block on the chip: (pages a block,
#: queries a block) by whether the call carries a prefill chunk.  Fixed
#: here, not looked up by device kind, so that every run compiles the
#: same kernel.
_PREFILL_BLOCK_KEYS = 512
_PREFILL_BLOCK_QUERIES = 128
_DECODE_BLOCK_KEYS = 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == 'tpu'


def paged_attention_reference(q, kv_pages, kv_lens, page_indices,
                              cu_q_lens, num_seqs, *, sm_scale: float,
                              sliding_window: Optional[int]):
    """The same reduction in plain ``jax.numpy``: every token gathers its
    own sequence's pages.  O(tokens x longest sequence) memory."""
    tokens, q_heads, head_dim = q.shape
    _, page_size, combined, _ = kv_pages.shape
    kv_heads = combined // 2
    max_seqs, pages_per_seq = page_indices.shape
    rows = jnp.arange(tokens, dtype=jnp.int32)
    # the sequence of each token: the last one that starts at or before it
    seq = jnp.clip(jnp.searchsorted(cu_q_lens, rows, side='right') - 1,
                   0, max_seqs - 1).astype(jnp.int32)
    live = (rows < cu_q_lens[num_seqs[0]]) & (seq < num_seqs[0])
    q_len = cu_q_lens[seq + 1] - cu_q_lens[seq]
    kv_len = kv_lens[seq]
    q_pos = kv_len - q_len + (rows - cu_q_lens[seq])
    keys = kv_pages[page_indices[seq]].reshape(
        tokens, pages_per_seq * page_size, combined, head_dim)
    k = keys[:, :, 0::2].astype(jnp.float32)
    v = keys[:, :, 1::2].astype(jnp.float32)
    group = q_heads // kv_heads
    qf = q.astype(jnp.float32).reshape(tokens, kv_heads, group, head_dim)
    scores = jnp.einsum('tkgd,tskd->tkgs', qf, k,
                        precision='highest') * sm_scale
    at = jnp.arange(pages_per_seq * page_size, dtype=jnp.int32)[None, :]
    seen = (at <= q_pos[:, None]) & (at < kv_len[:, None])
    if sliding_window is not None:
        seen &= q_pos[:, None] - at < sliding_window
    seen &= live[:, None]
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    weights = jnp.exp(scores - top)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.where(total > 0, total, 1.0)
    out = jnp.einsum('tkgs,tskd->tkgd', weights, v, precision='highest')
    return out.reshape(tokens, q_heads, head_dim).astype(q.dtype)


def paged_attention(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                    *, sm_scale: float, sliding_window: Optional[int],
                    prefill: bool):
    """``q``'s attention over each token's own sequence, causal, and inside
    ``sliding_window`` keys where that is given.  Rows at or past
    ``cu_q_lens[num_seqs]`` are padding and come back unspecified.

    ``prefill`` says whether the call may carry a chunk of many queries
    (it picks the flash blocks, nothing else)."""
    if not on_tpu():
        return paged_attention_reference(
            q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, sliding_window=sliding_window)
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention)
    page_size = kv_pages.shape[1]
    pages_per_seq = page_indices.shape[1]
    block_keys = _PREFILL_BLOCK_KEYS if prefill else _DECODE_BLOCK_KEYS
    kv_pages_per_block = max(1, min(block_keys // page_size, pages_per_seq))
    queries_per_block = (_PREFILL_BLOCK_QUERIES if prefill
                         else min(q.shape[0], _PREFILL_BLOCK_QUERIES))
    return ragged_paged_attention(
        q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, sliding_window=sliding_window,
        num_kv_pages_per_block=kv_pages_per_block,
        num_queries_per_block=queries_per_block,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)
