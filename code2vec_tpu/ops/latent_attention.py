"""Multi-head latent attention (MLA) over a page pool of latent vectors: a
position keeps ONE vector for every head, its normed key/value latent
``c`` and its rotated rope key ``kr`` (shared by the heads), and the
per-head keys and values are never stored.

For head ``h`` with query ``q_h = [q_nope | q_rope]``, up-projection
``W_kvb`` = per head ``[W_uk,h | W_uv,h]`` (``kv_lora`` x ``nope`` and
``kv_lora`` x ``v``)::

    score_h(i, j) = (q_nope,h . c_j W_uk,h + q_rope,h . kr_j) * scale
    o_h(i)        = sum_j softmax_j(score_h)(i, j) c_j W_uv,h

Two ways of computing the same thing, chosen by the row's kind:

- **absorbed** (a decode row: one query against a long history):
  ``q_lat,h = q_nope,h W_uk,h^T`` and ``o_h = (sum_j p_j c_j) W_uv,h``, so
  that the history's latents are read once for all heads and nothing is
  up-projected.  On a TPU this is the repo's Pallas kernel
  (``ops/pallas_latent.py``); ``absorbed_reference`` is its plain form.
- **expanded** (a prompt chunk: many queries against the history): the
  history is up-projected a block of positions at a time and attended per
  head with an online softmax (``expanded_chunk``); no ``[chunk, history,
  heads]`` array of scores is ever made.  Its cost grows with the chunk
  far more slowly than the absorbed form's.  On a TPU this is the repo's
  Pallas kernel too (``ops/pallas_latent.py::expanded_prefill``), which
  keeps a block's scores in fast memory: as plain XLA every block's float32
  scores went to HBM and back two or three times, five times the
  products' own time.

**The pool** of one layer is ``[pages, kv_lora + rope, page_size]``: a
page holds its positions' latents feature by feature, so that the lane
axis is the page's positions (128 at the cell's sizes) and no width is
padded to the lane tiling (``kv_lora + rope`` = 320 is not a multiple of
128; as ``[positions, 320]`` every row would be padded to 384).  The
absorbed product reads a page as the matrix it stands as; a chunk's block
of history is transposed once when it is up-projected.

Operands as the pool holds them, float32 accumulation; float32 operands
(the tests' exact mode) at full precision.  The softmax is float32; the
one reassociation against a plain softmax is the online one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from code2vec_tpu.ops.linear_attention import _mixed

#: float32 scores one block of the expanded path may hold (chunk x heads x
#: keys): 256 MiB
_BLOCK_SCORES = 1 << 26


def write_rows(pool, rows, latents):
    """``latents`` [rows, kv_lora + rope] written at flat positions
    ``rows`` (page x page_size + offset) of ``pool``, each row in a page of
    its own (decode rows: one position of one sequence each; rows that
    hold no sequence all write the spare page).  Whole pages are read and
    written back: a write of one position's column would make the compiler
    lay the whole pool out position-major, padded, and copy it every
    step."""
    page_size = pool.shape[-1]
    pages = rows // page_size
    lane = jnp.arange(page_size, dtype=jnp.int32)
    new = jnp.where(lane[None, None, :] == (rows % page_size)[:, None, None],
                    latents.astype(pool.dtype)[:, :, None], pool[pages])
    return pool.at[pages].set(new)


def write_chunk(pool, table, first, count, latents, spare):
    """``latents`` [chunk, kv_lora + rope] of positions ``first .. first +
    count - 1`` (the rows past ``count`` are padding) written into the
    pages ``table`` [pages] of ONE sequence, whole pages as ``write_rows``
    writes them: every page a chunk of this bucket can touch, those past
    its last page read from and written back to the page ``spare``."""
    page_size = pool.shape[-1]
    chunk = latents.shape[0]
    slabs = (chunk + page_size - 2) // page_size + 1
    start = first // page_size
    index = start + jnp.arange(slabs, dtype=jnp.int32)
    pages = jnp.where(index <= (first + count - 1) // page_size,
                      table[jnp.minimum(index, table.shape[0] - 1)], spare)
    at = index[:, None] * page_size \
        + jnp.arange(page_size, dtype=jnp.int32)[None, :] - first
    columns = jnp.take(latents.astype(pool.dtype).T,
                       jnp.clip(at, 0, chunk - 1), axis=1)  # [width, K, P]
    new = jnp.where(((at >= 0) & (at < count))[:, None, :],
                    jnp.swapaxes(columns, 0, 1), pool[pages])
    return pool.at[pages].set(new)


def absorb_query(q_nope, q_rope, w_uk):
    """The absorbed query ``[q_nope,h W_uk,h^T | q_rope,h]`` [rows, heads,
    kv_lora + rope] float32; ``w_uk`` [kv_lora, heads, nope]."""
    q_lat = _mixed('thn,chn->thc', q_nope, w_uk.astype(q_nope.dtype))
    return jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)


def expand_output(o_lat, w_uv, dtype):
    """``o_h = o_lat,h W_uv,h`` [rows, heads, v] float32 of the latent
    outputs ``o_lat`` [rows, heads, kv_lora]; ``w_uv`` [kv_lora, heads,
    v]; both multiplied in ``dtype``."""
    return _mixed('thc,chv->thv', o_lat.astype(dtype), w_uv.astype(dtype))


def absorbed_reference(q, pool, lengths, tables, *, kv_lora: int,
                       scale: float):
    """The absorbed product in plain ``jax.numpy``: every row gathers its
    whole page table.  ``q`` [rows, heads, kv_lora + rope] (as
    ``absorb_query`` makes it, in the pool's dtype), ``lengths`` [rows] the
    keys each row sees (0: an idle row, which comes back zero), ``tables``
    [rows, pages].  Returns ``sum_j p_j c_j`` [rows, heads, kv_lora]
    float32."""
    rows, pages = tables.shape
    _, width, page_size = pool.shape
    latents = pool[tables]                       # [rows, pages, width, P]
    latents = jnp.swapaxes(latents, 2, 3).reshape(rows, pages * page_size,
                                                  width)
    scores = _mixed('rhw,rkw->rhk', q, latents) * scale
    at = jnp.arange(pages * page_size, dtype=jnp.int32)
    seen = (at[None, :] < lengths[:, None])[:, None, :]
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    weight = jnp.where(seen, jnp.exp(scores - top), 0.0)
    total = jnp.sum(weight, axis=-1, keepdims=True)
    out = _mixed('rhk,rkc->rhc', weight.astype(latents.dtype),
                 latents[..., :kv_lora])
    return out / jnp.where(total > 0, total, 1.0)


def block_of(chunk: int, heads: int, page_size: int) -> int:
    """Positions of history the expanded path up-projects at a time: as
    many as keep a block's scores within ``_BLOCK_SCORES``, whole pages,
    between one page and 4,096 positions."""
    keys = _BLOCK_SCORES // max(chunk * heads, 1)
    keys = max(page_size, min(4096, keys)) // page_size * page_size
    return max(keys, page_size)


def expanded_chunk(q_nope, q_rope, first, table, kv_len, pool, w_kvb, *,
                   kv_lora: int, scale: float, block: int = 0):
    """Attention of a chunk's queries (ONE sequence) over its history by
    the expanded form.  ``q_nope`` [tokens, heads, nope], ``q_rope``
    [tokens, heads, rope] (rotated and scaled), the query of row ``t`` at
    position ``first + t``, ``table`` [pages] the sequence's pages in the
    layer's slab, ``kv_len`` the keys (every position before the chunk and
    the chunk's own, already written), ``w_kvb`` [kv_lora, heads, nope +
    v].  ``block`` positions of history (whole pages; 0: ``block_of``'s)
    are up-projected at a time, and a head's key is ``[k_nope | kr]``, so
    that a block's scores are one product.  Returns [tokens, heads, v]
    float32; a query sees the keys at or before its position (rows past
    the chunk are padding and come back unspecified but finite).  On a TPU
    the same is the kernel ``ops/pallas_latent.py::expanded_prefill``."""
    tokens, heads, nope = q_nope.shape
    rope = q_rope.shape[-1]
    page_size = pool.shape[-1]
    block = block or block_of(tokens, heads, page_size)
    per = block // page_size
    pages = table.shape[0]
    table = jnp.pad(table, (0, -(-pages // per) * per - pages))
    dtype = pool.dtype
    w_k = w_kvb[..., :nope].astype(dtype)
    w_v = w_kvb[..., nope:].astype(dtype)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    at = first + jnp.arange(tokens, dtype=jnp.int32)

    def one(b, carry):
        top, total, acc = carry
        held = jax.lax.dynamic_slice_in_dim(table, b * per, per)
        latents = jnp.swapaxes(pool[held], 1, 2).reshape(block, -1)
        c, kr = latents[:, :kv_lora], latents[:, kv_lora:]
        k = jnp.concatenate(
            [_mixed('bc,chn->bhn', c, w_k).astype(dtype),
             jnp.broadcast_to(kr[:, None, :], (block, heads, rope))],
            axis=-1)
        v = _mixed('bc,chv->bhv', c, w_v).astype(dtype)
        scores = _mixed('thd,bhd->thb', q, k) * scale
        key_at = b * block + jnp.arange(block, dtype=jnp.int32)
        seen = ((key_at[None, :] <= at[:, None])
                & (key_at[None, :] < kv_len))[:, None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        use = jnp.where(jnp.isfinite(new_top), new_top, 0.0)
        alpha = jnp.exp(top - use)
        weight = jnp.exp(scores - use[..., None])
        total = alpha * total + jnp.sum(weight, axis=-1)
        acc = alpha[..., None] * acc + _mixed('thb,bhv->thv',
                                              weight.astype(dtype), v)
        return new_top, total, acc

    v_dim = w_kvb.shape[-1] - nope
    start = (jnp.full((tokens, heads), -jnp.inf, jnp.float32),
             jnp.zeros((tokens, heads), jnp.float32),
             jnp.zeros((tokens, heads, v_dim), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, (kv_len + block - 1) // block, one,
                                      start)
    return acc / jnp.where(total > 0, total, 1.0)[..., None]
