"""Block-sparse attention over the page pool (InfLLM-V2, the MiniCPM4
family's ``sparse_config``): every query chooses, for each key/value head,
the ``topk`` blocks of ``block_size`` positions it reads.

For a query at position ``i`` and key/value head ``g`` (its group of query
heads ``h``)::

    kbar_j   = mean(k[stride j .. stride j + kernel - 1])     kernel = 2 stride
    p_h      = softmax_j(q_h . kbar_j / sqrt(d))   over j with its last
                                                    position <= i
    P_g      = sum_{h in g} p_h
    score_b  = max(P_g[r b - 1 .. r b + r - 1])    r = block / stride: the
                                                    pooled keys that overlap b
    score_b  = +inf for b < init_blocks and for every block that overlaps
               the last ``window`` positions
    chosen   = the topk highest-scoring visible blocks, ties to the lower b
    o        = causal softmax attention over the chosen blocks' positions,
               the same set for all heads of g

**The cache for stage 1** holds one row every ``stride`` positions: the
mean of the keys of that stride (``stride_means``), written when the
stride's last position is.  A pooled key spans two strides, so its score is
the mean of two stride scores, ``q . kbar_j = (q . s_j + q . s_{j+1}) / 2``:
one product against the stride rows and a shifted add, and no window of a
pooled key ever straddles what a page holds.

**The pool** is laid out for the gather: ``[2 (K, V), blocks, kv_heads,
block_size, d]``, so that what one query reads of one chosen block, a head's
keys (or values), is one contiguous run (16 KiB at the published sizes) and
the keys of many blocks gathered side by side are a matrix as they stand.  A
page is a whole number of consecutive blocks.  JAX's paged kernel wants a
position's heads side by side instead (``ops/lm_attention.py``); laid out
that way, the compiler transposed the whole pool in every layer of every
step to serve this gather.  So the dense branch (a query whose visible
length is at most ``dense_len``) is written here too, over the same pool:
``dense_attention`` gathers the sequence's first ``dense_len`` positions
once for all its queries.

**The choice** is exact and made without a sort: the ``topk``-th largest
score is found by bisection on the scores' bit patterns (32 counts), ties at
it go to the lower block index, and the chosen blocks' indices are read off
a running count.

**Stage 2** works ``QUERY_TILE`` consecutive queries a tile.  Half or more
of what a query chooses is forced and common to its neighbours: the blocks
of its window.  So a tile reads the blocks from its first query's window to
its last query's position ONCE, as one matrix for all its queries and heads
(``local``), each query masked to the blocks it chose there; only the chosen
blocks outside that range (at most ``topk - window / block_size``: the
initial block and the scored ones) are gathered query by query (``far``).
The two parts share one softmax.

**Decode rows** are eight sequences with a query each, most of them idle
between turns, and their lengths differ.  So they are not one tile:
``sparse_attention_rows`` works the live rows one after another, and
gathers and scores a row's stride rows a piece of its page table at a
time, only as far as its position reaches (``ROW_PIECES``); its choice and
plan are then a tile's of one query.

Plain ``jax.numpy`` and ``lax`` gathers: the same code runs in the CPU tests
and compiles for the chip.  On a TPU stage 2 is instead the Pallas kernel
of ``ops/pallas_sparse.py``, handed in as ``kernel``: it reads the same
near range and far lists (``plan_blocks``) straight from the pool, with no
gathered copy; ``attend_blocks`` stays its reference.  The scores of stage
1 accumulate in float32 from the operands as cached, so that the choice of
blocks follows the cache and not a rounding of the scores.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from code2vec_tpu.ops.linear_attention import _mixed

#: queries whose chosen blocks are gathered at once in a prefill chunk
QUERY_TILE = 32


@dataclasses.dataclass(frozen=True)
class SparseGeometry:
    kernel_size: int
    kernel_stride: int
    block_size: int
    window_size: int
    topk: int
    init_blocks: int
    dense_len: int

    @property
    def strides_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    def check(self, page_size: int) -> None:
        if self.kernel_size != 2 * self.kernel_stride:
            raise NotImplementedError(
                'sparse_config.kernel_size %d: only twice kernel_stride '
                '(%d) is implemented' % (self.kernel_size,
                                         self.kernel_stride))
        if self.block_size % self.kernel_stride or \
                page_size % self.block_size:
            raise ValueError(
                'a page (%d) must hold whole blocks (%d) of whole strides '
                '(%d)' % (page_size, self.block_size, self.kernel_stride))
        if self.window_size < self.kernel_size + self.block_size:
            raise ValueError(
                'sparse_config.window_size %d must cover a block and a '
                'pooled key (%d + %d): a block no pooled key has reached '
                'yet has to be a forced one'
                % (self.window_size, self.block_size, self.kernel_size))


def _flat_rows(pool, rows, part: int):
    """Rows of ``pool`` [2, blocks, kv_heads, block, d] seen as
    [2 x blocks x kv_heads x block, d] that hold flat positions ``rows``
    (block x block_size + offset) of part ``part`` (0 keys, 1 values), for
    every head: [len(rows), kv_heads]."""
    _, blocks, kv_heads, block, _ = pool.shape
    head = jnp.arange(kv_heads, dtype=jnp.int32)
    return ((part * blocks + rows // block)[:, None] * kv_heads
            + head[None]) * block + (rows % block)[:, None]


def write_kv(pool, rows, k, v):
    """``k``, ``v`` [tokens, kv_heads, d] written at flat positions ``rows``
    [tokens] of ``pool``.  Written as single rows of the pool seen flat, so
    that the pool keeps the one layout the gathers read."""
    d = pool.shape[-1]
    at = jnp.concatenate([_flat_rows(pool, rows, 0),
                          _flat_rows(pool, rows, 1)]).reshape(-1)
    new = jnp.concatenate([k, v]).astype(pool.dtype).reshape(-1, d)
    return pool.reshape(-1, d).at[at].set(new).reshape(pool.shape)


def stride_means(pool, first_rows, stride: int):
    """The mean key of each stride that starts at flat position
    ``first_rows`` [n] (a block holds whole strides): [n, kv_heads, d]
    float32.  Read as rows of the pool seen flat, as ``write_kv`` writes."""
    at = _flat_rows(pool, first_rows, 0)[:, :, None] \
        + jnp.arange(stride, dtype=jnp.int32)
    keys = pool.reshape(-1, pool.shape[-1])[at]       # [n, kv, stride, d]
    return jnp.mean(keys.astype(jnp.float32), axis=2)


def block_scores(q, positions, means, geo: SparseGeometry):
    """Stage 1.  ``q`` [tokens, q_heads, d] and ``positions`` [tokens] of
    ONE sequence, ``means`` [strides, kv_heads, d] its stride rows by
    stride index (rows of strides not yet complete are never read).
    Returns [tokens, kv_heads, blocks] float32: ``+inf`` at a forced
    block, ``-inf`` at one the query cannot see."""
    return scores_of_strides(stride_scores(q, means), positions, geo)


def stride_scores(q, means):
    """``q`` [tokens, q_heads, d] against stride rows ``means`` [strides,
    kv_heads, d]: [tokens, kv_heads, group, strides] float32, scaled."""
    tokens, q_heads, d = q.shape
    kv_heads = means.shape[1]
    return _mixed('tkgd,mkd->tkgm',
                  q.reshape(tokens, kv_heads, q_heads // kv_heads, d),
                  means) * (1.0 / math.sqrt(d))


def scores_of_strides(qs, positions, geo: SparseGeometry):
    """``block_scores`` from the queries' ``stride_scores`` ``qs``."""
    tokens, kv_heads, _, strides = qs.shape
    r = geo.strides_per_block
    blocks = strides // r
    pooled = 0.5 * (qs[..., :-1] + qs[..., 1:])          # j = 0 .. strides-2
    at = positions.astype(jnp.int32)
    # pooled key j is whole when stride j + kernel - 1 <= i
    whole = jnp.maximum(at - geo.kernel_size + 1 + geo.kernel_stride, 0) \
        // geo.kernel_stride                               # [tokens]
    seen = jnp.arange(strides - 1, dtype=jnp.int32)[None] < whole[:, None]
    seen = seen[:, None, None, :]
    top = jnp.max(jnp.where(seen, pooled, -jnp.inf), axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    weight = jnp.where(seen, jnp.exp(pooled - top), 0.0)
    total = jnp.sum(weight, axis=-1, keepdims=True)
    prob = weight / jnp.where(total > 0, total, 1.0)
    mass = jnp.where(seen[:, :, 0], jnp.sum(prob, axis=2), -1.0)
    # block b reads pooled keys r b - 1 .. r b + r - 1: shift by one
    shifted = jnp.concatenate(
        [jnp.full(mass.shape[:-1] + (1,), -1.0), mass,
         jnp.full(mass.shape[:-1] + (1,), -1.0)], axis=-1)  # [.., strides+1]
    inside = jnp.max(shifted[..., :blocks * r].reshape(
        tokens, kv_heads, blocks, r), axis=-1)
    edge = shifted[..., r::r][..., :blocks]
    score = jnp.maximum(inside, edge)
    b = jnp.arange(blocks, dtype=jnp.int32)[None]
    visible = b <= (at // geo.block_size)[:, None]
    forced = (b < geo.init_blocks) | (
        b >= (jnp.maximum(at - geo.window_size + 1, 0)
              // geo.block_size)[:, None])
    score = jnp.where(forced[:, None], jnp.inf, score)
    return jnp.where(visible[:, None], score, -jnp.inf)


def running_count(flags):
    """``cumsum`` of 0/1 ``flags`` over the last axis, int32.  Inside runs
    of 128 by a product with a triangle of ones (exact: the operands are 0
    and 1, the sums below 256), then the runs' totals carried over: the
    chip's own ``cumsum`` of a long axis is a slow window reduction."""
    n = flags.shape[-1]
    run = 128
    runs = -(-n // run)
    x = jnp.pad(flags.astype(jnp.bfloat16),
                [(0, 0)] * (flags.ndim - 1) + [(0, runs * run - n)])
    x = x.reshape(flags.shape[:-1] + (runs, run))
    triangle = jnp.triu(jnp.ones((run, run), jnp.bfloat16))
    inside = jnp.einsum('...k,kj->...j', x, triangle,
                        preferred_element_type=jnp.float32)
    totals = inside[..., -1]                              # [.., runs]
    before = jnp.einsum('...k,kj->...j', totals,
                        jnp.triu(jnp.ones((runs, runs), jnp.float32), 1),
                        precision='highest')
    count = inside + before[..., None]
    return count.reshape(flags.shape[:-1] + (runs * run,))[..., :n].astype(
        jnp.int32)


def choose(scores, topk: int):
    """Which blocks are chosen, [.., blocks] bool: the ``topk`` highest
    scores among those above ``-inf``, ties to the lower index.  Exact, by
    bisection: a float's bits, made unsigned and order-preserving, are
    searched from the top bit down for the ``topk``-th largest value."""
    blocks = scores.shape[-1]
    if blocks <= topk:
        return scores > -jnp.inf
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(ordered, jnp.uint32) \
        ^ jnp.uint32(0x80000000)

    def narrow(i, kth):
        trial = kth | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= trial[..., None], axis=-1) >= topk
        return jnp.where(enough, trial, kth)
    kth = jax.lax.fori_loop(0, 32, narrow,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    tied = key == kth[..., None]
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (tied & (running_count(tied) <= room))) \
        & (scores > -jnp.inf)


def listed(chosen, slots: int):
    """The indices of the first ``slots`` set entries of ``chosen``
    [.., blocks], and which slots hold one: read off the running count."""
    count = running_count(chosen)
    slot = jnp.arange(slots, dtype=jnp.int32)
    index = jnp.sum(count[..., None, :] <= slot[:, None], axis=-1)
    return (jnp.minimum(index, chosen.shape[-1] - 1).astype(jnp.int32),
            slot < count[..., -1:])


def pool_blocks_of(table, blocks, per_page: int):
    """Where the sequence's blocks ``blocks`` live in the pool, its pages
    being ``table``."""
    return table[blocks // per_page] * per_page + blocks % per_page


def _shared_softmax(parts, values, specs):
    """One softmax over several sets of keys: ``parts`` are (scores, mask)
    with the keys on the last two axes, ``values`` and ``specs`` their
    values and the products that apply them.  Rows that see nothing come
    back zero."""
    top = None
    for scores, mask in parts:
        here = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=(-2, -1),
                       keepdims=True)
        top = here if top is None else jnp.maximum(top, here)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    total, out = 0.0, 0.0
    for (scores, mask), v, spec in zip(parts, values, specs):
        weight = jnp.where(mask, jnp.exp(scores - top), 0.0)
        total = total + jnp.sum(weight, axis=(-2, -1))
        out = out + _mixed(spec, weight.astype(v.dtype), v)
    return out / jnp.where(total > 0, total, 1.0)[..., None]


def _split(positions, live, chosen, geo: SparseGeometry):
    """How stage 2 reads a tile's chosen blocks: the ``span`` blocks from
    ``low`` (from the first live query's window to the last query) are
    read once for the tile, as ``near`` [span] with each query's choice of
    them ``picked`` [tokens, kv_heads, span]; what a query chose outside
    them (before them: see ``plan_blocks``), at most ``slots``, is read for
    it alone, ``far`` [tokens, kv_heads, slots] with ``held`` which slots
    hold one."""
    tokens, _, blocks = chosen.shape
    block = geo.block_size
    at = positions.astype(jnp.int32)
    span = min(blocks, (geo.window_size + tokens) // block + 2)
    alive = live > 0
    first = jnp.min(jnp.where(alive, at, jnp.iinfo(jnp.int32).max))
    first = jnp.where(jnp.any(alive), first, 0)
    low = jnp.clip((first - geo.window_size + 1) // block, 0, blocks - span)
    near = low + jnp.arange(span, dtype=jnp.int32)
    picked = jax.lax.dynamic_slice_in_dim(chosen, low, span, axis=2)
    inside = (jnp.arange(blocks, dtype=jnp.int32) >= low) \
        & (jnp.arange(blocks, dtype=jnp.int32) < low + span)
    slots = max(1, min(geo.topk, blocks) - geo.window_size // block)
    far, held = listed(chosen & ~inside, slots)
    return near, picked, far, held


class BlockPlan(NamedTuple):
    """What stage 2 reads for a tile, in pool blocks (``plan_blocks``)."""
    live: jax.Array         # [] any query of the tile is live
    near: jax.Array         # [span] the near range's pool blocks
    near_mask: jax.Array    # [kv_heads, tokens, span x block] int8: the key
    #                         is chosen, visible and its query live
    far: jax.Array          # [tokens, kv_heads, slots] far pool blocks
    far_count: jax.Array    # [tokens, kv_heads] slots held (0: not live)


def plan_blocks(positions, live, chosen, table, geo: SparseGeometry,
                page_size: int) -> BlockPlan:
    """``attend_blocks``' reading of ``chosen`` for the kernel of
    ``ops/pallas_sparse.py``: the same near range and far lists, as pool
    blocks.  A far block lies before the near range, which begins at or
    before every live query's window, so each of its keys is visible to
    every live query: its mask is which slots are held."""
    tokens = chosen.shape[0]
    block = geo.block_size
    per_page = page_size // block
    at = positions.astype(jnp.int32)
    alive = live > 0
    near, picked, far, held = _split(positions, live, chosen, geo)
    key_at = near[:, None] * block + jnp.arange(block, dtype=jnp.int32)
    mask = (picked[..., None] & alive[:, None, None, None]
            & (key_at[None, None] <= at[:, None, None, None]))
    return BlockPlan(
        live=jnp.any(alive),
        near=pool_blocks_of(table, near, per_page),
        near_mask=mask.transpose(1, 0, 2, 3).reshape(
            chosen.shape[1], tokens, -1).astype(jnp.int8),
        far=pool_blocks_of(table, far, per_page),
        far_count=jnp.where(alive[:, None],
                            jnp.sum(held, axis=-1), 0).astype(jnp.int32))


def attend_blocks(q, positions, live, chosen, table, pool,
                  geo: SparseGeometry, page_size: int):
    """Stage 2 for a tile of consecutive queries.  ``chosen`` [tokens,
    kv_heads, blocks] from ``choose``; ``table`` [pages] the sequence's
    pages (already offset to its layer's slab).  Returns [tokens, q_heads,
    d] float32."""
    tokens, q_heads, d = q.shape
    kv_heads = chosen.shape[1]
    group = q_heads // kv_heads
    block = geo.block_size
    per_page = page_size // block
    at = positions.astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(tokens, kv_heads, group, d)
    near, picked, far, held = _split(positions, live, chosen, geo)
    # local: the near range read once for the tile
    where = pool_blocks_of(table, near, per_page)
    k_near, v_near = pool[0, where], pool[1, where]     # [span, kv, b, d]
    key_at = near[:, None] * block + jnp.arange(block, dtype=jnp.int32)
    near_mask = (picked[..., None]
                 & (key_at[None, None] <= at[:, None, None, None]))
    near_scores = _mixed('tkgd,nkbd->tkgnb', qg, k_near) * scale
    # far: what a query chose outside that range, gathered for it alone
    where = pool_blocks_of(table, far, per_page)
    key_at = far[..., None] * block + jnp.arange(block, dtype=jnp.int32)
    far_mask = held[..., None] & (key_at <= at[:, None, None, None])
    outs = []
    for g in range(kv_heads):
        k_far = pool[0, where[:, g], g]                 # [t, slots, b, d]
        v_far = pool[1, where[:, g], g]
        far_scores = _mixed('tgd,tnbd->tgnb', qg[:, g], k_far) * scale
        outs.append(_shared_softmax(
            [(near_scores[:, g], near_mask[:, g][:, None]),
             (far_scores, far_mask[:, g][:, None])],
            [v_near[:, g], v_far], ['tgnb,nbd->tgd', 'tgnb,tnbd->tgd']))
    return jnp.stack(outs, axis=1).reshape(tokens, q_heads, d)


def dense_attention(q, positions, table, pool, geo: SparseGeometry,
                    page_size: int, tile: int = 256):
    """Causal attention of ``q`` [tokens, q_heads, d] of ONE sequence over
    every key at or before each query, for queries within ``dense_len``:
    the sequence's first ``dense_len`` positions are gathered once.  A
    query beyond ``dense_len`` comes back unspecified but finite."""
    tokens, q_heads, d = q.shape
    kv_heads = pool.shape[2]
    group = q_heads // kv_heads
    count = -(-geo.dense_len // geo.block_size)
    where = pool_blocks_of(table, jnp.arange(count, dtype=jnp.int32),
                           page_size // geo.block_size)
    k, v = pool[0, where], pool[1, where]           # [count, kv, block, d]
    key_at = jnp.arange(count * geo.block_size, dtype=jnp.int32).reshape(
        count, geo.block_size)
    tile = min(tile, tokens)
    tiles = -(-tokens // tile)
    pad = tiles * tile - tokens
    at = positions.astype(jnp.int32)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        at = jnp.pad(at, (0, pad))

    def one(xs):
        qt, pt = xs
        scores = _mixed('tkgd,nkbd->tkgnb',
                        qt.reshape(-1, kv_heads, group, d), k) \
            * (1.0 / math.sqrt(d))
        mask = (key_at[None] <= pt[:, None, None])[:, None, None]
        return _shared_softmax([(scores, mask)], [v], ['tkgnb,nkbd->tkgd'])
    if tiles == 1:
        out = one((q, at))
    else:
        out = jax.lax.map(one, (q.reshape(tiles, tile, q_heads, d),
                                at.reshape(tiles, tile)))
    return out.reshape(-1, q_heads, d)[:tokens]


def _select(q, positions, live, means, geo: SparseGeometry):
    """Stage 1 for a tile: (chosen [tokens, kv_heads, blocks], (blocks
    chosen, blocks visible) summed over the live queries and key/value
    heads)."""
    with jax.named_scope('sparse_select'):
        chosen = choose(block_scores(q, positions, means, geo), geo.topk)
    return chosen, _counted(chosen, positions, live, geo)


def _counted(chosen, positions, live, geo: SparseGeometry):
    """(blocks chosen, blocks visible) summed over the live queries and
    key/value heads."""
    alive = (live > 0)
    picked = jnp.sum(jnp.where(alive[:, None, None], chosen, False))
    visible = jnp.sum(jnp.where(
        alive, positions.astype(jnp.int32) // geo.block_size + 1, 0)) \
        * chosen.shape[1]
    return jnp.stack([picked, visible]).astype(jnp.int32)


def sparse_attention(q, positions, live, means, table, pool,
                     geo: SparseGeometry, page_size: int):
    """Both stages for ``q`` [tokens, q_heads, d], consecutive queries of
    ONE sequence; ``live`` [tokens] marks the queries that take this branch
    (the others' outputs are unspecified but finite).  Returns the outputs
    float32 and (blocks chosen, blocks visible) summed over the live
    queries and key/value heads."""
    chosen, counts = _select(q, positions, live, means, geo)
    with jax.named_scope('sparse_attention'):
        out = attend_blocks(q, positions, live, chosen, table, pool, geo,
                            page_size)
    return out, counts


def planned(q, positions, live, means, table, geo: SparseGeometry,
            page_size: int):
    """``sparse_attention`` up to what its stage 2 reads: (``BlockPlan``,
    counts) for the kernel of ``ops/pallas_sparse.py``."""
    chosen, counts = _select(q, positions, live, means, geo)
    with jax.named_scope('sparse_attention'):
        plan = plan_blocks(positions, live, chosen, table, geo, page_size)
    return plan, counts


def _with_kernel_queries(counts, live, kernel):
    """(blocks chosen, blocks visible, queries whose stage 2 ran in the
    kernel)."""
    queries = jnp.sum(live > 0) if kernel is not None else 0
    return jnp.concatenate([counts, jnp.asarray(queries, jnp.int32)[None]])


def sparse_attention_chunk(q, positions, live, means, table, pool,
                           geo: SparseGeometry, page_size: int,
                           tile: int = QUERY_TILE, kernel=None):
    """``sparse_attention`` for a prefill chunk, ``tile`` queries at a
    time; a tile with no live query is skipped.  ``kernel`` is stage 2 as
    ``ops/pallas_sparse.py::attend_planned`` (None: ``attend_blocks``):
    stage 1 still runs a tile at a time, stage 2 of every tile is one call
    of it.  Returns the outputs and (blocks chosen, blocks visible, queries
    whose stage 2 ran in the kernel)."""
    tokens, q_heads, d = q.shape
    tiles = -(-tokens // tile)
    pad = tiles * tile - tokens
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, (0, pad))
        live = jnp.pad(live, (0, pad))
    tiled = (q.reshape(tiles, tile, q_heads, d),
             positions.reshape(tiles, tile), live.reshape(tiles, tile))
    both = planned if kernel is not None else functools.partial(
        sparse_attention, pool=pool)

    def one(xs):
        qt, pt, lt = xs

        def work():
            return both(qt, pt, lt, means, table, geo=geo,
                        page_size=page_size)

        def skip():
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(work))
        return jax.lax.cond(jnp.any(lt > 0), work, skip)
    done, counts = jax.lax.map(one, tiled)
    if kernel is not None:
        with jax.named_scope('sparse_attention'):
            done = kernel(tiled[0], done, pool)
    return (done.reshape(tiles * tile, q_heads, d)[:tokens],
            _with_kernel_queries(counts.sum(0), live, kernel))


#: the pieces a decode row's page table is cut into: a row's stride rows
#: are gathered and scored a piece at a time, as far as its positions reach
ROW_PIECES = 4


def sparse_attention_rows(q, positions, live, pooled, tables, pool,
                          geo: SparseGeometry, page_size: int,
                          kernel=None):
    """Both stages for decode rows ``q`` [rows, q_heads, d], each of its
    own sequence: ``tables`` [rows, pages] its pages (offset to the layer's
    slab), ``pooled`` [pool pages, strides a page, kv_heads, d] the stride
    rows page for page.

    A row chooses its blocks alone, for what it can see.  The live rows
    are worked one after another, a loop as long as they are many, and a
    row that is not live does nothing and comes back zero.  A live row's
    stride rows are gathered and scored one of ``ROW_PIECES`` pieces of its
    table at a time, up to the piece that holds its position; the strides
    past it are ones it cannot see, and its choice and plan are a tile's
    of one query over the whole table.  With a ``kernel`` stage 2 of every
    row is one call of it, and a row that is not live copies nothing.
    Returns the outputs and (blocks chosen, blocks visible, queries whose
    stage 2 ran in the kernel, stride rows scored)."""
    rows, q_heads, d = q.shape
    pages = tables.shape[1]
    piece = -(-pages // ROW_PIECES)
    # whole pieces: the pages past the table are beyond every position
    tables = jnp.pad(tables, ((0, 0), (0, piece * ROW_PIECES - pages)),
                     mode='edge')
    strides_per_page = pooled.shape[1]
    kv_heads = pooled.shape[2]
    strides = pages * strides_per_page
    alive = live > 0
    order = jnp.nonzero(alive, size=rows, fill_value=0)[0]

    def one(row):
        qr, at, lr = q[row][None], positions[row][None], live[row][None]
        table = tables[row]

        def scored(p, qs):
            means = pooled[jax.lax.dynamic_slice_in_dim(
                table, p * piece, piece)].reshape(-1, kv_heads, d)
            with jax.named_scope('sparse_select'):
                part = stride_scores(qr, means)
            return jax.lax.dynamic_update_slice_in_dim(
                qs, part, p * part.shape[-1], axis=3)
        reach = -(-(at[0] // page_size + 1) // piece)
        qs = jax.lax.fori_loop(
            0, reach, scored,
            jnp.zeros((1, kv_heads, q_heads // kv_heads,
                       piece * ROW_PIECES * strides_per_page), jnp.float32))
        with jax.named_scope('sparse_select'):
            chosen = choose(scores_of_strides(qs[..., :strides], at, geo),
                            geo.topk)
        with jax.named_scope('sparse_attention'):
            if kernel is not None:
                done = plan_blocks(at, lr, chosen, table, geo, page_size)
            else:
                done = attend_blocks(qr, at, lr, chosen, table, pool, geo,
                                     page_size)[0]
        return done, jnp.concatenate(
            [_counted(chosen, at, lr, geo),
             (jnp.minimum(reach * piece, pages)
              * strides_per_page)[None].astype(jnp.int32)])

    def next_row(i, carry):
        done, counts = carry
        row = order[i]
        got, counted = one(row)
        done = jax.tree_util.tree_map(
            lambda every, mine: every.at[row].set(mine), done, got)
        return done, counts + counted
    one_row, counted = jax.eval_shape(one, 0)
    nothing = (jax.tree_util.tree_map(
        lambda s: jnp.zeros((rows,) + s.shape, s.dtype), one_row),
        jnp.zeros(counted.shape, counted.dtype))
    done, counts = jax.lax.fori_loop(0, jnp.sum(alive), next_row, nothing)
    if kernel is not None:
        with jax.named_scope('sparse_attention'):
            done = kernel(q[:, None], done, pool)[:, 0]
    return done, jnp.concatenate(
        [_with_kernel_queries(counts[:2], live, kernel), counts[2:]])
