"""Stage 2 of the block-sparse layers as one Pallas TPU kernel: the blocks
``ops/sparse_attention.py`` chose for each query, copied from the page pool
straight into VMEM and attended there.

The ``jax.numpy`` form (``sparse_attention.attend_blocks``) gathers each
query's far blocks into a ``[tokens, slots, block, d]`` array a key/value
head, writes it to HBM and reads it back for the products.  Here the pool
stays in HBM (``memory_space=pl.ANY``) and the pool-block ids of
``sparse_attention.plan_blocks`` are scalar-prefetched; every chosen
block's keys and values (16 KiB each at the published sizes) are copied by
an async copy into a VMEM buffer, a wave of blocks a copy, two buffers so
that the next wave's copies run while this wave is multiplied.  The model
is JAX's own paged-attention kernel
(``jax.experimental.pallas.ops.tpu.paged_attention``).

One grid step is one tile: consecutive queries of one sequence (32 in a
prefill chunk) or one decode row.  Its waves, in order:

- **near**: the blocks from the tile's first live query's window to its
  last query, copied once and used as one matrix for every query and head
  of a key/value head, masked per query to what it chose (and causally);
- **far**: what one query chose before that range (at most ``topk -
  window / block`` slots), copied for that query and key/value head alone;
  every slot is copied where one is held (a slot not held copies a block
  of the sequence and is weighted 0), so that a wave is waited for once,
  and a query that is not live or chose nothing there copies nothing.

The two parts share one softmax, kept online (running max and sum): the
one reassociation against the ``jax.numpy`` form.  Operands as the pool
holds them, float32 accumulation, the weights rounded to the values'
dtype for their product, as ``linear_attention._mixed`` does.  A tile with
no live query copies nothing and returns zeros; so does a query that is
not live.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code2vec_tpu.ops._pallas_common import resolve_interpret

#: lanes of the running max and sum (one value a row, kept lane-wide)
_LANES = 128
#: scores of one near wave at most (rows x keys): 1 MiB of float32
_NEAR_SCORES = 1 << 18
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _near_wave(rows: int, span: int, block: int, slots: int) -> int:
    """Blocks of one near wave: as many as a far wave's buffer holds, and
    at most ``_NEAR_SCORES`` scores for ``rows`` query rows."""
    return max(1, min(span, slots, _NEAR_SCORES // (rows * block)))


def _kernel(tile_live, near_ids, far_ids, far_count,        # scalars
            q_ref, mask_ref, pool_ref,                        # inputs
            out_ref,                                          # output
            k_buf, v_buf, sems, m_ref, l_ref, acc_ref,        # scratch
            *, kv_heads: int, group: int, span: int, slots: int,
            wave: int, scale: float):
    tile = pl.program_id(0)
    tokens = q_ref.shape[0]
    block, d = k_buf.shape[2], k_buf.shape[3]
    rows = tokens * group
    near = [(g, j, min(wave, span - j)) for g in range(kv_heads)
            for j in range(0, span, wave)]
    exact = q_ref.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None

    def copy(part, block_id, g, slot, j):
        buf = k_buf if part == 0 else v_buf
        return pltpu.make_async_copy(pool_ref.at[part, block_id, g],
                                     buf.at[slot, j], sems.at[part, slot])

    def wait_wave(slot, nb):
        """Waits for a wave's ``nb`` blocks of keys and of values: one wait
        a part for all their bytes (the copies signal one semaphore)."""
        for part, buf in ((0, k_buf), (1, v_buf)):
            whole = buf.at[slot, pl.ds(0, nb)]
            pltpu.make_async_copy(whole, whole, sems.at[part, slot]).wait()

    def far_at(t, g):
        return (tile * tokens + t) * kv_heads + g

    def start_wave(index, slot):
        """Starts wave ``index``: a near one (static), or the far one of
        query ``index[0]`` and key/value head ``index[1]``, every slot of
        it (a slot not held copies a block of the sequence, weighted 0)
        where any is held."""
        def each(count, block_of, g):
            # a loop and not unrolled: unrolled waves made each trace of
            # the kernel take about a second, eight a step program
            def one(j, carry):
                for part in (0, 1):
                    copy(part, block_of(j), g, slot, j).start()
                return carry
            jax.lax.fori_loop(0, count, one, 0)
        if isinstance(index, int):
            g, j0, nb = near[index]
            each(nb, lambda j: near_ids[tile * span + j0 + j], g)
            return
        t, g = index
        base = far_at(t, g) * slots

        @pl.when(far_count[far_at(t, g)] > 0)
        def _copy():
            each(slots, lambda j: far_ids[base + j], g)

    def product(a, b, b_axis):
        """``a`` [n, x] times ``b`` contracted on its axis ``b_axis``,
        float32 accumulation."""
        return jax.lax.dot_general(
            a, b, (((1,), (b_axis,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    def scores_of(q, k):
        return product(q, k, 1) * scale

    def update(g, at, scores, values):
        """Folds ``scores`` [n, keys] (masked to -inf) of rows ``at`` of
        key/value head ``g`` into the running max, sum and output."""
        n = scores.shape[0]
        m_prev = m_ref[g, at][:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        m_use = jnp.where(m_next == -jnp.inf, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_use)
        weight = jnp.exp(scores - m_use)
        total = alpha * l_ref[g, at][:, :1] + jnp.sum(weight, axis=1,
                                                       keepdims=True)
        l_ref[g, at] = jnp.broadcast_to(total, (n, _LANES))
        m_ref[g, at] = jnp.broadcast_to(m_next, (n, _LANES))
        acc_ref[g, at] = alpha * acc_ref[g, at] + product(
            weight.astype(values.dtype), values, 0)

    @pl.when(tile_live[tile] == 0)
    def _nothing():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(tile_live[tile] != 0)
    def _live():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start_wave(0, 0)
        for i, (g, j0, nb) in enumerate(near):
            slot = i % 2
            if i + 1 < len(near):
                start_wave(i + 1, 1 - slot)
            else:
                start_wave((0, 0), 1 - slot)
            wait_wave(slot, nb)
            keys = nb * block
            q = q_ref[:, g * group:(g + 1) * group, :].reshape(rows, d)
            scores = scores_of(q, k_buf[slot, :nb].reshape(keys, d))
            mask = mask_ref[g, :, j0 * block:j0 * block + keys].astype(
                jnp.int32) != 0
            scores = jnp.where(mask[:, None, :],
                               scores.reshape(tokens, group, keys),
                               -jnp.inf).reshape(rows, keys)
            update(g, slice(None), scores,
                   v_buf[slot, :nb].reshape(keys, d))

        keys = slots * block
        for g in range(kv_heads):
            def far(t, carry, g=g):
                slot = (len(near) + g * tokens + t) % 2
                nxt = 1 - slot

                @pl.when(t + 1 < tokens)
                def _next_query():
                    start_wave((t + 1, g), nxt)
                if g + 1 < kv_heads:
                    @pl.when(t + 1 == tokens)
                    def _next_head():
                        start_wave((0, g + 1), nxt)
                held = far_count[far_at(t, g)]

                @pl.when(held > 0)
                def _fold():
                    wait_wave(slot, slots)
                    scores = scores_of(q_ref[t, g * group:(g + 1) * group, :],
                                       k_buf[slot].reshape(keys, d))
                    lane = jax.lax.broadcasted_iota(jnp.int32,
                                                    scores.shape, 1)
                    scores = jnp.where(lane < held * block, scores, -jnp.inf)
                    at = pl.ds(pl.multiple_of(t * group, group), group)
                    update(g, at, scores, v_buf[slot].reshape(keys, d))
                return carry
            jax.lax.fori_loop(0, tokens, far, 0)

        for g in range(kv_heads):
            total = l_ref[g][:, :1]
            out = acc_ref[g] / jnp.where(total > 0, total, 1.0)
            out_ref[:, g * group:(g + 1) * group, :] = out.reshape(
                tokens, group, d)


def attend_planned(q, plan, pool, *, interpret: bool = False):
    """Stage 2 of every tile of ``q`` [tiles, tokens, q_heads, d] over
    ``pool`` [2, blocks, kv_heads, block, d], the blocks read being
    ``plan`` (``sparse_attention.plan_blocks`` stacked over tiles).
    Returns [tiles, tokens, q_heads, d] float32: zeros at a query that is
    not live."""
    return _attend(q, plan, pool,
                   resolve_interpret(interpret, 'block-sparse stage 2'))


# jitted so that the kernel is traced once a shape and process: a step
# program calls it twice a sparse layer, and there are five programs
@functools.partial(jax.jit, static_argnums=(3,))
def _attend(q, plan, pool, interpret: bool):
    tiles, tokens, q_heads, d = q.shape
    _, _, kv_heads, block, _ = pool.shape
    group = q_heads // kv_heads
    span = plan.near.shape[-1]
    slots = plan.far.shape[-1]
    wave = _near_wave(tokens * group, span, block, slots)
    kernel = functools.partial(
        _kernel, kv_heads=kv_heads, group=group, span=span, slots=slots,
        wave=wave, scale=1.0 / math.sqrt(d))
    tile_of = pl.BlockSpec((None, tokens, q_heads, d),
                           lambda i, *_: (i, 0, 0, 0))
    buffer = (2, slots, block, d)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[tile_of,
                      pl.BlockSpec((None, kv_heads, tokens, span * block),
                                   lambda i, *_: (i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile_of,
            scratch_shapes=[
                pltpu.VMEM(buffer, pool.dtype),
                pltpu.VMEM(buffer, pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kv_heads, tokens * group, _LANES), jnp.float32),
                pltpu.VMEM((kv_heads, tokens * group, _LANES), jnp.float32),
                pltpu.VMEM((kv_heads, tokens * group, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='sparse_attention',
    )(plan.live.astype(jnp.int32), plan.near.reshape(-1),
      plan.far.reshape(-1), plan.far_count.reshape(-1),
      q, plan.near_mask, pool)
