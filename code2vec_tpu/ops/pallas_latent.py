"""Multi-head latent attention over latent pages as two Pallas TPU kernels:
the absorbed product of the decode rows (``absorbed_decode``), each row's
latent pages copied from the pool straight into VMEM ONCE for all heads,
and the expanded product of a prompt chunk (``expanded_prefill``), the
chunk's history up-projected a block at a time in VMEM and attended there,
its scores never written to HBM.

``ops/latent_attention.py`` says what is computed: for a decode row with
absorbed query ``q`` [heads, kv_lora + rope] and the latents ``L_j =
[c_j | kr_j]`` of its history, ``p = softmax_j(q . L_j * scale)`` per head
and ``o = sum_j p_j c_j`` [heads, kv_lora].  A page of the pool is
``[kv_lora + rope, page_size]`` (features by positions), so a page is one
matrix: ``q @ page`` gives every head's scores of its positions and
``p @ page[:kv_lora]^T`` every head's part of ``o``.

The pool stays in HBM (``memory_space=pl.ANY``) and the page tables and
the rows' lengths are scalar-prefetched.  One grid step is one decode row;
its pages are copied by async copies side by side into a VMEM buffer
``[kv_lora + rope, WAVE x page_size]``, ``WAVE`` pages a wave, two
buffers, so that the next wave's copies run while this wave is multiplied
as ONE matrix (two products and one softmax update a wave, not a page).
Only the row's own pages are copied (a wave past its last page copies
less, and zeros the rest of its buffer), and a row of length 0 (an idle
decode slot) copies nothing and returns zeros.  The softmax is kept online
(running max and sum, float32): the one reassociation against the plain
form.  Operands as the pool holds them, float32 accumulation, the weights
rounded to the pool's dtype for their product.  The model is JAX's own
paged-attention kernel (``jax.experimental.pallas.ops.tpu.paged_attention``)
and ``ops/pallas_sparse.py``.

The expanded kernel is a flash attention whose keys and values are made in
fast memory: one grid step is a group of heads; a block of the history's
pages is copied in (two buffers, as above), each head of the group
up-projects it (``k_nope = W_uk^T c``, ``v = W_uv^T c``, float32
accumulation, rounded to the pool's dtype as the plain form rounds them),
scores the chunk's queries against ``[k_nope | kr]`` and folds the block
into its running max, sum and output.  A block is read once a group, and a
group holds as many heads as keep its outputs within ``_GROUP_ROWS`` rows.
Only a block that reaches past the chunk's first position is masked:
every query sees the blocks before it whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code2vec_tpu.ops._pallas_common import resolve_interpret

#: pages a wave: 8 pages of 128 positions are 640 KiB of latents
WAVE = 8
#: lanes of the running max and sum (one value a head, kept lane-wide)
_LANES = 128
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: history positions the expanded kernel up-projects at a time
PREFILL_BLOCK = 512
#: query rows x heads a grid step of the expanded kernel keeps outputs for
_GROUP_ROWS = 8192
_PREFILL_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _kernel(lengths_ref, tables_ref,                     # scalars
            q_ref, pool_ref,                             # inputs
            out_ref,                                     # output
            buf, sems, m_ref, l_ref, acc_ref,            # scratch
            *, wave: int, pages_per_row: int, kv_lora: int, page: int,
            scale: float):
    row = pl.program_id(0)
    length = lengths_ref[row]
    pages = (length + page - 1) // page
    waves = (pages + wave - 1) // wave
    exact = q_ref.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None

    def lanes(j):
        return pl.ds(j * page, page)

    def start(w, slot):
        for j in range(wave):
            @pl.when(w * wave + j < pages)
            def _copy():
                index = tables_ref[row * pages_per_row + w * wave + j]
                pltpu.make_async_copy(pool_ref.at[index],
                                      buf.at[slot, :, lanes(j)],
                                      sems.at[slot]).start()

    def wait(w, slot):
        for j in range(wave):
            @pl.when(w * wave + j < pages)
            def _wait():
                # a wait takes one page's bytes off the slot's semaphore
                pltpu.make_async_copy(pool_ref.at[0],
                                      buf.at[slot, :, lanes(j)],
                                      sems.at[slot]).wait()

            @pl.when(w * wave + j >= pages)
            def _clear():
                # a page the row does not hold: weighted 0, and kept finite
                # so that 0 times it is 0
                buf[slot, :, lanes(j)] = jnp.zeros(
                    (buf.shape[1], page), buf.dtype)

    @pl.when(length == 0)
    def _idle():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(length > 0)
    def _live():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[0]                                     # [heads, width]
        heads = q.shape[0]
        start(0, 0)

        def body(w, carry):
            slot = w % 2

            @pl.when(w + 1 < waves)
            def _next():
                start(w + 1, 1 - slot)
            wait(w, slot)
            latents = buf[slot]                          # [width, wave x P]
            scores = jax.lax.dot_general(
                q, latents, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            at = w * wave * page + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            scores = jnp.where(at < length, scores, -jnp.inf)
            m_prev = m_ref[...][:, :1]
            m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1,
                                                 keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            weight = jnp.exp(scores - m_next)
            total = alpha * l_ref[...][:, :1] + jnp.sum(weight, axis=1,
                                                        keepdims=True)
            l_ref[...] = jnp.broadcast_to(total, (heads, _LANES))
            m_ref[...] = jnp.broadcast_to(m_next, (heads, _LANES))
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                weight.astype(latents.dtype), latents[:kv_lora],
                (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
            return carry
        jax.lax.fori_loop(0, waves, body, 0)
        total = l_ref[...][:, :1]
        out_ref[0] = acc_ref[...] / jnp.where(total > 0, total, 1.0)


def absorbed_decode(q, pool, lengths, tables, *, kv_lora: int, scale: float,
                    interpret: bool = False):
    """``latent_attention.absorbed_reference`` as the kernel: ``q`` [rows,
    heads, kv_lora + rope] in the pool's dtype, ``pool`` [pages, kv_lora +
    rope, page_size], ``lengths`` [rows] int32 (0: an idle row),
    ``tables`` [rows, pages a row] int32.  Returns [rows, heads, kv_lora]
    float32."""
    return _decode(q, pool, lengths, tables, kv_lora, float(scale),
                   resolve_interpret(interpret, 'absorbed latent decode'),
                   WAVE)


# jitted so that the kernel is traced once a shape and process: a step
# program calls it once a layer
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _decode(q, pool, lengths, tables, kv_lora: int, scale: float,
            interpret: bool, wave: int):
    rows, heads, width = q.shape
    _, _, page_size = pool.shape
    pages_per_row = tables.shape[1]
    wave = min(wave, pages_per_row)
    kernel = functools.partial(_kernel, wave=wave,
                               pages_per_row=pages_per_row, kv_lora=kv_lora,
                               page=page_size, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[pl.BlockSpec((1, heads, width),
                                   lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, kv_lora),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, width, wave * page_size), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, kv_lora), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, kv_lora), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='latent_decode',
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      q, pool)


def _prefill_kernel(meta_ref, table_ref,                 # scalars
                    q_ref, wk_ref, wv_ref, pool_ref,     # inputs
                    out_ref,                             # output
                    buf, sems, m_ref, l_ref, acc_ref,    # scratch
                    *, per: int, kv_lora: int, page: int, scale: float):
    kv_len, first = meta_ref[0], meta_ref[1]
    group, tokens, _ = q_ref.shape
    block = per * page
    blocks = (kv_len + block - 1) // block
    pages = (kv_len + page - 1) // page
    exact = q_ref.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None
    dtype = buf.dtype

    def lanes(j):
        return pl.ds(j * page, page)

    def start(b, slot):
        for j in range(per):
            @pl.when(b * per + j < pages)
            def _copy():
                pltpu.make_async_copy(pool_ref.at[table_ref[b * per + j]],
                                      buf.at[slot, :, lanes(j)],
                                      sems.at[slot]).start()

    def wait(b, slot):
        for j in range(per):
            @pl.when(b * per + j < pages)
            def _wait():
                pltpu.make_async_copy(pool_ref.at[0],
                                      buf.at[slot, :, lanes(j)],
                                      sems.at[slot]).wait()

            @pl.when(b * per + j >= pages)
            def _clear():
                buf[slot, :, lanes(j)] = jnp.zeros((buf.shape[1], page),
                                                   dtype)

    def product(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start(0, 0)

    def attend(b, slot, masked: bool):
        """Folds block ``b`` into every head's running softmax.
        ``masked``: the block holds keys after a query's position or past
        ``kv_len`` (the chunk's own keys); a block wholly at or before the
        chunk's first position is seen by every query, and needs no
        mask."""
        latents = buf[slot]                              # [width, block]
        c = latents[:kv_lora]

        def head(g, carry):
            keys = jnp.concatenate(
                [product(wk_ref[g], c, ((1,), (0,))).astype(dtype),
                 latents[kv_lora:]], axis=0)             # [nope+rope, block]
            values = product(wv_ref[g], c, ((1,), (0,))).astype(dtype)
            scores = product(q_ref[g], keys, ((1,), (0,))) * scale
            if masked:
                # the query at row t of the chunk is at position first + t;
                # padding rows past the chunk see its keys too, and are
                # dropped by the caller
                at_query = first + jax.lax.broadcasted_iota(
                    jnp.int32, (tokens, block), 0)
                at_key = b * block + jax.lax.broadcasted_iota(
                    jnp.int32, (tokens, block), 1)
                scores = jnp.where((at_key <= at_query) & (at_key < kv_len),
                                   scores, -jnp.inf)
            m_prev = m_ref[g][:, :1]
            m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1,
                                                 keepdims=True))
            m_use = jnp.where(m_next == -jnp.inf, 0.0, m_next)
            alpha = jnp.exp(m_prev - m_use)
            weight = jnp.exp(scores - m_use)
            l_ref[g] = jnp.broadcast_to(
                alpha * l_ref[g][:, :1] + jnp.sum(weight, axis=1,
                                                  keepdims=True),
                (tokens, _LANES))
            m_ref[g] = jnp.broadcast_to(m_next, (tokens, _LANES))
            acc_ref[g] = alpha * acc_ref[g] + product(
                weight.astype(dtype), values, ((1,), (1,)))
            return carry
        jax.lax.fori_loop(0, group, head, 0)

    def body(b, carry):
        slot = b % 2

        @pl.when(b + 1 < blocks)
        def _next():
            start(b + 1, 1 - slot)
        wait(b, slot)
        whole = (b + 1) * block <= first + 1

        @pl.when(whole)
        def _whole():
            attend(b, slot, False)

        @pl.when(jnp.logical_not(whole))
        def _masked():
            attend(b, slot, True)
        return carry
    jax.lax.fori_loop(0, blocks, body, 0)

    def finish(g, carry):
        total = l_ref[g][:, :1]
        out_ref[g] = acc_ref[g] / jnp.where(total > 0, total, 1.0)
        return carry
    jax.lax.fori_loop(0, group, finish, 0)


def expanded_prefill(q_nope, q_rope, first, table, kv_len, pool, w_kvb, *,
                     kv_lora: int, scale: float, interpret: bool = False):
    """``latent_attention.expanded_chunk`` as the kernel: the chunk's
    queries at positions ``first ..`` (``q_nope`` [tokens, heads, nope],
    ``q_rope`` [tokens, heads, rope], rotated and scaled, in the pool's
    dtype), ``table`` [pages] the sequence's pages in the layer's slab,
    ``kv_len`` the keys, ``w_kvb`` [kv_lora, heads, nope + v].  Returns
    [tokens, heads, v] float32."""
    return _prefill(q_nope, q_rope, jnp.stack([kv_len, first]).astype(
        jnp.int32), table.astype(jnp.int32), pool, w_kvb, kv_lora,
        float(scale), resolve_interpret(interpret, 'expanded latent prefill'),
        PREFILL_BLOCK)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _prefill(q_nope, q_rope, meta, table, pool, w_kvb, kv_lora: int,
             scale: float, interpret: bool, prefill_block: int):
    tokens, heads, nope = q_nope.shape
    _, width, page_size = pool.shape
    v_dim = w_kvb.shape[-1] - nope
    dtype = pool.dtype
    group = max(1, min(heads, _GROUP_ROWS // tokens))
    while heads % group:
        group -= 1
    per = max(1, prefill_block // page_size)
    block = per * page_size
    # head-major: a grid step's queries and up-projections are its heads'
    q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(dtype).transpose(
        1, 0, 2)                                          # [H, T, qk]
    w_k = w_kvb[..., :nope].astype(dtype).transpose(1, 2, 0)   # [H, nope, c]
    w_v = w_kvb[..., nope:].astype(dtype).transpose(1, 2, 0)   # [H, v, c]
    pages = table.shape[0]
    table = jnp.pad(table, (0, -(-pages // per) * per - pages))
    kernel = functools.partial(_prefill_kernel, per=per, kv_lora=kv_lora,
                               page=page_size, scale=scale)

    def heads_of(*rest):
        return pl.BlockSpec((group,) + rest, lambda h, *_: (h, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads // group,),
            in_specs=[heads_of(tokens, q.shape[-1]),
                      heads_of(nope, kv_lora), heads_of(v_dim, kv_lora),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=heads_of(tokens, v_dim),
            scratch_shapes=[
                pltpu.VMEM((2, width, block), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((group, tokens, _LANES), jnp.float32),
                pltpu.VMEM((group, tokens, _LANES), jnp.float32),
                pltpu.VMEM((group, tokens, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, tokens, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='latent_prefill',
    )(meta, table, q, w_k, w_v, pool)
    return out.transpose(1, 0, 2)
