"""Stage 2 of the block-sparse layers as the Pallas kernel
(``ops/pallas_sparse.py``) against its ``jax.numpy`` form
(``ops/sparse_attention.py::attend_blocks``), both behind the same stage 1,
the kernel in Pallas's TPU interpreter on the CPU: decode rows with idle
slots among them, chunks whose tiles cross block and page boundaries, a
query at a block's first position, far lists shorter than their slots, a
tile with no live query.  Float32 to 1e-5; bfloat16 within the ``jax.numpy``
form's own rounding.  A tile or query that is not live comes back zero and
copies nothing.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.ops import pallas_sparse, sparse_attention as sa

#: blocks of 4, a page of two blocks, a window of 8, top-6: 4 far slots
GEO = sa.SparseGeometry(kernel_size=4, kernel_stride=2, block_size=4,
                        window_size=8, topk=6, init_blocks=1, dense_len=8)
PAGE, SEQ_PAGES, POOL_PAGES = 8, 12, 40
KV, HEADS, D, TILE = 2, 4, 8, 4
KERNEL = functools.partial(pallas_sparse.attend_planned, interpret=True)


def pool_and_tables(rows: int, seed: int):
    """(pool, tables [rows, pages], the stride rows page for page [pool
    pages, strides a page, kv, d])."""
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(2, POOL_PAGES * PAGE // GEO.block_size, KV,
                            GEO.block_size, D)) * 1.75
    tables = np.stack([rng.permutation(POOL_PAGES)[:SEQ_PAGES]
                       for _ in range(rows)]).astype(np.int32)
    pooled = rng.normal(size=(POOL_PAGES, PAGE // GEO.kernel_stride, KV, D))
    return pool, tables, pooled


def run_both(kind, positions, live, seed, dtype):
    """(jnp outputs, kernel outputs, float32 outputs of the same
    operands, jnp counts, kernel counts)."""
    positions = jnp.asarray(positions, jnp.int32)
    live = jnp.asarray(live, jnp.int32)
    rows = positions.shape[0] if kind == 'rows' else 1
    pool, tables, pooled = pool_and_tables(rows, seed)
    q = np.random.default_rng(seed + 1).normal(
        size=(positions.shape[0], HEADS, D)) * 1.75
    outs = []
    for kernel, as_dtype in ((None, dtype), (KERNEL, dtype),
                             (None, jnp.float32)):
        # the float32 reference multiplies the operands as rounded
        args = [jnp.asarray(x, dtype).astype(as_dtype)
                for x in (q, pooled, pool)]
        if kind == 'rows':
            outs.append(sa.sparse_attention_rows(
                args[0], positions, live, args[1], jnp.asarray(tables),
                args[2], GEO, PAGE, kernel=kernel))
        else:
            means = args[1][tables[0]].reshape(-1, KV, D)
            outs.append(sa.sparse_attention_chunk(
                args[0], positions, live, means, jnp.asarray(tables[0]),
                args[2], GEO, PAGE, tile=TILE, kernel=kernel))
    (plain, counted), (kernel, k_counted), (exact, _) = outs
    return (np.asarray(plain), np.asarray(kernel), np.asarray(exact),
            np.asarray(counted), np.asarray(k_counted))


CASES = {
    # rows of sequences 95, 40, 17 and 63 long, two idle slots among them
    'decode-rows-idle-slots': ('rows', [95, 0, 40, 17, 0, 63],
                               [1, 0, 1, 1, 0, 1]),
    # 11 queries from position 37: tiles of 4 cross blocks 9..11 and
    # pages 4..5, the last tile padded
    'chunk-across-blocks-and-pages': ('chunk', list(range(37, 48)),
                                      [1] * 11),
    # a chunk that begins at a block's (and a page's) first position
    'query-at-block-start': ('chunk', list(range(64, 72)), [1] * 8),
    # early queries: fewer blocks before the near range than far slots
    'far-lists-short': ('chunk', list(range(13, 21)), [1] * 8),
    # the middle tile holds no live query; a dead query in a live tile
    'tile-without-live-query': ('chunk', list(range(50, 62)),
                                [1, 1, 0, 1, 0, 0, 0, 0, 1, 1, 1, 0]),
}


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('case', list(CASES))
def test_the_kernel_attends_to_what_the_jnp_form_does(case, dtype):
    kind, positions, live = CASES[case]
    plain, kernel, exact, counted, k_counted = run_both(
        kind, positions, live, seed=len(case), dtype=dtype)
    alive = np.asarray(live) > 0
    if dtype == jnp.float32:
        np.testing.assert_allclose(kernel[alive], plain[alive], atol=1e-5,
                                   rtol=1e-5)
    else:
        # the kernel's rounding against float32 is the jnp form's, give or
        # take the online softmax's other maxima
        own = np.abs(plain[alive] - exact[alive]).max()
        assert 0 < own < 0.05
        assert np.abs(kernel[alive] - exact[alive]).max() <= 2 * own
    assert not np.any(kernel[~alive])
    # the same blocks chosen and visible; the kernel counts its queries
    np.testing.assert_array_equal(k_counted[:2], counted[:2])
    assert counted[2] == 0 and k_counted[2] == alive.sum()


def test_a_tile_without_live_query_copies_nothing():
    """The plan of a chunk whose second tile is dead, that tile's block ids
    made out of range: the interpreter refuses any copy from outside the
    pool, and none is made; the same ids in a live tile are refused."""
    positions = jnp.arange(50, 62, dtype=jnp.int32)
    live = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0, 1, 0, 1, 1], jnp.int32)
    pool, tables, pooled = pool_and_tables(1, seed=3)
    means = pooled[tables[0]].reshape(-1, KV, D)
    q = jnp.asarray(np.random.default_rng(4).normal(size=(12, HEADS, D)),
                    jnp.float32)
    pool = jnp.asarray(pool, jnp.float32)
    tiles = (q.reshape(3, TILE, HEADS, D), positions.reshape(3, TILE),
             live.reshape(3, TILE))
    plan = jax.vmap(lambda qt, pt, lt: sa.planned(
        qt, pt, lt, jnp.asarray(means, jnp.float32),
        jnp.asarray(tables[0]), GEO, PAGE)[0])(*tiles)
    outside = pool.shape[1] + 5

    def poisoned(tile):
        return plan._replace(near=plan.near.at[tile].set(outside),
                             far=plan.far.at[tile].set(outside))
    out = pallas_sparse.attend_planned(tiles[0], plan, pool, interpret=True)
    dead = pallas_sparse.attend_planned(tiles[0], poisoned(1), pool,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(dead), np.asarray(out))
    assert not np.any(np.asarray(out)[1])
    with pytest.raises(Exception):
        jax.block_until_ready(pallas_sparse.attend_planned(
            tiles[0], poisoned(0), pool, interpret=True))
