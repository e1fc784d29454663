"""The block-sparse layers' decode rows (``ops/sparse_attention.py::
sparse_attention_rows``): each live row chooses its blocks alone, its
stride rows scored only as far as the pieces of its page table its
position reaches, and a row that is not live does nothing.  Held against the one-sequence form the prefill
chunk runs (``sparse_attention`` / ``planned`` over every block the table
allows), row by row: the same outputs to float32 rounding, the same blocks
chosen and visible, and the same plan where many blocks tie at the
``topk``-th score.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.ops import sparse_attention as sa

#: blocks of 4, a page of two blocks, a window of 8, top-6, dense up to 8
GEO = sa.SparseGeometry(kernel_size=4, kernel_stride=2, block_size=4,
                        window_size=8, topk=6, init_blocks=1, dense_len=8)
PAGE, SEQ_PAGES, POOL_PAGES = 8, 12, 48
KV, HEADS, D = 2, 4, 8
PER_PAGE = PAGE // GEO.block_size
STRIDES_A_PAGE = PAGE // GEO.kernel_stride
LAST = SEQ_PAGES * PAGE - 1


def operands(rows: int, seed: int, seq_pages: int = SEQ_PAGES):
    """(q, pool, pooled, tables) in float32: a table of distinct pages a
    row, the stride rows held page for page."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(rows, HEADS, D)) * 1.75
    pool = rng.normal(size=(2, POOL_PAGES * PER_PAGE, KV, GEO.block_size,
                            D)) * 1.75
    pooled = rng.normal(size=(POOL_PAGES, STRIDES_A_PAGE, KV, D))
    tables = np.stack([rng.permutation(POOL_PAGES)[:seq_pages]
                       for _ in range(rows)]).astype(np.int32)
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, pool, pooled)) \
        + (jnp.asarray(tables),)


@jax.jit
def rows_form(q, positions, live, pooled, tables, pool):
    return sa.sparse_attention_rows(q, positions, live, pooled, tables,
                                    pool, GEO, PAGE)


def row_by_row(q, positions, live, pooled, tables, pool):
    """The one-sequence form over every block the table allows, a row at a
    time: (outputs of the live rows, (chosen, visible) summed)."""
    outs, counts = [], np.zeros(2, np.int64)
    for row in range(q.shape[0]):
        if not live[row]:
            outs.append(None)
            continue
        means = pooled[tables[row]].reshape(-1, KV, D)
        out, counted = sa.sparse_attention(
            q[row][None], positions[row][None], live[row][None], means,
            tables[row], pool, GEO, PAGE)
        outs.append(np.asarray(out[0]))
        counts += np.asarray(counted)
    return outs, counts


def own_prefix(at: int, seq_pages: int = SEQ_PAGES) -> int:
    """The stride rows a live row at ``at`` is scored over: the whole
    pieces of its table up to the one that holds its position."""
    piece = -(-seq_pages // sa.ROW_PIECES)
    pages = -(-(at // PAGE + 1) // piece) * piece
    return min(pages, seq_pages) * STRIDES_A_PAGE


def check_rows(positions, live, seed, seq_pages=SEQ_PAGES):
    positions = jnp.asarray(positions, jnp.int32)
    live_a = jnp.asarray(live, jnp.int32)
    q, pool, pooled, tables = operands(positions.shape[0], seed, seq_pages)
    out, counts = rows_form(q, positions, live_a, pooled, tables, pool)
    out, counts = np.asarray(out), np.asarray(counts)
    want, want_counts = row_by_row(q, positions, live_a, pooled, tables,
                                   pool)
    for row, expected in enumerate(want):
        if expected is None:
            assert not np.any(out[row])
        else:
            np.testing.assert_allclose(out[row], expected, rtol=1e-6,
                                       atol=1e-6)
    np.testing.assert_array_equal(counts[:2], want_counts)
    assert counts[2] == 0           # no kernel: the jax.numpy stage 2
    assert counts[3] == sum(own_prefix(int(at), seq_pages)
                            for at, alive in zip(positions, live) if alive)
    return counts


@pytest.mark.parametrize('positions,live,seq_pages', [
    # the first sparse position (visible length dense_len + 1), a row
    # mid-table and one at the table's last position, idle rows between
    ([GEO.dense_len, 0, 47, 0, LAST, 0], [1, 0, 1, 0, 1, 0], SEQ_PAGES),
    # every row live, one at each end of the table
    ([LAST, GEO.dense_len, 30, 63, 64, 71], [1] * 6, SEQ_PAGES),
    # a lone live row behind idle ones
    ([0, 0, 0, 0, 0, 58], [0, 0, 0, 0, 0, 1], SEQ_PAGES),
    # a table of 10 pages: pieces of 3, the last one partly past the table
    ([GEO.dense_len, 0, 35, 79], [1, 0, 1, 1], 10)],
    ids=['mixed-with-idle', 'all-live', 'one-live', 'table-in-part-pieces'])
def test_live_rows_match_the_one_sequence_form(positions, live, seq_pages):
    check_rows(positions, live, seed=sum(positions), seq_pages=seq_pages)


def test_every_position_of_the_table_matches():
    """Every sparse position of the table, six rows a call: each piece's
    edges, and the table's end, where the whole table is read."""
    at = np.arange(GEO.dense_len, LAST + 1)
    at = np.concatenate([at, np.full(-len(at) % 6, LAST)])
    for i, start in enumerate(range(0, len(at), 6)):
        check_rows(at[start:start + 6], [1] * 6, seed=i)


def test_a_row_is_scored_over_its_own_prefix_only():
    """The stride rows a lone live row is scored over, as the program
    counts them, position by position: whole pieces of its table, at
    least its own stride rows, no fewer for a longer row, and the whole
    table from the last piece's first position on."""
    q, pool, pooled, tables = operands(1, seed=3)
    positions = range(GEO.dense_len, LAST + 1)
    scored = [int(rows_form(q, jnp.asarray([at], jnp.int32),
                            jnp.ones(1, jnp.int32), pooled, tables,
                            pool)[1][3]) for at in positions]
    piece = SEQ_PAGES // sa.ROW_PIECES * STRIDES_A_PAGE
    whole = SEQ_PAGES * STRIDES_A_PAGE
    assert all(n % piece == 0 for n in scored)
    assert all(n >= at // GEO.kernel_stride + 1
               for n, at in zip(scored, positions))
    assert scored == sorted(scored) and scored[0] < whole
    last_piece = (SEQ_PAGES - SEQ_PAGES // sa.ROW_PIECES) * PAGE
    assert [at for at, n in zip(positions, scored) if n == whole] \
        == list(range(last_piece, LAST + 1))


def test_no_live_row_does_no_work():
    """Idle rows with positions and tables that would be out of range:
    nothing of them is read, they come back zero, and nothing is counted;
    beside a live row they change nothing of it."""
    q, pool, pooled, tables = operands(4, seed=5)
    poisoned = tables.at[1:].set(10 ** 6)
    positions = jnp.asarray([40, 10 ** 6, 10 ** 6, 10 ** 6], jnp.int32)
    none = jnp.zeros(4, jnp.int32)
    out, counts = rows_form(q, positions, none, pooled, poisoned, pool)
    assert not np.any(np.asarray(out))
    assert not np.any(np.asarray(counts))
    one = jnp.asarray([1, 0, 0, 0], jnp.int32)
    out, counts = rows_form(q, positions, one, pooled, poisoned, pool)
    clean, clean_counts = rows_form(q, positions.at[1:].set(0), one,
                                    pooled, tables, pool)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(clean_counts))
    assert not np.any(np.asarray(out)[1:])


def plans(q, positions, live, pooled, tables):
    """(the plan of the rows' form, the one-sequence form's plan over the
    whole table, row by row)."""
    taken = []

    def capture(q_rows, plan, pool):
        taken.append(plan)
        return jnp.zeros(q_rows.shape, jnp.float32)
    sa.sparse_attention_rows(q, positions, live, pooled, tables, None, GEO,
                             PAGE, kernel=capture)
    whole = jax.vmap(lambda qr, at, lr, table: sa.planned(
        qr[None], at[None], lr[None],
        pooled[table].reshape(-1, KV, D), table, GEO, PAGE)[0])(
            q, positions, live, tables)
    return taken[0], whole


@pytest.mark.parametrize('pattern', ['equal', 'three-values'])
def test_the_choice_is_the_whole_tables_on_ties(pattern):
    """Stride rows built so that many blocks tie at the ``topk``-th score
    (all equal: every pooled key has the same mass; three values in turn:
    runs of equal block scores): a row's plan, its stride rows scored as
    far as it reaches, is the plan of the whole table's choice, block for
    block, ties to the lower block."""
    q, _, pooled, tables = operands(6, seed=9)
    if pattern == 'equal':
        pooled = jnp.ones_like(pooled)
    else:
        pooled = jnp.asarray(np.resize(np.arange(3.0), pooled.shape[:2])[
            ..., None, None] * np.ones(pooled.shape), jnp.float32)
    positions = jnp.asarray([GEO.dense_len, 0, 35, 60, 0, LAST], jnp.int32)
    live = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.int32)
    got, want = plans(q, positions, live, pooled, tables)
    alive = np.asarray(live) > 0
    np.testing.assert_array_equal(np.asarray(got.live), alive)
    for field in ('near', 'near_mask', 'far_count'):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field))[alive],
            np.asarray(getattr(want, field))[alive], err_msg=field)
    held = np.arange(got.far.shape[-1]) < np.asarray(got.far_count)[..., None]
    np.testing.assert_array_equal(np.where(held, got.far, -1)[alive],
                                  np.where(held, want.far, -1)[alive])
    # the ties are there: more blocks score as the topk-th than are taken
    scores = np.asarray(sa.block_scores(
        q[3][None], positions[3][None],
        pooled[tables[3]].reshape(-1, KV, D), GEO))[0, 0]
    kth = np.sort(scores)[::-1][GEO.topk - 1]
    taken = np.asarray(sa.choose(jnp.asarray(scores), GEO.topk))
    assert (scores == kth).sum() > (taken & (scores == kth)).sum() > 0
    assert not np.any(np.asarray(got.far_count)[~alive])
