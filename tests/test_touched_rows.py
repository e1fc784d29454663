"""Touched rows on the packed wire (data/packed.py) and the gradient
reduction that uses them (ops/pallas_ragged.py ``_rows_table_grad``): a
training batch names the embedding rows its slots touch, ONE ascending set
a table for all of its data shards; every shard sums its slots into that
row space, a data-parallel mesh adds the shards' sums (an all-reduce of
the set's rows where the dense form all-reduces the tables), and one
scatter, unique and sorted, writes them into zeros.

CPU, up to four of the eight virtual devices: what the packer emits, that
the train step's table gradients are the dense form's on one shard and on
several, which collectives and scatters the step holds, and that a stream
packed without ``table_rows`` (eval, predict) sees none of it."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.data.reader import (Batch, EstimatorAction,
                                      PathContextReader, context_valid_mask)
from code2vec_tpu.models.backends import create_backend
from code2vec_tpu.ops import pallas_ragged
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.training.trainer import Trainer
from code2vec_tpu.vocab import SizeOnlyVocabs

from tests.test_packed import random_plane_batch
from tests.test_reader import small_setup, _write_train  # noqa: F401
from tests.test_sharding import _config

TOKENS, PATHS, TARGETS = 40, 12, 24
BATCH, CONTEXTS = 16, 8


def table_rows(config):
    return packed_lib.embedding_table_rows(
        SizeOnlyVocabs(TOKENS, PATHS, TARGETS), config.PARAM_ROW_ALIGNMENT)


def make_trainer(data, model, **overrides):
    """A float32 trainer on ``data`` x ``model`` of the virtual devices,
    dropout off and float32 moments: after one step from zero moments
    ``mu`` is 0.1 x the gradient the step handed the optimizer."""
    config = _config(
        data, model, DROPOUT_KEEP_RATE=1.0, ADAM_MU_DTYPE='float32',
        ADAM_NU_DTYPE='float32',
        MESH_DEVICE_INDICES=','.join(map(str, range(data * model))),
        **overrides)
    return Trainer(config, create_backend(
        config, SizeOnlyVocabs(TOKENS, PATHS, TARGETS)))


def packers(trainer, minimum=8):
    """(packer that names rows, packer that does not) for ``trainer``."""
    shards = trainer.mesh.shape[mesh_lib.DATA_AXIS]
    return (packed_lib.StickyPacker(0, 0, data_shards=shards,
                                    minimum=minimum,
                                    table_rows=table_rows(trainer.config)),
            packed_lib.StickyPacker(0, 0, data_shards=shards,
                                    minimum=minimum))


def plane_batch(rng, paths=PATHS):
    """A random batch with every structural corner (test_packed.py), its
    path rows drawn from the first ``paths`` of the table."""
    batch = random_plane_batch(rng, BATCH, CONTEXTS, pad_row_rate=0.1)
    return batch._replace(path=np.minimum(batch.path, paths - 1))


def corner_batch(shards, fill_paths):
    """One batch with every corner the issues list: rows that every shard
    names (all draw their tokens from the same few), rows that one shard
    alone names (the first shard's paths past 3), an empty method, an
    interior all-PAD context, and a step whose methods name exactly
    ``fill_paths`` distinct path rows, PAD among them."""
    rng = np.random.default_rng(11)
    source = rng.integers(1, 9, (BATCH, CONTEXTS)).astype(np.int32)
    target = rng.integers(1, 9, (BATCH, CONTEXTS)).astype(np.int32)
    path = rng.integers(1, 4, (BATCH, CONTEXTS)).astype(np.int32)
    per_shard = BATCH // shards
    # first shard: paths 1..fill_paths-1, each at least once (+ PAD = fill)
    wanted = np.arange(1, fill_paths, dtype=np.int32)
    path[:per_shard].reshape(-1)[:wanted.size] = wanted
    path[:per_shard].reshape(-1)[wanted.size:] = 1
    # an interior all-PAD context in the first shard, an empty method and
    # a short one in the last
    source[1, 3] = target[1, 3] = path[1, 3] = 0
    source[-1] = target[-1] = path[-1] = 0
    source[-2, 5:] = target[-2, 5:] = path[-2, 5:] = 0
    weight = np.ones((BATCH,), np.float32)
    weight[-1] = 0.0
    label = rng.integers(1, TARGETS, (BATCH,)).astype(np.int32)
    label[-1] = 0
    mask = context_valid_mask(source, path, target, 0, 0)
    return Batch(source=source, path=path, target=target, mask=mask,
                 label=label, weight=weight)


# ------------------------------------------------------------------ packer
@pytest.fixture(params=['pack_batch', 'pack_ragged'])
def packed_pair(request):
    """(with rows, without) of the same random batch through either of
    the packer's two entries (reader / v1 cache, and v2 cache)."""
    rng = np.random.default_rng(3)
    batch = plane_batch(rng)
    tables = (packed_lib.table_rows(TOKENS, 128),
              packed_lib.table_rows(PATHS, 128))
    out = []
    for rows in (tables, None):
        packer = packed_lib.StickyPacker(0, 0, data_shards=4, minimum=8,
                                         table_rows=rows)
        if request.param == 'pack_batch':
            out.append(packer.pack_batch(batch))
        else:
            ctx_rows, count = packed_lib.ragged_from_planes(
                batch.source, batch.path, batch.target, batch.mask)
            out.append(packer.pack_ragged(ctx_rows, count, batch.label,
                                          batch.weight))
    return out[0], out[1], tables


def test_rows_ascending_unique_and_padded_past_the_table(packed_pair):
    packed, _plain, tables = packed_pair
    for rows, in_table, columns in ((packed.tok_rows, tables[0], (0, 2)),
                                    (packed.path_rows, tables[1], (1,))):
        # one set a step, shipped in four equal runs
        assert rows.dtype == np.int32 and rows.shape[0] == 4
        whole = rows.reshape(-1)
        assert (np.diff(whole.astype(np.int64)) > 0).all()
        own = whole[whole < in_table]
        np.testing.assert_array_equal(
            own, np.union1d(packed.ctx[..., columns].ravel(), [0]))
        # past the step's own rows: rows_in_table + k, k = 0, 1, ...
        np.testing.assert_array_equal(
            whole[own.size:], in_table + np.arange(whole.size - own.size))


def test_pad_row_is_among_the_steps_rows(packed_pair):
    packed, _plain, _tables = packed_pair
    assert packed.tok_rows[0, 0] == 0 and packed.path_rows[0, 0] == 0
    # and in the set once: a later run does not begin with it again
    assert (packed.tok_rows[1:] > 0).all()
    assert (packed.path_rows[1:] > 0).all()


def test_inv_finds_every_slots_row(packed_pair):
    packed, _plain, _tables = packed_pair
    tok, path = packed.tok_rows.reshape(-1), packed.path_rows.reshape(-1)
    np.testing.assert_array_equal(tok[packed.inv[..., 0]],
                                  packed.ctx[..., 0])
    np.testing.assert_array_equal(path[packed.inv[..., 1]],
                                  packed.ctx[..., 1])
    np.testing.assert_array_equal(tok[packed.inv[..., 2]],
                                  packed.ctx[..., 2])


def test_rows_follow_the_four_wire_arrays(packed_pair):
    packed, plain, _tables = packed_pair
    arrays = packed.device_arrays()
    assert len(arrays) == 7 and len(plain.device_arrays()) == 4
    for with_rows, without in zip(arrays, plain.device_arrays()):
        np.testing.assert_array_equal(with_rows, without)
    assert arrays[4] is packed.tok_rows and arrays[5] is packed.path_rows
    assert arrays[6] is packed.inv


def test_row_capacities_are_sticky_and_never_shrink():
    rng = np.random.default_rng(5)
    packer = packed_lib.StickyPacker(
        0, 0, data_shards=2, minimum=4,
        table_rows=(packed_lib.table_rows(TOKENS, 128),
                    packed_lib.table_rows(PATHS, 128)))
    wide = plane_batch(rng)
    narrow = wide._replace(source=np.minimum(wide.source, 2),
                           target=np.minimum(wide.target, 2),
                           path=np.minimum(wide.path, 1))
    seen = []
    for batch in (narrow, wide, narrow, wide):
        packed = packer.pack_batch(batch)
        assert packed.tok_rows.shape[0] == packed.path_rows.shape[0] == 2
        seen.append((packed.tok_rows.size, packed.path_rows.size))
        assert seen[-1] == (packer.tok_capacity, packer.path_capacity)
    assert seen[1][0] > seen[0][0] and seen[1][1] > seen[0][1]
    assert seen[2] == seen[1] == seen[3]


@pytest.mark.parametrize('shards', [1, 3, 4])
def test_row_capacities_are_multiples_of_the_shards(shards):
    # the set ships in equal runs over ``data``, whatever the bucket
    rng = np.random.default_rng(5)
    batch = random_plane_batch(rng, 12, CONTEXTS, pad_row_rate=0.1)
    packer = packed_lib.StickyPacker(0, 0, data_shards=shards, minimum=1,
                                     table_rows=(128, 128))
    packed = packer.pack_batch(batch)
    for rows, capacity in ((packed.tok_rows, packer.tok_capacity),
                           (packed.path_rows, packer.path_capacity)):
        assert rows.shape == (shards, capacity // shards)
        assert capacity % shards == 0
        assert (np.diff(rows.reshape(-1).astype(np.int64)) > 0).all()


def test_row_capacity_leaves_head_room_and_holds_what_fits():
    assert packed_lib.row_capacity(5, current=8) == 8
    assert packed_lib.row_capacity(8, current=8) == 8
    assert packed_lib.row_capacity(9, current=8, minimum=4) == 12
    # the benchmark's shards name 30.3-32.5K token and 16.4-17.6K path
    # rows in a first batch: one capacity each, whatever the seed
    for distinct in (30300, 30959, 32500):
        assert packed_lib.row_capacity(distinct, current=64) == 36864
    for distinct in (16400, 16716, 17600):
        assert packed_lib.row_capacity(distinct, current=64) == 20480
    assert packed_lib.row_capacity(33000, current=36864) == 36864
    # the four shards of its data=4 step name 95.2K and 52.1K together:
    # the token set lies by a bucket's edge (94,663), so a first batch
    # gives it one of two capacities, and either holds every later batch
    for distinct, capacity in ((93000, 106496), (94663, 106496),
                               (94664, 114688), (95200, 114688),
                               (97500, 114688)):
        assert packed_lib.row_capacity(distinct, current=64) == capacity
    assert packed_lib.row_capacity(99000, current=106496) == 106496
    for distinct in (51000, 52100, 53500):
        assert packed_lib.row_capacity(distinct, current=64) == 61440
    for distinct in (100, 16385, 30959, 81920):
        capacity = packed_lib.row_capacity(distinct, current=64)
        assert distinct * 9 // 8 <= capacity <= distinct * 5 // 4 + 64


@pytest.mark.parametrize('table_rows_given', [True, False])
def test_one_data_shard_ships_the_plain_pack_and_its_rows_where_asked(
        table_rows_given):
    rng = np.random.default_rng(7)
    batch = plane_batch(rng)
    packer = packed_lib.StickyPacker(
        0, 0, data_shards=1, minimum=8,
        table_rows=(128, 128) if table_rows_given else None)
    packed = packer.pack_batch(batch)
    want = packed_lib.pack_batch(batch, 0, 0, data_shards=1,
                                 capacity_minimum=8)
    arrays = packed.device_arrays()
    for got, ref in zip(arrays, want.device_arrays()):
        np.testing.assert_array_equal(got, ref)
    if not table_rows_given:
        assert packed.inv is None and packed.tok_rows is None
        assert len(arrays) == 4
        return
    assert len(arrays) == 7
    assert packed.tok_rows.shape == (1, packer.tok_capacity)
    assert packed.path_rows.shape == (1, packer.path_capacity)
    assert packed.inv.shape == packed.ctx.shape
    np.testing.assert_array_equal(
        packed.tok_rows[0][packed.inv[0][:, (0, 2)]],
        packed.ctx[0][:, (0, 2)])
    np.testing.assert_array_equal(packed.path_rows[0][packed.inv[0, :, 1]],
                                  packed.ctx[0, :, 1])


@pytest.mark.parametrize('shards,arrays_past_the_limit', [(1, 4), (2, 7)])
def test_one_shard_stops_naming_rows_past_the_measured_capacity(
        monkeypatch, shards, arrays_past_the_limit):
    # PERF.md section 6 (PR 34): past 2**17 slots a shard the dense
    # scatter-adds win on one chip; across chips the rows also replace the
    # tables' all-reduce and stay
    monkeypatch.setattr(packed_lib, 'ONE_SHARD_ROWS_MAX_CAPACITY', 48)

    def batch_of(contexts):
        """Every method with ``contexts`` contexts: 16 x 2 = 32 slots fit
        under the limit on one shard and on two, 16 x 8 = 128 do not."""
        ids = np.where(np.arange(CONTEXTS) < contexts,
                       1 + np.arange(BATCH * CONTEXTS).reshape(
                           BATCH, CONTEXTS) % 7, 0).astype(np.int32)
        return Batch(source=ids, path=ids, target=ids,
                     mask=context_valid_mask(ids, ids, ids, 0, 0),
                     label=np.ones((BATCH,), np.int32),
                     weight=np.ones((BATCH,), np.float32))

    packer = packed_lib.StickyPacker(0, 0, data_shards=shards, minimum=8,
                                     table_rows=(128, 128))
    seen = [len(packer.pack_batch(batch_of(contexts)).device_arrays())
            for contexts in (2, 8, 2)]
    assert packer.capacity == BATCH * CONTEXTS // shards > 48
    assert seen == [7, arrays_past_the_limit, arrays_past_the_limit]


def test_training_reader_names_rows_and_eval_reader_does_not(
        small_setup):  # noqa: F811
    config, vocabs, prefix = small_setup
    _write_train(prefix, ['lbl1 s1,p1,t1 s2,p2,t1', 'lbl2 s2,p2,t1'])
    with open(str(prefix) + '.test.c2v', 'w') as f:
        f.write('lbl1 s1,p1,t1\nlbl2 s2,p2,t1\n')
    config.TEST_DATA_PATH = str(prefix) + '.test.c2v'
    train = PathContextReader(vocabs, config, EstimatorAction.Train,
                              data_shards=2)
    packed = next(iter(train.iter_epoch(shuffle=False,
                                        wire_format='packed')))
    assert len(packed.device_arrays()) == 7
    token_rows, path_rows = packed_lib.embedding_table_rows(
        vocabs, config.PARAM_ROW_ALIGNMENT)
    assert packed.tok_rows.max() >= token_rows > packed.ctx[..., 0].max()
    assert packed.path_rows.max() >= path_rows > packed.ctx[..., 1].max()
    for action, shards, arrays in ((EstimatorAction.Evaluate, 2, 4),
                                   (EstimatorAction.Evaluate, 1, 4),
                                   (EstimatorAction.Train, 1, 7)):
        reader = PathContextReader(vocabs, config, action,
                                   data_shards=shards)
        packed = next(iter(reader.iter_epoch(shuffle=False,
                                             wire_format='packed')))
        assert len(packed.device_arrays()) == arrays, (action, shards)


# -------------------------------------------------------- gradient parity
def table_grads(state):
    """(token, path) gradient the one step taken handed Adam: mu / 0.1."""
    mu = state.opt_state[0].mu
    return (np.asarray(mu.token_embedding) / 0.1,
            np.asarray(mu.path_embedding) / 0.1)


def assert_corner_step_equals_the_dense_form(trainer):
    """One real train step on ``corner_batch`` from a packer that names
    rows and from one that does not: the same loss, and the same token and
    path gradients through ``mu``, to 1e-6."""
    data = trainer.mesh.shape[mesh_lib.DATA_AXIS]
    with_rows, plain = packers(trainer, minimum=4)
    rng = np.random.default_rng(13)
    # the first batch sets the row capacities; the second's first shard
    # then names exactly as many path rows as there is room for
    first = with_rows.pack_batch(plane_batch(rng, paths=9))
    assert first.path_rows.size == PATHS
    corner = corner_batch(data, fill_paths=PATHS)
    packed = with_rows.pack_batch(corner)
    assert packed.path_rows.shape == first.path_rows.shape == (
        data, PATHS // data)
    tokens_in_table, paths_in_table = table_rows(trainer.config)
    assert (packed.path_rows < paths_in_table).all()           # U, exactly
    assert (packed.tok_rows >= tokens_in_table).any()          # and padding
    named = [set(packed.ctx[shard, :, 1]) for shard in range(data)]
    if data > 1:
        assert len(set.intersection(*named)) > 2        # rows of every shard
        assert named[0] - set.union(*named[1:])         # rows of one alone
    assert packed.count[-1] == 0 and packed.count[1] == CONTEXTS

    state = trainer.init_state(seed=0)
    got_state, got_loss = trainer.train_step(state, packed)
    want_state, want_loss = trainer.train_step(
        trainer.init_state(seed=0), plain.pack_batch(corner))
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-6)
    for got, want in zip(table_grads(got_state), table_grads(want_state)):
        assert got.dtype == np.float32 and np.abs(want).max() > 1e-3
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize('data,model,opt_sharding', [
    (4, 1, 'mirror'), (2, 2, 'mirror'), (4, 1, 'zero'),
    (1, 1, 'mirror'), (1, 2, 'mirror')])
def test_table_gradients_equal_the_dense_form(data, model, opt_sharding):
    assert_corner_step_equals_the_dense_form(make_trainer(
        data, model, OPTIMIZER_STATE_SHARDING=opt_sharding))


def test_pallas_pair_on_one_shard_reduces_over_the_rows(pallas_interpret):
    # the kernels end where the slots' cotangents do: the scatters behind
    # them are _bwd_compute's tail, the jnp pair's
    assert_corner_step_equals_the_dense_form(make_trainer(
        1, 1, RAGGED_TRAIN_KERNEL=True))


@pytest.mark.parametrize('data', [4, 1])
def test_eight_steps_end_at_the_dense_forms_loss(data):
    trainer = make_trainer(data, 1)
    with_rows, plain = packers(trainer)
    rng = np.random.default_rng(17)
    batches = [plane_batch(rng) for _ in range(8)]
    losses = []
    for packer in (with_rows, plain):
        state = trainer.init_state(seed=0)
        for batch in batches:
            state, loss = trainer.train_step(state,
                                             packer.pack_batch(batch))
        losses.append(float(loss))
    assert abs(losses[0] - losses[1]) < 1e-5, losses


# ------------------------------------------------------ the compiled step
COLLECTIVE = re.compile(
    r'= (.+?) (all-reduce|all-gather|reduce-scatter|all-to-all|'
    r'collective-permute)(?:-start)?\(')


def collectives(text):
    """(kind, result shapes) of every collective in a compiled text."""
    return [(m.group(2), m.group(1)) for m in
            (COLLECTIVE.search(line) for line in text.splitlines()) if m]


def lowered(trainer, packed):
    """The packed train step lowered for ``packed``'s shapes."""
    arrays = mesh_lib.shard_batch(packed.device_arrays(), trainer.mesh)
    return trainer._train_step_packed.lower(trainer.init_state(seed=0),
                                            arrays)


def compiled_text(trainer, packed):
    return lowered(trainer, packed).compile().as_text()


def narrow_batch(seed):
    """A batch whose step names fewer rows than either table holds, so
    that a set's row count cannot be mistaken for a table's."""
    batch = plane_batch(np.random.default_rng(seed), paths=8)
    return batch._replace(source=np.minimum(batch.source, 19),
                          target=np.minimum(batch.target, 19))


def test_no_collective_of_the_step_carries_a_table():
    # tables of their own size, so a row count cannot be mistaken
    trainer = make_trainer(4, 1, PARAM_ROW_ALIGNMENT=8,
                           MAX_TOKEN_VOCAB_SIZE=TOKENS)
    with_rows, plain = packers(trainer, minimum=4)
    batch = narrow_batch(19)
    tokens_in_table, paths_in_table = table_rows(trainer.config)
    assert (tokens_in_table, paths_in_table) == (40, 16)
    carries_table = re.compile(r'\[(%d|%d),8\]' % (tokens_in_table,
                                                  paths_in_table))
    dense = collectives(compiled_text(trainer, plain.pack_batch(batch)))
    assert {kind for kind, shape in dense
            if carries_table.search(shape)} == {'all-reduce'}, dense
    packed = with_rows.pack_batch(batch)
    sets = (packed.tok_rows.size, packed.path_rows.size)
    assert sets == (24, 12)
    by_rows = collectives(compiled_text(trainer, packed))
    assert by_rows and not [
        (kind, shape) for kind, shape in by_rows
        if carries_table.search(shape)], by_rows
    # the shards' row sums are ADDED across ``data``, (U_step, d) a table:
    # an all-reduce, or a reduce-scatter with the all-gather that follows
    for rows in sets:
        summed = [kind for kind, shape in by_rows
                  if 'f32[%d,8]' % rows in shape
                  and kind in ('all-reduce', 'all-gather')]
        assert summed, (rows, by_rows)
        assert 'all-reduce' in summed or 'f32[%d,8]' % (rows // 4) in ''.join(
            shape for kind, shape in by_rows if kind == 'reduce-scatter')
    # and no shard's buffer is gathered: D x U float32 rows
    assert not [shape for kind, shape in by_rows if kind == 'all-gather'
                and re.search(r'f32\[(4,(%d|%d)|%d|%d),8\]'
                              % (*sets, 4 * sets[0], 4 * sets[1]), shape)
                ], by_rows


def test_a_packer_without_table_rows_lowers_the_one_device_step_as_before():
    # eval, predict, serving and bulk build their packer without
    # ``table_rows``: four arrays, the step program of the plain pack
    trainer = make_trainer(1, 1)
    batch = plane_batch(np.random.default_rng(23))
    packed = packed_lib.StickyPacker(
        0, 0, data_shards=1, minimum=8).pack_batch(batch)
    assert len(packed.device_arrays()) == 4
    assert lowered(trainer, packed).as_text() == lowered(
        trainer, packed_lib.pack_batch(
            batch, 0, 0, data_shards=1, capacity_minimum=8)).as_text()
    new_state, loss = trainer.train_step(trainer.init_state(seed=0), packed)
    assert np.isfinite(float(loss)) and int(new_state.step) == 1


SCATTER = re.compile(
    r'(%\w+) = "stablehlo\.scatter"\((%\w+), [^\n]*?'
    r'unique_indices = (true|false)\}>.*?'
    r'\}\) : \(tensor<[0-9x]+xf32>, tensor<[0-9x]+xi32>, '
    r'tensor<([0-9x]+)xf32>\) -> tensor<([0-9x]+)xf32>', re.S)


def scatters_into(text, tables):
    """(unique_indices, update shape, destination known to be zeros) of
    every scatter of a lowered text whose result is one of ``tables``
    (shapes as '40x8'), in program order."""
    out = []
    for _name, operand, unique, updates, result in SCATTER.findall(text):
        if result not in tables:
            continue
        made = re.search(r'%s = stablehlo\.broadcast_in_dim (%%\w+), dims = '
                         r'\[\]' % re.escape(operand), text)
        zeros = bool(made and re.search(
            r'%s = stablehlo\.constant dense<0\.0+e\+00>'
            % re.escape(made.group(1)), text))
        out.append((unique, updates, zeros))
    return out


def small_tables_trainer(data):
    # tables of their own size, so a row count cannot be mistaken
    trainer = make_trainer(data, 1, PARAM_ROW_ALIGNMENT=8,
                           MAX_TOKEN_VOCAB_SIZE=TOKENS)
    tokens_in_table, paths_in_table = table_rows(trainer.config)
    assert (tokens_in_table, paths_in_table) == (40, 16)
    return trainer, ('%dx8' % tokens_in_table, '%dx8' % paths_in_table)


def test_one_device_step_scatters_unique_rows_into_the_tables():
    trainer, tables = small_tables_trainer(1)
    with_rows, plain = packers(trainer)
    batch = plane_batch(np.random.default_rng(23))

    def into_tables(wire):
        """``unique_indices`` of every scatter whose result is a table's
        gradient, in program order."""
        text = lowered(trainer, wire).as_text()
        assert not re.search(r'stablehlo\.(all_|reduce_scatter|collective_)'
                             r'|@Sharding', text)
        return [unique for unique, _updates, _zeros
                in scatters_into(text, tables)]

    # the dense form: a scatter-add of every slot, then the PAD row's term
    assert into_tables(plain.pack_batch(batch)) == [
        'false', 'true', 'false', 'true']
    # by rows: the duplicates meet in the compact buffers, and nothing
    # reaches a table but rows the scatter is told are unique
    packed = with_rows.pack_batch(batch)
    assert len(packed.device_arrays()) == 7
    assert into_tables(packed) == ['true'] * 4
    assert not collectives(compiled_text(trainer, packed))


@pytest.mark.parametrize('data', [4, 2, 1])
def test_step_scatters_one_row_set_a_table_into_zeros(data):
    # whatever the number of shards: per table ONE scatter of the step's
    # set, unique and sorted, into a destination the compiler is shown to
    # be zeros, then the PAD row's one-row term; no shard's sums meet a
    # table that already holds another's
    trainer, tables = small_tables_trainer(data)
    with_rows, _plain = packers(trainer, minimum=4)
    packed = with_rows.pack_batch(narrow_batch(29))
    sets = (packed.tok_rows.size, packed.path_rows.size)
    assert sets == (24, 12)
    found = scatters_into(lowered(trainer, packed).as_text(), tables)
    assert found == [('true', '%dx8' % sets[0], True), ('true', '8', False),
                     ('true', '%dx8' % sets[1], True), ('true', '8', False)]


def parent_rows_table_grad(table_rows, rows, inv, cot, mesh):
    """``_rows_table_grad`` as PR 34 left it (its one-device path, no
    mesh): the reference the one-shard step must still compile to."""
    assert mesh is None
    shards, capacity = rows.shape
    dim = cot.shape[-1]
    compact = jax.vmap(
        lambda i, c: jnp.zeros((capacity, dim), cot.dtype).at[i].add(c))(
            inv, cot)
    grad = jnp.zeros((table_rows, dim), cot.dtype)
    for shard in range(shards):
        grad = grad.at[rows[shard]].add(
            compact[shard], unique_indices=True, indices_are_sorted=True,
            mode='drop')
    return grad


def test_one_shard_step_compiles_to_the_program_it_was(monkeypatch):
    # one shard is the degenerate case of the same code: the run of one
    # and the sum over one shard leave nothing in the compiled program
    trainer, _tables = small_tables_trainer(1)
    with_rows, _plain = packers(trainer)
    packed = with_rows.pack_batch(plane_batch(np.random.default_rng(23)))
    assert packed.tok_rows.shape[0] == packed.path_rows.shape[0] == 1

    def program():
        """The compiled step's instructions, names and source lines
        apart, as a sorted list."""
        text = re.sub(r', metadata=\{[^}]*\}', '',
                      compiled_text(trainer, packed))
        text = re.sub(r'%[\w.-]+',
                      lambda m: re.sub(r'[._]?\d+', '', m.group(0)), text)
        return sorted(line.strip() for line in text.splitlines()
                      if ' = ' in line)

    ours = program()
    monkeypatch.setattr(pallas_ragged, '_rows_table_grad',
                        parent_rows_table_grad)
    jax.clear_caches()
    trainer, _tables = small_tables_trainer(1)
    theirs = program()
    assert [line for line in ours if 'scatter(' in line]
    assert ours == theirs
    assert not collectives('\n'.join(ours))


@pytest.mark.parametrize('shards', [1, 2, 4])
def test_rows_table_grad_is_the_dense_scatter_add(shards):
    # the function alone, no mesh: every shard's slots, rows shared
    # between shards and padding past the table included
    rng = np.random.default_rng(31)
    in_table, slots, dim = 24, 40, 8
    ids = rng.integers(0, 12, (shards, slots)).astype(np.int32)
    cot = rng.standard_normal((shards, slots, dim)).astype(np.float32)
    lut = np.empty((in_table,), np.int32)
    own, inv = packed_lib.distinct_rows(ids.ravel(), 0, lut)
    rows = packed_lib.pad_rows(own, 16, in_table, shards)
    got = pallas_ragged._rows_table_grad(
        in_table, jnp.asarray(rows), jnp.asarray(inv.reshape(shards, slots)),
        jnp.asarray(cot), None)
    want = np.zeros((in_table, dim), np.float32)
    np.add.at(want, ids.ravel(), cot.reshape(-1, dim))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_capacity_tracker_counts_a_new_row_capacity_once():
    from code2vec_tpu.telemetry.jit_tracker import CapacityTracker
    lines = []
    tracker = CapacityTracker(log=lines.append)
    tracker.observe(64, 0, rows=(16, 8))
    tracker.observe(64, 1, rows=(16, 8))
    tracker.observe(64, 2, rows=(24, 8))
    assert len(lines) == 2
    assert 'touched rows 24 token, 8 path' in lines[1]


def test_packer_reports_the_row_gauges(packed_pair):
    from code2vec_tpu.telemetry import core
    packed, _plain, tables = packed_pair
    was = core.enabled()
    core.enable()
    try:
        rng = np.random.default_rng(3)
        packer = packed_lib.StickyPacker(0, 0, data_shards=4, minimum=8,
                                         table_rows=tables)
        packed = packer.pack_batch(plane_batch(rng))
        # the step's distinct rows, not the sum of its shards' own
        distinct = (np.union1d(packed.ctx[..., (0, 2)].ravel(), [0]).size
                    + np.union1d(packed.ctx[..., 1].ravel(), [0]).size)
        assert distinct == int((packed.tok_rows < tables[0]).sum()
                               + (packed.path_rows < tables[1]).sum())
        reg = core.registry()
        assert reg.gauge('input/unique_row_share').value == pytest.approx(
            distinct / (3 * int(packed.count.sum())))
        assert reg.gauge('input/row_capacity_fill').value == pytest.approx(
            distinct / (packed.tok_rows.size + packed.path_rows.size))
    finally:
        if not was:
            core.disable()
