"""Touched rows on the packed wire (data/packed.py) and the gradient
reduction that uses them (ops/pallas_ragged.py ``_rows_table_grad``): on
a data-parallel mesh a training batch names, per shard, the embedding
rows its slots touch, and the step gathers those rows' sums where the
dense form all-reduces the tables.

CPU, four of the eight virtual devices: what the packer emits, that the
train step's table gradients are the dense form's, which collectives the
compiled step holds, and that one data shard sees none of it."""
import re

import jax
import numpy as np
import pytest

from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.data.reader import (Batch, EstimatorAction,
                                      PathContextReader, context_valid_mask)
from code2vec_tpu.models.backends import create_backend
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
    """One batch with every corner the issue lists: rows shared between
    shards (every shard draws from the same few), an empty method, an
    interior all-PAD context, and a first shard whose methods name
    exactly ``fill_paths`` distinct path rows, PAD among them."""
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
    for rows, in_table in ((packed.tok_rows, tables[0]),
                           (packed.path_rows, tables[1])):
        assert rows.dtype == np.int32 and rows.shape[0] == 4
        assert (np.diff(rows.astype(np.int64), axis=1) > 0).all()
        for shard in rows:
            own = shard[shard < in_table]
            # past a shard's own rows: rows_in_table + k, k = 0, 1, ...
            np.testing.assert_array_equal(
                shard[own.size:],
                in_table + np.arange(shard.size - own.size))


def test_pad_row_is_among_every_shards_rows(packed_pair):
    packed, _plain, _tables = packed_pair
    assert (packed.tok_rows[:, 0] == 0).all()
    assert (packed.path_rows[:, 0] == 0).all()


def test_inv_finds_every_slots_row(packed_pair):
    packed, _plain, _tables = packed_pair
    shard = np.arange(4)[:, None]
    np.testing.assert_array_equal(
        packed.tok_rows[shard, packed.inv[..., 0]], packed.ctx[..., 0])
    np.testing.assert_array_equal(
        packed.path_rows[shard, packed.inv[..., 1]], packed.ctx[..., 1])
    np.testing.assert_array_equal(
        packed.tok_rows[shard, packed.inv[..., 2]], packed.ctx[..., 2])


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
        seen.append((packed.tok_rows.shape[1], packed.path_rows.shape[1]))
        assert seen[-1] == (packer.tok_capacity, packer.path_capacity)
    assert seen[1][0] > seen[0][0] and seen[1][1] > seen[0][1]
    assert seen[2] == seen[1] == seen[3]


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
    for distinct in (100, 16385, 30959, 81920):
        capacity = packed_lib.row_capacity(distinct, current=64)
        assert distinct * 9 // 8 <= capacity <= distinct * 5 // 4 + 64


@pytest.mark.parametrize('table_rows_given', [True, False])
def test_one_data_shard_ships_the_four_arrays_unchanged(table_rows_given):
    rng = np.random.default_rng(7)
    batch = plane_batch(rng)
    packer = packed_lib.StickyPacker(
        0, 0, data_shards=1, minimum=8,
        table_rows=(128, 128) if table_rows_given else None)
    packed = packer.pack_batch(batch)
    assert packed.inv is None and packed.tok_rows is None
    want = packed_lib.pack_batch(batch, 0, 0, data_shards=1,
                                 capacity_minimum=8)
    assert len(packed.device_arrays()) == 4
    for got, ref in zip(packed.device_arrays(), want.device_arrays()):
        np.testing.assert_array_equal(got, ref)


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
    for action, shards in ((EstimatorAction.Evaluate, 2),
                           (EstimatorAction.Train, 1)):
        reader = PathContextReader(vocabs, config, action,
                                   data_shards=shards)
        packed = next(iter(reader.iter_epoch(shuffle=False,
                                             wire_format='packed')))
        assert len(packed.device_arrays()) == 4


# -------------------------------------------------------- gradient parity
def table_grads(state):
    """(token, path) gradient the one step taken handed Adam: mu / 0.1."""
    mu = state.opt_state[0].mu
    return (np.asarray(mu.token_embedding) / 0.1,
            np.asarray(mu.path_embedding) / 0.1)


@pytest.mark.parametrize('data,model,opt_sharding', [
    (4, 1, 'mirror'), (2, 2, 'mirror'), (4, 1, 'zero')])
def test_table_gradients_equal_the_dense_form(data, model, opt_sharding):
    trainer = make_trainer(data, model,
                           OPTIMIZER_STATE_SHARDING=opt_sharding)
    with_rows, plain = packers(trainer, minimum=4)
    rng = np.random.default_rng(13)
    # the first batch sets the row capacities; the second's first shard
    # then names exactly as many path rows as there is room for
    first = with_rows.pack_batch(plane_batch(rng, paths=9))
    assert first.path_rows.shape[1] == PATHS
    corner = corner_batch(data, fill_paths=PATHS)
    packed = with_rows.pack_batch(corner)
    assert packed.path_rows.shape == first.path_rows.shape
    paths_in_table = table_rows(trainer.config)[1]
    assert (packed.path_rows[0] < paths_in_table).all()        # U, exactly
    assert (packed.path_rows[1:] >= paths_in_table).any()
    shared = set(packed.tok_rows[0]) & set(packed.tok_rows[1])
    assert len(shared) > 2                                     # shared rows
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


def test_eight_steps_end_at_the_dense_forms_loss():
    trainer = make_trainer(4, 1)
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


def compiled_text(trainer, packed):
    arrays = mesh_lib.shard_batch(packed.device_arrays(), trainer.mesh)
    state = trainer.init_state(seed=0)
    return trainer._train_step_packed.lower(state, arrays).compile(
        ).as_text()


def test_no_collective_of_the_step_carries_a_table():
    # tables of their own size, so a row count cannot be mistaken
    trainer = make_trainer(4, 1, PARAM_ROW_ALIGNMENT=8,
                           MAX_TOKEN_VOCAB_SIZE=TOKENS)
    with_rows, plain = packers(trainer)
    batch = plane_batch(np.random.default_rng(19))
    tokens_in_table, paths_in_table = table_rows(trainer.config)
    assert (tokens_in_table, paths_in_table) == (40, 16)
    carries_table = re.compile(r'\[(%d|%d),8\]' % (tokens_in_table,
                                                  paths_in_table))
    dense = collectives(compiled_text(trainer, plain.pack_batch(batch)))
    assert {kind for kind, shape in dense
            if carries_table.search(shape)} == {'all-reduce'}, dense
    by_rows = collectives(compiled_text(trainer,
                                        with_rows.pack_batch(batch)))
    assert by_rows and not [
        (kind, shape) for kind, shape in by_rows
        if carries_table.search(shape)], by_rows
    gathered = [shape for kind, shape in by_rows if kind == 'all-gather']
    assert any(shape.startswith('f32[') for shape in gathered), by_rows


def test_one_device_step_takes_the_four_arrays_and_lowers_as_before():
    trainer = make_trainer(1, 1)
    batch = plane_batch(np.random.default_rng(23))
    packed = packed_lib.StickyPacker(
        0, 0, data_shards=1, minimum=8,
        table_rows=table_rows(trainer.config)).pack_batch(batch)
    assert len(packed.device_arrays()) == 4
    state = trainer.init_state(seed=0)

    def lowered(wire):
        arrays = mesh_lib.shard_batch(wire.device_arrays(), trainer.mesh)
        return trainer._train_step_packed.lower(state, arrays).as_text()

    text = lowered(packed)
    assert text == lowered(packed_lib.pack_batch(
        batch, 0, 0, data_shards=1, capacity_minimum=8))
    new_state, loss = trainer.train_step(state, packed)
    assert np.isfinite(float(loss)) and int(new_state.step) == 1


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
        distinct = int((packed.tok_rows < tables[0]).sum()
                       + (packed.path_rows < tables[1]).sum())
        reg = core.registry()
        assert reg.gauge('input/unique_row_share').value == pytest.approx(
            distinct / (3 * int(packed.count.sum())))
        assert reg.gauge('input/row_capacity_fill').value == pytest.approx(
            distinct / (4 * (packed.tok_rows.shape[1]
                             + packed.path_rows.shape[1])))
    finally:
        if not was:
            core.disable()
