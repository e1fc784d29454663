"""Multi-chip semantics on the 8-virtual-device CPU mesh (SURVEY.md §4):
DP-only, TP-only and mixed meshes must produce the same numbers as a
single-device run — sharding is configuration, not semantics."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import Batch
from code2vec_tpu.models.backends import create_backend
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.training.trainer import Trainer
from code2vec_tpu.vocab import Code2VecVocabs, SizeOnlyVocabs


def _make_batch(rng, B=16, C=8, Vt=40, Vp=12):
    source = rng.integers(1, Vt, (B, C)).astype(np.int32)
    path = rng.integers(1, Vp, (B, C)).astype(np.int32)
    target = rng.integers(1, Vt, (B, C)).astype(np.int32)
    mask = np.ones((B, C), np.float32)
    label = rng.integers(1, 20, (B,)).astype(np.int32)
    weight = np.ones((B,), np.float32)
    return Batch(source=source, path=path, target=target, mask=mask,
                 label=label, weight=weight)


def _config(data_axis, model_axis, framework='jax', **overrides):
    kwargs = dict(
        TRAIN_DATA_PATH_PREFIX='unused', DL_FRAMEWORK=framework,
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=8, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        MESH_DATA_AXIS_SIZE=data_axis, MESH_MODEL_AXIS_SIZE=model_axis,
        MAX_TOKEN_VOCAB_SIZE=40, MAX_PATH_VOCAB_SIZE=12,
        MAX_TARGET_VOCAB_SIZE=24, TOKEN_EMBEDDINGS_SIZE=8,
        PATH_EMBEDDINGS_SIZE=8, CODE_VECTOR_SIZE=24,
        TARGET_EMBEDDINGS_SIZE=24, LEARNING_RATE=0.01)
    kwargs.update(overrides)
    return Config(**kwargs)


def _trainer(data_axis, model_axis, framework='jax', **overrides):
    config = _config(data_axis, model_axis, framework, **overrides)
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend = create_backend(config, vocabs)
    return Trainer(config, backend)


def _run_steps(trainer, n=3, seed=0, make_batch=_make_batch):
    state = trainer.init_state(seed=123)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(n):
        batch = make_batch(rng)
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    return state, losses


def test_mesh_shapes():
    assert mesh_lib.create_mesh(_config(8, 1)).shape == {'data': 8, 'model': 1}
    assert mesh_lib.create_mesh(_config(4, 2)).shape == {'data': 4, 'model': 2}
    assert mesh_lib.create_mesh(_config(-1, 2)).shape == {'data': 4, 'model': 2}
    with pytest.raises(ValueError):
        mesh_lib.create_mesh(_config(3, 2))


def test_param_placement_on_mixed_mesh():
    trainer = _trainer(4, 2)
    state = trainer.init_state()
    named = trainer.backend.named_params(state.params)
    # embeddings row-sharded over model axis
    assert named.token_embedding.sharding.spec == P('model', None)
    assert named.target_embedding.sharding.spec == P('model', None)
    # dense params replicated
    assert named.transform.sharding.spec in (P(), P(None, None))
    # Adam moments inherit the table sharding (name-based mapping)
    mu = state.opt_state[0].mu
    leaf = mu.token_embedding if hasattr(mu, 'token_embedding') \
        else mu['token_embedding']
    assert leaf.sharding.spec == P('model', None)


@pytest.mark.parametrize('mesh_shape', [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_training_matches_single_device(mesh_shape):
    # ground truth: 1x1 mesh on device 0
    config1 = _config(1, 1)
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend1 = create_backend(config1, vocabs)
    mesh1 = mesh_lib.create_mesh(config1, devices=jax.devices()[:1])
    trainer1 = Trainer(config1, backend1, mesh=mesh1)
    _, losses1 = _run_steps(trainer1)

    trainerN = _trainer(*mesh_shape)
    _, lossesN = _run_steps(trainerN)
    np.testing.assert_allclose(losses1, lossesN, rtol=2e-4, atol=1e-5)


def test_eval_step_on_sharded_mesh_matches_single_device():
    config1 = _config(1, 1)
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend1 = create_backend(config1, vocabs)
    mesh1 = mesh_lib.create_mesh(config1, devices=jax.devices()[:1])
    trainer1 = Trainer(config1, backend1, mesh=mesh1)
    state1, _ = _run_steps(trainer1)

    trainerN = _trainer(2, 4)
    stateN, _ = _run_steps(trainerN)

    rng = np.random.default_rng(7)
    batch = _make_batch(rng)
    out1 = trainer1.eval_step(state1.params, batch)
    outN = trainerN.eval_step(stateN.params, batch)
    np.testing.assert_array_equal(np.asarray(out1['topk_indices']),
                                  np.asarray(outN['topk_indices']))
    np.testing.assert_allclose(np.asarray(out1['topk_scores']),
                               np.asarray(outN['topk_scores']),
                               rtol=2e-4, atol=1e-5)


def test_shard_contexts_divisibility_validated_upfront():
    config = _config(2, 4)
    config.SHARD_CONTEXTS = True
    config.MAX_CONTEXTS = 6  # not divisible by model axis 4
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend = create_backend(config, vocabs)
    with pytest.raises(ValueError, match='SHARD_CONTEXTS'):
        Trainer(config, backend)


def test_row_alignment_divisibility_validated_upfront():
    config = _config(2, 4)
    config.PARAM_ROW_ALIGNMENT = 6  # not divisible by model axis 4
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend = create_backend(config, vocabs)
    with pytest.raises(ValueError, match='PARAM_ROW_ALIGNMENT'):
        Trainer(config, backend)


def test_shard_contexts_training_matches_unsharded():
    config = _config(2, 4)
    config.SHARD_CONTEXTS = True  # MAX_CONTEXTS=8 divisible by 4
    vocabs = SizeOnlyVocabs(40, 12, 24)
    backend = create_backend(config, vocabs)
    trainer_sp = Trainer(config, backend)
    _, losses_sp = _run_steps(trainer_sp)

    config1 = _config(1, 1)
    backend1 = create_backend(config1, SizeOnlyVocabs(40, 12, 24))
    mesh1 = mesh_lib.create_mesh(config1, devices=jax.devices()[:1])
    trainer1 = Trainer(config1, backend1, mesh=mesh1)
    _, losses1 = _run_steps(trainer1)
    np.testing.assert_allclose(losses1, losses_sp, rtol=2e-4, atol=1e-5)


def test_shard_contexts_long_bag_matches_unsharded():
    """Long-context scaling (SURVEY.md §5): a 1024-context bag sharded
    over the model axis (the order-free 'ring attention' analog — the
    attention reductions compile to XLA collectives) must match the
    unsharded numbers. This is the MAX_CONTEXTS-scaling story, not just
    the divisibility smoke at C=8."""
    LONG_C = 1024
    config = _config(2, 4)
    config.MAX_CONTEXTS = LONG_C
    config.SHARD_CONTEXTS = True
    vocabs = SizeOnlyVocabs(40, 12, 24)
    trainer_sp = Trainer(config, create_backend(config, vocabs))

    config1 = _config(1, 1)
    config1.MAX_CONTEXTS = LONG_C
    backend1 = create_backend(config1, SizeOnlyVocabs(40, 12, 24))
    mesh1 = mesh_lib.create_mesh(config1, devices=jax.devices()[:1])
    trainer1 = Trainer(config1, backend1, mesh=mesh1)

    def make_long_batch(rng):
        batch = _make_batch(rng, B=8, C=LONG_C)
        # half the contexts masked: the masked-softmax denominator must
        # psum identically across context shards
        return batch._replace(
            mask=(np.arange(LONG_C)[None, :] < LONG_C // 2)
            .astype(np.float32).repeat(8, axis=0))

    _, losses1 = _run_steps(trainer1, n=2, seed=7,
                            make_batch=make_long_batch)
    _, losses_sp = _run_steps(trainer_sp, n=2, seed=7,
                              make_batch=make_long_batch)
    np.testing.assert_allclose(losses1, losses_sp, rtol=2e-4, atol=1e-5)


def test_profile_trace_capture_smoke(tmp_path):
    """--profile (jax.profiler window inside fit): must produce a trace
    artifact — guards the path so the on-chip profiling day isn't spent
    debugging the harness (VERDICT r1 #2 groundwork)."""
    config = _config(8, 1)
    config.NUM_TRAIN_EPOCHS = 1
    config.PROFILE_DIR = str(tmp_path / 'trace')
    config.PROFILE_START_STEP = 1
    config.PROFILE_NUM_STEPS = 2
    vocabs = SizeOnlyVocabs(40, 12, 24)
    trainer = Trainer(config, create_backend(config, vocabs))
    state = trainer.init_state(seed=0)
    rng = np.random.default_rng(0)
    batches = [_make_batch(rng) for _ in range(6)]
    trainer.fit(state, lambda epoch: iter(batches), start_epoch=0)
    trace_files = list((tmp_path / 'trace').rglob('*'))
    assert any(f.is_file() for f in trace_files), 'no trace artifacts'
    assert any(f.name.endswith('.xplane.pb') for f in trace_files)
    # the legend beside it: the text of the one program the window ran
    # (telemetry off, planes wire), whose op_name gives each part
    programs = sorted(f.name for f in (tmp_path / 'trace'
                                       / 'programs').iterdir())
    assert programs == ['jit_train_step.planes-16.hlo.txt',
                        'jit_train_step.planes-16.json']
    text = (tmp_path / 'trace' / 'programs' / programs[0]).read_text()
    for scope in ('c2v_encode', 'c2v_logits', 'c2v_ce', 'c2v_adam'):
        assert scope in text, scope


def test_checkpoint_metadata_mismatch_is_clear_error(tmp_path):
    from code2vec_tpu.checkpoints import CheckpointStore
    store = CheckpointStore(str(tmp_path / 'm'),
                            metadata={'param_row_alignment': 128})
    store._write_metadata()
    store2 = CheckpointStore(str(tmp_path / 'm'),
                             metadata={'param_row_alignment': 256})
    with pytest.raises(ValueError, match='param_row_alignment'):
        store2.verify_metadata()


def _mu_leaf(state):
    mu = state.opt_state[0].mu
    return mu.token_embedding if hasattr(mu, 'token_embedding') \
        else mu['token_embedding']


def test_zero_opt_state_sharding_matches_mirror():
    """OPTIMIZER_STATE_SHARDING='zero' shards the moment tables over the
    whole (data, model) mesh: same losses as the mirrored layout, and the
    zero sharding survives the donated train step (no silent re-layout
    back to replicated-along-data)."""
    zero = _trainer(4, 2, PARAM_ROW_ALIGNMENT=8,
                    OPTIMIZER_STATE_SHARDING='zero')
    mirror = _trainer(4, 2, PARAM_ROW_ALIGNMENT=8)
    state_z, losses_z = _run_steps(zero, n=3)
    _, losses_m = _run_steps(mirror, n=3)
    np.testing.assert_allclose(losses_z, losses_m, rtol=2e-4, atol=1e-5)
    assert _mu_leaf(state_z).sharding.spec == P(('data', 'model'), None)
    # params stay replicated along data (ZeRO-1, not ZeRO-3)
    named = zero.backend.named_params(state_z.params)
    assert named.token_embedding.sharding.spec == P('model', None)


def test_remat_encode_on_mesh_matches_default():
    """jax.checkpoint around encode composes with the sharded train step
    (SHARD_CONTEXTS sequence parallelism included): identical losses."""
    _, plain = _run_steps(_trainer(4, 2, SHARD_CONTEXTS=True), n=2)
    _, remat = _run_steps(_trainer(4, 2, SHARD_CONTEXTS=True,
                                   REMAT_ENCODE=True), n=2)
    np.testing.assert_allclose(remat, plain, rtol=1e-6)


def test_zero_opt_state_requires_whole_mesh_alignment():
    with pytest.raises(ValueError, match='data\\*model'):
        _trainer(4, 2, PARAM_ROW_ALIGNMENT=2,
                 OPTIMIZER_STATE_SHARDING='zero')


@pytest.mark.parametrize('fused', [False, True])
@pytest.mark.usefixtures('pallas_interpret')
def test_bf16_grads_on_mixed_mesh_tracks_fp32_twin(fused):
    """The combined pod recipe: GRADS_DTYPE='bfloat16' (bf16 compute, as
    verify() requires) on a (4,2) DP+TP mesh, with and without the
    shard_mapped fused CE. A FIXED batch makes the trajectory strictly
    descend, so a silently dead bf16 cotangent path (grads zeroed through
    the psum/shard_map or fused-CE vjp) fails the descent assertion —
    proximity alone cannot catch it: over a few steps the loss moves less
    than any usable tolerance (review r5 measurement). The bf16 arm must
    also track the fp32 twin within grad-rounding tolerance."""
    rng = np.random.default_rng(3)
    fixed = _make_batch(rng)

    def make_fixed(_rng):
        return fixed

    base = _trainer(4, 2, COMPUTE_DTYPE='bfloat16',
                    GRADS_DTYPE='float32', USE_PALLAS_FUSED_CE=fused)
    lo = _trainer(4, 2, COMPUTE_DTYPE='bfloat16',
                  GRADS_DTYPE='bfloat16', USE_PALLAS_FUSED_CE=fused)
    _, base_losses = _run_steps(base, n=5, make_batch=make_fixed)
    _, lo_losses = _run_steps(lo, n=5, make_batch=make_fixed)
    # the bf16-grads arm LEARNS: repeated-batch loss must clearly drop
    # (a dead-grad arm stays flat at the step-1 value)
    assert lo_losses[-1] < lo_losses[0] - 0.05, (fused, lo_losses)
    for a, b in zip(base_losses, lo_losses):
        assert abs(a - b) / max(abs(a), 1e-6) < 0.03, (fused, base_losses,
                                                       lo_losses)


def test_fused_ce_changes_target_table_allocation():
    """USE_PALLAS_FUSED_CE (and the mesh model axis under it) grows the
    target-table allocation; the padded row count is what checkpoint
    metadata records ('target_vocab_rows') so a resume whose allocation
    differs fails with a clear config error instead of an opaque orbax
    shape mismatch — while resumes whose padding coincides still load."""
    from code2vec_tpu.models.backends import (JaxBackend,
                                              target_row_alignment)
    from code2vec_tpu.ops.pallas_ce import VOCAB_TILE

    base = _config(1, 1, PARAM_ROW_ALIGNMENT=8)
    assert target_row_alignment(base) == 8
    fused = _config(1, 1, PARAM_ROW_ALIGNMENT=8, USE_PALLAS_FUSED_CE=True)
    assert target_row_alignment(fused) == VOCAB_TILE
    fused_tp = _config(4, 2, PARAM_ROW_ALIGNMENT=8,
                       USE_PALLAS_FUSED_CE=True)
    assert target_row_alignment(fused_tp) == 2 * VOCAB_TILE

    vocabs = SizeOnlyVocabs(40, 12, 24)
    assert JaxBackend(base, vocabs).sizes['target_vocab_size'] == 24
    assert JaxBackend(fused, vocabs).sizes['target_vocab_size'] == \
        VOCAB_TILE
    assert JaxBackend(fused_tp, vocabs).sizes['target_vocab_size'] == \
        2 * VOCAB_TILE


def test_sharded_top_k_matches_lax_top_k():
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from code2vec_tpu.ops.topk import sharded_top_k
    config = _config(2, 4)
    mesh = mesh_lib.create_mesh(config)
    rng = np.random.default_rng(0)
    # distinct values so tie-breaking can't differ
    logits = rng.permutation(16 * 64).reshape(16, 64).astype(np.float32)
    ref_vals, ref_idx = jax.lax.top_k(jnp.asarray(logits), 10)
    placed = jax.device_put(logits, NamedSharding(mesh, P('data', 'model')))
    vals, idx = jax.jit(
        lambda x: sharded_top_k(x, 10, mesh))(placed)
    np.testing.assert_array_equal(np.asarray(ref_idx), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(ref_vals), np.asarray(vals))

    # k larger than the per-shard width (V/m = 2 < k = 5): every shard
    # contributes all columns
    small = rng.permutation(16 * 8).reshape(16, 8).astype(np.float32)
    ref_vals5, ref_idx5 = jax.lax.top_k(jnp.asarray(small), 5)
    placed5 = jax.device_put(small, NamedSharding(mesh, P('data', 'model')))
    vals5, idx5 = jax.jit(lambda x: sharded_top_k(x, 5, mesh))(placed5)
    np.testing.assert_array_equal(np.asarray(ref_idx5), np.asarray(idx5))
    np.testing.assert_allclose(np.asarray(ref_vals5), np.asarray(vals5))


def test_flax_backend_shards_too():
    trainer = _trainer(4, 2, framework='flax')
    _, losses = _run_steps(trainer, n=2)
    assert all(np.isfinite(losses))


def test_bf16_mu_matches_layout_on_tp_mesh():
    """ADAM_MU_DTYPE='bfloat16' on a (4, 2) mesh: the bf16 first moment
    must mirror the row-sharded table layout (mu sharded like params) and
    training must still run."""
    import jax.numpy as jnp

    trainer = _trainer(4, 2, ADAM_MU_DTYPE='bfloat16')
    state, losses = _run_steps(trainer, n=2)
    assert np.isfinite(losses).all()

    mu = state.opt_state[0].mu
    leaves = jax.tree_util.tree_leaves(mu)
    assert {leaf.dtype for leaf in leaves} == {np.dtype(jnp.bfloat16)}
    # the token table's mu shards over 'model' rows exactly like the param
    token_mu = mu.token_embedding
    token_param = state.params.token_embedding
    assert token_mu.sharding.spec == token_param.sharding.spec


def test_rbg_dropout_trains_on_tp_mesh():
    """DROPOUT_PRNG_IMPL='rbg' on a (4, 2) mesh with SHARD_CONTEXTS: the
    (B, C, 3d) rng_bit_generator mask draw must lower through SPMD
    partitioning (it was only exercised single-device before) and produce
    finite, decreasing-ish losses like the threefry path."""
    trainer = _trainer(4, 2, DROPOUT_PRNG_IMPL='rbg', SHARD_CONTEXTS=True)
    _, losses = _run_steps(trainer, n=3)
    assert np.isfinite(losses).all()
    # seed-deterministic, so this is not flaky: a degenerate rbg mask
    # (e.g. all-dropped) would keep loss pinned at ~ln(V) instead
    assert losses[-1] < losses[0]

    # same data, threefry path: rbg is a different (valid) random stream,
    # so only coarse agreement is expected — both must actually learn
    trainer_tf = _trainer(4, 2, SHARD_CONTEXTS=True)
    _, losses_tf = _run_steps(trainer_tf, n=3)
    assert np.isfinite(losses_tf).all()
    assert abs(losses[0] - losses_tf[0]) < 1.0
