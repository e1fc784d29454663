"""Checkpoint / resume / release round-trips (reference parity:
tensorflow_model.py:370-377, keras_model.py:230-296)."""
import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.model_api import Code2VecModel
from tests.test_train_overfit import make_dataset


def _train_config(tmp_path, prefix, **overrides):
    defaults = dict(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=2, SAVE_EVERY_EPOCHS=1,
        SHUFFLE_BUFFER_SIZE=64, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        MODEL_SAVE_PATH=str(tmp_path / 'models' / 'saved_model'))
    defaults.update(overrides)
    return Config(**defaults)


def test_save_creates_sidecar_and_checkpoints(tmp_path):
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix)
    model = Code2VecModel(config)
    model.train()
    model_dir = tmp_path / 'models'
    assert (model_dir / 'dictionaries.bin').exists()
    assert (model_dir / 'saved_model__entire-model').is_dir()


@pytest.mark.parametrize('train_framework,load_framework',
                         [('jax', 'jax'), ('flax', 'flax'),
                          ('jax', 'flax'), ('flax', 'jax')])
def test_load_params_reproduces_predictions(tmp_path, train_framework,
                                            load_framework):
    """Checkpoints use a canonical params layout: a model trained under
    either backend loads (params-only) under either backend — a capability
    the reference lacked (README.md:210)."""
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, DL_FRAMEWORK=train_framework)
    model = Code2VecModel(config)
    model.train()
    line = 'get|a toka0,pA,toka1 toka1,pB,toka2    '
    before = model.predict([line])[0]

    config2 = Config(
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'),
        DL_FRAMEWORK=load_framework, COMPUTE_DTYPE='float32', MAX_CONTEXTS=6,
        VERBOSE_MODE=0, READER_USE_NATIVE=False)
    model2 = Code2VecModel(config2)
    after = model2.predict([line])[0]
    assert before.topk_predicted_words == after.topk_predicted_words
    np.testing.assert_allclose(before.topk_predicted_words_scores,
                               after.topk_predicted_words_scores, rtol=1e-5)


def test_release_under_other_framework_preserves_meta(tmp_path):
    """--release under the other backend must not relabel the training
    checkpoint's framework in meta.json — the cross-framework resume
    diagnostic depends on the original writer's value."""
    import json
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, DL_FRAMEWORK='jax',
                           NUM_TRAIN_EPOCHS=1)
    Code2VecModel(config).train()

    load_path = str(tmp_path / 'models' / 'saved_model')
    config_r = Config(MODEL_LOAD_PATH=load_path, RELEASE=True,
                      DL_FRAMEWORK='flax', COMPUTE_DTYPE='float32',
                      MAX_CONTEXTS=6, VERBOSE_MODE=0,
                      READER_USE_NATIVE=False)
    model_r = Code2VecModel(config_r)
    model_r.release_model()
    with open(load_path + '.meta.json') as f:
        meta = json.load(f)
    assert meta['framework'] == 'jax'
    assert meta['checkpoint_layout'] == 'canonical-v1'


def test_cross_framework_training_resume_raises_clearly(tmp_path):
    """Optimizer state is backend-specific: resuming TRAINING under the
    other framework must fail with an explanation, not an orbax shape
    error (params-only loads are covered by the test above)."""
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, DL_FRAMEWORK='jax',
                           NUM_TRAIN_EPOCHS=1)
    Code2VecModel(config).train()

    config2 = _train_config(
        tmp_path, prefix, DL_FRAMEWORK='flax', NUM_TRAIN_EPOCHS=2,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    with pytest.raises(ValueError, match='framework'):
        Code2VecModel(config2)


def test_resume_from_foreign_optimizer_tree_raises_clearly(tmp_path):
    """A checkpoint whose optimizer state is not dense Adam's tree (as a
    lazy-Adam checkpoint of an older version is: token/path moments in
    dicts beside a small optax state) must end a TRAINING resume in the
    store's clear error, not in orbax's tree diff; its parameters still
    load."""
    import collections
    import jax.numpy as jnp
    prefix = make_dataset(tmp_path)
    model = Code2VecModel(_train_config(tmp_path, prefix))
    named = model.backend.named_params(model.state.params)._asdict()
    tables = {name: jnp.zeros_like(named[name])
              for name in ('token_embedding', 'path_embedding')}
    foreign = collections.namedtuple('Foreign', 'dense mu nu')(
        dense=(jnp.zeros((), jnp.int32),), mu=tables, nu=dict(tables))
    model.save(state=model.state._replace(opt_state=foreign))
    model.close_stores()

    load_path = str(tmp_path / 'models' / 'saved_model')
    with pytest.raises(ValueError, match='optimizer state') as raised:
        Code2VecModel(_train_config(tmp_path, prefix,
                                    MODEL_LOAD_PATH=load_path))
    assert 'Params-only loads' in str(raised.value)
    # a store-wide cause, not corruption: nothing was renamed aside
    assert (tmp_path / 'models' / 'saved_model__entire-model' / '0').is_dir()

    loaded = Code2VecModel(_train_config(
        tmp_path, prefix, TRAIN_DATA_PATH_PREFIX=None,
        MODEL_SAVE_PATH=None, MODEL_LOAD_PATH=load_path))
    got = loaded.backend.named_params(loaded.params)._asdict()
    for name, want in named.items():
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want), err_msg=name)


def test_resume_training_continues_from_epoch(tmp_path):
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=2)
    model = Code2VecModel(config)
    model.train()

    # resume with --load and --data: starts at epoch 2
    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=4,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    assert model2._start_epoch == 2
    assert int(model2.state.step) > 0
    model2.train()  # runs epochs 2..3 without error


@pytest.mark.parametrize('saved_mu,resume_mu',
                         [('float32', 'bfloat16'),
                          ('bfloat16', 'float32')])
def test_resume_across_adam_mu_dtype(tmp_path, saved_mu, resume_mu):
    """ADAM_MU_DTYPE's default flipped fp32 -> bf16 (2026-07-31 A/B):
    resuming an older checkpoint under the new default (and vice versa)
    must adapt — restore as stored, cast mu to the configured dtype —
    not fail with an orbax dtype mismatch (advisor r5)."""
    import jax
    import jax.numpy as jnp

    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1,
                           ADAM_MU_DTYPE=saved_mu)
    Code2VecModel(config).train()

    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, ADAM_MU_DTYPE=resume_mu,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    assert model2._start_epoch == 1
    mu = model2.state.opt_state[0].mu
    mu_dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(mu)}
    assert mu_dtypes == {np.dtype(getattr(jnp, resume_mu))}
    model2.train()  # epoch 1 runs under the configured mu dtype


@pytest.mark.parametrize('saved_nu,resume_nu',
                         [('float32', 'bfloat16'),
                          ('bfloat16', 'float32')])
def test_resume_across_adam_nu_dtype(tmp_path, saved_nu, resume_nu):
    """ADAM_NU_DTYPE is gated on the same flip rule as mu was: cross-dtype
    resume must adapt in both directions — restore the second moment as
    stored, cast to the configured dtype (checkpoints._MOMENT_FIELDS
    covers both moments)."""
    import jax
    import jax.numpy as jnp

    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1,
                           ADAM_NU_DTYPE=saved_nu)
    Code2VecModel(config).train()

    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, ADAM_NU_DTYPE=resume_nu,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    assert model2._start_epoch == 1
    nu = model2.state.opt_state[0].nu
    nu_dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(nu)}
    assert nu_dtypes == {np.dtype(getattr(jnp, resume_nu))}
    model2.train()  # epoch 1 runs under the configured nu dtype


def test_resume_across_opt_state_sharding_modes(tmp_path):
    """A checkpoint written with the mirrored moment layout resumes under
    OPTIMIZER_STATE_SHARDING='zero' (and the moments land zero-sharded):
    orbax re-shards onto the restore target's layout, so the knob is a
    runtime choice, not a checkpoint property."""
    from jax.sharding import PartitionSpec as P

    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1,
                           PARAM_ROW_ALIGNMENT=8,
                           MESH_DATA_AXIS_SIZE=4, MESH_MODEL_AXIS_SIZE=2)
    Code2VecModel(config).train()

    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, PARAM_ROW_ALIGNMENT=8,
        MESH_DATA_AXIS_SIZE=4, MESH_MODEL_AXIS_SIZE=2,
        OPTIMIZER_STATE_SHARDING='zero',
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    mu = model2.state.opt_state[0].mu
    leaf = mu.token_embedding if hasattr(mu, 'token_embedding') \
        else mu['token_embedding']
    assert leaf.sharding.spec == P(('data', 'model'), None)
    model2.train()  # epoch 1 runs under the zero layout without error


@pytest.mark.usefixtures('pallas_interpret')
def test_resume_across_fused_ce_and_mesh_reshape(tmp_path):
    """ADVICE r3: the fused-CE target-table allocation folds in the vocab
    tile and mesh model-axis size, so its row count is topology-dependent —
    restore must pad/slice the masked padding rows instead of rejecting the
    checkpoint, in BOTH directions (fused-CE -> plain slice, plain ->
    fused-CE pad)."""
    prefix = make_dataset(tmp_path)
    # save under fused CE + model axis 2: rows align to VOCAB_TILE*2
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1,
                           PARAM_ROW_ALIGNMENT=8,
                           MESH_DATA_AXIS_SIZE=4, MESH_MODEL_AXIS_SIZE=2,
                           USE_PALLAS_FUSED_CE=True)
    model = Code2VecModel(config)
    model.train()
    line = 'get|a toka0,pA,toka1 toka1,pB,toka2    '
    before = model.predict([line])[0]
    fused_rows = model.backend.sizes['target_vocab_size']

    # training resume with fused CE OFF on a plain mesh: rows shrink to the
    # plain alignment; Adam moments slice with the table
    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, PARAM_ROW_ALIGNMENT=8,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    assert model2.backend.sizes['target_vocab_size'] < fused_rows
    assert (model2.state.params.target_embedding.shape[0]
            == model2.backend.sizes['target_vocab_size'])
    after = model2.predict([line])[0]
    # the fused allocation's top-k can run past the valid vocab into masked
    # padding columns; the sliced model can't — compare the valid prefix
    n = min(len(before.topk_predicted_words), len(after.topk_predicted_words))
    assert before.topk_predicted_words[:n] == after.topk_predicted_words[:n]
    np.testing.assert_allclose(before.topk_predicted_words_scores[:n],
                               after.topk_predicted_words_scores[:n],
                               rtol=1e-5)
    model2.train()  # epoch 1 runs with the sliced moments without error
    # train() wrote a NEWER checkpoint — the state model3 restores below.
    # Compare against a fresh prediction of THAT state: the pre-train
    # `after` only matches when the extra epoch happens to move nothing
    # (it did on the original toolchain, by convergence luck, but the
    # pad-direction claim is about the restore, not about training being
    # a no-op).
    after_train = model2.predict([line])[0]

    # params-only load back UNDER fused CE (pad direction)
    config3 = Config(
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'),
        DL_FRAMEWORK='jax', COMPUTE_DTYPE='float32', MAX_CONTEXTS=6,
        VERBOSE_MODE=0, READER_USE_NATIVE=False, PARAM_ROW_ALIGNMENT=8,
        USE_PALLAS_FUSED_CE=True)
    model3 = Code2VecModel(config3)
    assert model3.backend.sizes['target_vocab_size'] > \
        model2.backend.sizes['target_vocab_size']
    padded = model3.predict([line])[0]
    m = min(len(padded.topk_predicted_words),
            len(after_train.topk_predicted_words))
    assert padded.topk_predicted_words[:m] == \
        after_train.topk_predicted_words[:m]


@pytest.mark.usefixtures('pallas_interpret')
def test_release_rows_rewrite_does_not_poison_older_checkpoints(tmp_path):
    """ADVICE r4: one meta.json serves the whole history, and its
    target_vocab_rows tracks only the NEWEST writer — after a --release
    under a plain (smaller-rows) config, a resume of the older fused-CE
    entire-model checkpoint used to build restore targets with the
    release's row count against the checkpoint's larger arrays. The
    restore must read the saved row count from the artifact itself
    (orbax array metadata), not the shared sidecar."""
    import json
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1,
                           PARAM_ROW_ALIGNMENT=8, USE_PALLAS_FUSED_CE=True)
    model = Code2VecModel(config)
    model.train()
    line = 'get|a toka0,pA,toka1 toka1,pB,toka2    '
    before = model.predict([line])[0]
    fused_rows = model.backend.sizes['target_vocab_size']

    # --release under a plain config rewrites the sidecar's rows
    load_path = str(tmp_path / 'models' / 'saved_model')
    config_r = Config(MODEL_LOAD_PATH=load_path, RELEASE=True,
                      DL_FRAMEWORK='jax', COMPUTE_DTYPE='float32',
                      MAX_CONTEXTS=6, VERBOSE_MODE=0,
                      READER_USE_NATIVE=False, PARAM_ROW_ALIGNMENT=8)
    Code2VecModel(config_r).release_model()
    with open(load_path + '.meta.json') as f:
        sidecar_rows = json.load(f)['target_vocab_rows']
    assert sidecar_rows < fused_rows

    # resume TRAINING from the fused-CE entire-model checkpoint: its
    # arrays hold fused_rows rows while the sidecar now says sidecar_rows
    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, PARAM_ROW_ALIGNMENT=8,
        USE_PALLAS_FUSED_CE=True, MODEL_LOAD_PATH=load_path)
    model2 = Code2VecModel(config2)
    assert model2._start_epoch == 1
    assert (model2.state.params.target_embedding.shape[0] == fused_rows)
    after = model2.predict([line])[0]
    assert before.topk_predicted_words == after.topk_predicted_words
    np.testing.assert_allclose(before.topk_predicted_words_scores,
                               after.topk_predicted_words_scores, rtol=1e-5)
    model2.train()  # epoch 1 runs from the restored moments without error


def test_step_interval_saves_and_midepoch_resume(tmp_path):
    """SAVE_EVERY_N_STEPS (VERDICT r1 #8): step-keyed async snapshots
    during the epoch bound preemption loss, in their OWN short-retention
    store (they must not evict epoch-boundary history); resume prefers the
    newest state across both stores and restarts an interrupted epoch."""
    # 60 examples, batch 16 -> 4 (padded) steps/epoch, 8 steps over 2 epochs
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=2,
                           SAVE_EVERY_EPOCHS=1, SAVE_EVERY_N_STEPS=2)
    model = Code2VecModel(config)
    model.train()

    store = model._store_for(config.MODEL_SAVE_PATH)
    # epoch-boundary saves keep their own retention window...
    assert sorted(store.manager().all_steps()) == [4, 8]
    # ...interval snapshots fire between boundaries (the step-4 interval is
    # deduplicated against the epoch-0 boundary save)
    assert sorted(store.snapshot_manager().all_steps()) == [2, 6]
    model.close_stores()

    # newest checkpoint (step 8 = end of epoch 1) must record epoch 1 even
    # though a step interval also landed on that boundary -> resume at
    # epoch 2, not a replay of epoch 1
    config2 = _train_config(
        tmp_path, prefix, NUM_TRAIN_EPOCHS=2, SAVE_EVERY_N_STEPS=0,
        MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))
    model2 = Code2VecModel(config2)
    assert int(model2.state.step) == 8
    assert model2._start_epoch == 2
    model2.close_stores()

    # drop the epoch-boundary checkpoints: the newest mid-epoch snapshot
    # (step 6, inside epoch 1) must restart epoch 1
    import shutil
    entire = tmp_path / 'models' / 'saved_model__entire-model'
    shutil.rmtree(entire / '8')
    shutil.rmtree(entire / '4')
    model3 = Code2VecModel(config2)
    assert int(model3.state.step) == 6
    assert model3._start_epoch == 1  # restart the interrupted epoch
    model3.train()  # completes epoch 1 without error


def test_release_params_only(tmp_path):
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix)
    model = Code2VecModel(config)
    model.train()

    load_path = str(tmp_path / 'models' / 'saved_model')
    config_release = Config(
        MODEL_LOAD_PATH=load_path, RELEASE=True, DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, VERBOSE_MODE=0,
        READER_USE_NATIVE=False)
    model_r = Code2VecModel(config_release)
    model_r.release_model()
    weights_dir = tmp_path / 'models' / 'saved_model__only-weights'
    assert weights_dir.is_dir()

    # a released model loads (params-only path preferred) and predicts
    config3 = Config(
        MODEL_LOAD_PATH=load_path, DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, VERBOSE_MODE=0,
        READER_USE_NATIVE=False)
    model3 = Code2VecModel(config3)
    result = model3.predict(['get|a toka0,pA,toka1    '])[0]
    assert len(result.topk_predicted_words) > 0


def test_word2vec_export(tmp_path):
    from code2vec_tpu.vocab import VocabType
    prefix = make_dataset(tmp_path)
    config = _train_config(tmp_path, prefix, NUM_TRAIN_EPOCHS=1)
    model = Code2VecModel(config)
    dest = tmp_path / 'tokens.w2v'
    model.save_word2vec_format(str(dest), VocabType.Token)
    lines = dest.read_text().splitlines()
    vocab_size, dim = map(int, lines[0].split())
    assert vocab_size == model.vocabs.token_vocab.size
    assert dim == config.TOKEN_EMBEDDINGS_SIZE
    assert len(lines) == vocab_size + 1
