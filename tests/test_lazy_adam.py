"""Lazy (sparse-row) Adam: math vs a numpy reference, TF1 lazy-moment
semantics through the Trainer, backend agnosticism, and mesh parity."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import Batch
from code2vec_tpu.models.backends import create_backend
from code2vec_tpu.ops.lazy_adam import sparse_row_adam
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.training.trainer import Trainer
from code2vec_tpu.vocab import SizeOnlyVocabs


def numpy_lazy_adam(table, mu, nu, dense_grad, rows, lr, step,
                    b1=0.9, b2=0.999, eps=1e-8):
    """Straight-line reference: one update per UNIQUE touched row."""
    table, mu, nu = table.copy(), mu.copy(), nu.copy()
    lr_t = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
    for r in np.unique(rows):
        g = dense_grad[r]
        mu[r] = b1 * mu[r] + (1 - b1) * g
        nu[r] = b2 * nu[r] + (1 - b2) * g * g
        table[r] = table[r] - lr_t * mu[r] / (np.sqrt(nu[r]) + eps)
    return table, mu, nu


def test_sparse_row_adam_matches_numpy_with_duplicates():
    rng = np.random.default_rng(0)
    v, d = 12, 5
    table = rng.normal(size=(v, d)).astype(np.float32)
    mu = rng.normal(size=(v, d)).astype(np.float32) * 0.1
    nu = np.abs(rng.normal(size=(v, d))).astype(np.float32) * 0.01
    grad = rng.normal(size=(v, d)).astype(np.float32)
    rows = np.array([3, 7, 3, 0, 7, 7, 11], np.int32)  # heavy duplication
    grad[[r for r in range(v) if r not in rows]] = 0.0

    got_t, got_m, got_v = sparse_row_adam(
        jnp.asarray(table), jnp.asarray(mu), jnp.asarray(nu),
        jnp.asarray(grad), jnp.asarray(rows),
        learning_rate=0.01, step=jnp.asarray(3))
    want_t, want_m, want_v = numpy_lazy_adam(table, mu, nu, grad, rows,
                                             lr=0.01, step=3)
    np.testing.assert_allclose(np.asarray(got_t), want_t, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_m), want_m, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-6)
    # untouched rows bit-identical
    untouched = [r for r in range(v) if r not in rows]
    np.testing.assert_array_equal(np.asarray(got_t)[untouched],
                                  table[untouched])


VOCAB_TOK, VOCAB_PATH, VOCAB_TGT = 48, 24, 16


def make_trainer(framework='jax', **overrides):
    overrides.setdefault('LAZY_EMBEDDING_ADAM', True)
    config = Config(
        TRAIN_DATA_PATH_PREFIX='unused', DL_FRAMEWORK=framework,
        VERBOSE_MODE=0, READER_USE_NATIVE=False, MAX_CONTEXTS=6,
        TRAIN_BATCH_SIZE=8, TEST_BATCH_SIZE=8, COMPUTE_DTYPE='float32',
        MAX_TOKEN_VOCAB_SIZE=VOCAB_TOK, MAX_PATH_VOCAB_SIZE=VOCAB_PATH,
        MAX_TARGET_VOCAB_SIZE=VOCAB_TGT, TOKEN_EMBEDDINGS_SIZE=8,
        PATH_EMBEDDINGS_SIZE=8, CODE_VECTOR_SIZE=24,
        TARGET_EMBEDDINGS_SIZE=24, PARAM_ROW_ALIGNMENT=8,
        LEARNING_RATE=0.01, **overrides)
    backend = create_backend(
        config, SizeOnlyVocabs(VOCAB_TOK, VOCAB_PATH, VOCAB_TGT))
    return Trainer(config, backend)


def batch_touching(tok_lo, tok_hi, seed=0):
    """All token/target indices drawn from [tok_lo, tok_hi)."""
    rng = np.random.default_rng(seed)
    b, c = 8, 6
    return Batch(
        source=rng.integers(tok_lo, tok_hi, (b, c)).astype(np.int32),
        path=rng.integers(1, VOCAB_PATH, (b, c)).astype(np.int32),
        target=rng.integers(tok_lo, tok_hi, (b, c)).astype(np.int32),
        mask=np.ones((b, c), np.float32),
        label=rng.integers(1, VOCAB_TGT, (b,)).astype(np.int32),
        weight=np.ones((b,), np.float32))


def canonical(trainer, params):
    named = trainer.backend.named_params(params)
    return {k: np.asarray(v) for k, v in named._asdict().items()}


def test_lazy_moments_skip_untouched_rows():
    """LazyAdam semantics: a row touched in step 1 but absent from step 2
    must not move in step 2 (dense Adam — the reference-parity default —
    would decay its momentum and apply the drift)."""
    trainer = make_trainer()
    state = trainer.init_state(seed=0)
    low = batch_touching(1, 8, seed=0)    # rows 1..7
    high = batch_touching(30, 40, seed=1)  # rows 30..39

    state, _ = trainer.train_step(state, low)
    after_step1 = canonical(trainer, state.params)
    state, _ = trainer.train_step(state, high)
    after_step2 = canonical(trainer, state.params)

    # rows 1..7 moved in step 1...
    assert not np.allclose(after_step1['token_embedding'][1:8],
                           canonical(trainer,
                                     trainer.init_state(seed=0).params)
                           ['token_embedding'][1:8])
    # ...and stayed EXACTLY put in step 2 (lazy moments)
    np.testing.assert_array_equal(after_step2['token_embedding'][1:8],
                                  after_step1['token_embedding'][1:8])
    # while step 2's own rows moved
    assert not np.allclose(after_step2['token_embedding'][30:40],
                           after_step1['token_embedding'][30:40])
    # dense params (transform) moved both steps
    assert not np.allclose(after_step2['transform'], after_step1['transform'])


def test_lazy_loss_decreases():
    trainer = make_trainer()
    state = trainer.init_state(seed=0)
    batch = batch_touching(1, VOCAB_TOK)
    first = last = None
    for _ in range(30):
        state, loss = trainer.train_step(state, batch)
        if first is None:
            first = float(loss)
        last = float(loss)
    assert last < first * 0.7, (first, last)


def test_lazy_backend_parity_jax_vs_flax():
    """Same canonical params + same batch -> identical params after one
    lazy step under either backend."""
    t_jax = make_trainer('jax')
    t_flax = make_trainer('flax')
    s_jax = t_jax.init_state(seed=0)
    start = canonical(t_jax, s_jax.params)
    s_flax = t_flax.state_from_params(
        t_flax.backend.from_canonical(dict(start)), step=0, seed=0)
    # align the dropout key; COPY the leaves (train_step donates its
    # state, so sharing buffers across the two states would leave the
    # second step reading deleted arrays)
    s_flax = s_flax._replace(rng=jnp.array(np.asarray(s_jax.rng)),
                             step=jnp.array(np.asarray(s_jax.step)))

    batch = batch_touching(1, VOCAB_TOK)
    s_jax, loss_jax = t_jax.train_step(s_jax, batch)
    s_flax, loss_flax = t_flax.train_step(s_flax, batch)
    assert float(loss_jax) == pytest.approx(float(loss_flax), rel=1e-6)
    a = canonical(t_jax, s_jax.params)
    b = canonical(t_flax, s_flax.params)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_lazy_mesh_parity():
    """A 4x2 mesh lazy step equals the single-device result."""
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip('needs 8 virtual devices')
    t_single = make_trainer()
    mesh = mesh_lib.create_mesh(
        Config(TRAIN_DATA_PATH_PREFIX='unused', MESH_DATA_AXIS_SIZE=4,
               MESH_MODEL_AXIS_SIZE=2, VERBOSE_MODE=0),
        devices=devices[:8])
    t_mesh = make_trainer(MESH_DATA_AXIS_SIZE=4, MESH_MODEL_AXIS_SIZE=2)
    assert t_mesh.mesh.shape == mesh.shape

    s_single = t_single.init_state(seed=0)
    start = canonical(t_single, s_single.params)
    s_mesh = t_mesh.state_from_params(
        t_mesh.backend.from_canonical(dict(start)), step=0, seed=0)
    s_mesh = s_mesh._replace(rng=jnp.array(np.asarray(s_single.rng)),
                             step=jnp.array(np.asarray(s_single.step)))

    batch = batch_touching(1, VOCAB_TOK)
    s_single, loss_a = t_single.train_step(s_single, batch)
    s_mesh, loss_b = t_mesh.train_step(s_mesh, batch)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    a = canonical(t_single, s_single.params)
    b = canonical(t_mesh, s_mesh.params)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_lazy_checkpoint_resume(tmp_path):
    """Full save/resume round-trip with the lazy optimizer state (orbax
    must restore the LazyAdamState pytree, moments included)."""
    from code2vec_tpu.model_api import Code2VecModel
    from tests.test_train_overfit import make_dataset
    prefix = make_dataset(tmp_path)
    common = dict(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, SAVE_EVERY_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False, LAZY_EMBEDDING_ADAM=True,
        MODEL_SAVE_PATH=str(tmp_path / 'models' / 'saved_model'))
    model = Code2VecModel(Config(NUM_TRAIN_EPOCHS=1, **common))
    model.train()

    resumed = Code2VecModel(Config(
        NUM_TRAIN_EPOCHS=2, **dict(
            common,
            MODEL_LOAD_PATH=str(tmp_path / 'models' / 'saved_model'))))
    assert resumed._start_epoch == 1
    # restored moments are a LazyAdamState with the right leaves
    from code2vec_tpu.ops.lazy_adam import LazyAdamState
    opt = resumed.state.opt_state
    assert isinstance(opt, LazyAdamState) or hasattr(opt, 'mu')
    resumed.train()  # second epoch runs without error


def test_bf16_mu_adam_trains():
    """ADAM_MU_DTYPE='bfloat16' (dense Adam only) stores the first moment
    in bf16 and still reduces the loss; the second moment is PINNED fp32
    here (ADAM_NU_DTYPE has its own default and tests —
    test_adam_dtypes.py), and checkpoint restore targets carry the same
    dtypes."""
    import jax
    import jax.numpy as jnp

    trainer = make_trainer(LAZY_EMBEDDING_ADAM=False,
                           ADAM_MU_DTYPE='bfloat16',
                           ADAM_NU_DTYPE='float32')
    state = trainer.init_state(seed=0)
    mu_dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        state.opt_state[0].mu)}
    nu_dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        state.opt_state[0].nu)}
    assert mu_dtypes == {np.dtype(jnp.bfloat16)}
    assert nu_dtypes == {np.dtype(jnp.float32)}

    batch = batch_touching(1, VOCAB_TOK, seed=2)
    state, loss0 = trainer.train_step(state, batch)  # donates old state
    loss = loss0
    for _ in range(20):
        state, loss = trainer.train_step(state, batch)
    assert float(loss) < float(loss0)

    # resume consistency: abstract_state derives from the configured
    # optimizer, so the restore target must be bf16-mu too
    _, abstract_opt = trainer.abstract_state()
    abs_mu = {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        abstract_opt[0].mu)}
    assert abs_mu == {np.dtype(jnp.bfloat16)}


def test_bf16_mu_ignored_with_lazy_adam():
    """ADAM_MU_DTYPE='bfloat16' is the config DEFAULT; lazy Adam keeps
    fp32 moments, does not consume the knob, and must warn (not raise —
    raising would break lazy users who never touched the default)."""
    import logging

    import jax
    import jax.numpy as jnp

    # attach a handler directly: earlier tests may have configured the
    # package logger in ways that stop propagation to pytest's caplog
    records = []

    class _Collect(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger('code2vec_tpu.training.trainer')
    handler = _Collect(level=logging.WARNING)
    logger.addHandler(handler)
    try:
        trainer = make_trainer(ADAM_MU_DTYPE='bfloat16')
    finally:
        logger.removeHandler(handler)
    assert any('ignored' in msg for msg in records)
    state = trainer.init_state(seed=0)
    float_dtypes = {leaf.dtype
                    for leaf in jax.tree_util.tree_leaves(state.opt_state)
                    if hasattr(leaf, 'dtype')
                    and jnp.issubdtype(leaf.dtype, jnp.floating)}
    # every floating moment the lazy path stores stays fp32
    assert float_dtypes == {np.dtype(jnp.float32)}
