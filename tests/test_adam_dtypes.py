"""training/adam_dtypes.py — Adam with reduced-precision moment storage.

The transform must (a) reproduce ``optax.adam`` exactly when no dtype is
narrowed (it replaces it in the trainer only when ADAM_NU_DTYPE='bfloat16',
so the swap must be semantics-free), (b) store the moments in the
configured dtypes while computing the update in fp32, and (c) drive a real
train step through the Trainer.

Reference anchor: the reference's Adam is fp32-moment
tf.compat.v1.train.AdamOptimizer (/root/reference/tensorflow_model.py:232);
moment STORAGE dtype is a TPU HBM knob gated by the PERF.md flip rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu import benchlib
from code2vec_tpu.training import adam_dtypes


def _params():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        'table': jax.random.normal(k1, (64, 8), jnp.float32),
        'dense': {'w': jax.random.normal(k2, (8, 4), jnp.float32),
                  'b': jax.random.normal(k3, (4,), jnp.float32)},
    }


def _grads(step: int):
    key = jax.random.PRNGKey(100 + step)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        'table': jax.random.normal(k1, (64, 8), jnp.float32),
        'dense': {'w': jax.random.normal(k2, (8, 4), jnp.float32),
                  'b': jax.random.normal(k3, (4,), jnp.float32)},
    }


def test_matches_optax_adam_when_not_narrowed():
    """mu_dtype/nu_dtype = None must be a drop-in for optax.adam."""
    params_ref = _params()
    params_new = _params()
    opt_ref = optax.adam(1e-3)
    opt_new = adam_dtypes.adam(1e-3)
    state_ref = opt_ref.init(params_ref)
    state_new = opt_new.init(params_new)
    for step in range(5):
        g = _grads(step)
        upd_ref, state_ref = opt_ref.update(g, state_ref, params_ref)
        upd_new, state_new = opt_new.update(g, state_new, params_new)
        params_ref = optax.apply_updates(params_ref, upd_ref)
        params_new = optax.apply_updates(params_new, upd_new)
    for leaf_ref, leaf_new in zip(jax.tree_util.tree_leaves(params_ref),
                                  jax.tree_util.tree_leaves(params_new)):
        np.testing.assert_allclose(leaf_ref, leaf_new, rtol=1e-6, atol=1e-7)
    # same state tree structure/field names -> checkpoint-compatible
    assert (jax.tree_util.tree_structure(state_ref)
            == jax.tree_util.tree_structure(state_new))


def test_narrowed_moments_store_bf16_and_track_fp32():
    """bf16 mu+nu storage: state leaves are bf16, the trajectory stays
    within bf16 rounding of the fp32-moment trajectory."""
    params_ref = _params()
    params_new = _params()
    opt_ref = optax.adam(1e-3)
    opt_new = adam_dtypes.adam(1e-3, mu_dtype=jnp.bfloat16,
                               nu_dtype=jnp.bfloat16)
    state_ref = opt_ref.init(params_ref)
    state_new = opt_new.init(params_new)
    for field in ('mu', 'nu'):
        for leaf in jax.tree_util.tree_leaves(
                getattr(state_new[0], field)):
            assert leaf.dtype == jnp.bfloat16
    for step in range(10):
        g = _grads(step)
        upd_ref, state_ref = opt_ref.update(g, state_ref, params_ref)
        upd_new, state_new = opt_new.update(g, state_new, params_new)
        params_ref = optax.apply_updates(params_ref, upd_ref)
        params_new = optax.apply_updates(params_new, upd_new)
    for field in ('mu', 'nu'):
        for leaf in jax.tree_util.tree_leaves(
                getattr(state_new[0], field)):
            assert leaf.dtype == jnp.bfloat16
    # bf16 has ~3 decimal digits; after 10 steps of lr=1e-3 updates the
    # drift must stay at bf16-rounding scale, not blow up
    for leaf_ref, leaf_new in zip(jax.tree_util.tree_leaves(params_ref),
                                  jax.tree_util.tree_leaves(params_new)):
        np.testing.assert_allclose(np.asarray(leaf_ref),
                                   np.asarray(leaf_new),
                                   rtol=0.05, atol=5e-4)


def test_update_math_is_fp32_despite_bf16_storage():
    """The sqrt denominator must be formed from an fp32 upcast: feeding a
    gradient whose square underflows bf16 (but not fp32) must still move
    the parameter by a finite, fp32-accurate amount."""
    params = {'w': jnp.zeros((4,), jnp.float32)}
    opt = adam_dtypes.adam(1e-3, mu_dtype=jnp.bfloat16,
                           nu_dtype=jnp.bfloat16)
    state = opt.init(params)
    g = {'w': jnp.full((4,), 1e-3, jnp.float32)}
    upd, state = opt.update(g, state, params)
    # first-step Adam update is ~ -lr * sign(g) regardless of magnitude
    np.testing.assert_allclose(np.asarray(upd['w']),
                               -1e-3 * np.ones(4), rtol=1e-2)
    assert np.all(np.isfinite(np.asarray(upd['w'])))


def test_bf16_grads_keep_fp32_moment_math():
    """With bf16 gradients and bf16-stored moments, the nu EMA must not
    accumulate in bf16: a (1-b2)*g^2 increment ~1e-3 of nu is below bf16
    epsilon and would be silently dropped, freezing nu. Feed constant
    grads: after N steps nu must track the fp32-reference within rounding
    instead of sticking at its first value."""
    params = {'w': jnp.zeros((8,), jnp.float32)}
    opt = adam_dtypes.adam(1e-3, mu_dtype=jnp.bfloat16,
                           nu_dtype=jnp.bfloat16)
    state = opt.init(params)
    g32 = jnp.full((8,), 0.5, jnp.float32)
    g = {'w': g32.astype(jnp.bfloat16)}
    for _ in range(20):
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
    # fp32 EMA reference after 20 steps of constant g
    nu_ref = float(0.25 * (1 - 0.999 ** 20))
    nu_got = float(np.asarray(state[0].nu['w'].astype(jnp.float32))[0])
    # one bf16 rounding per step compounds; 2% tolerance catches the
    # bf16-EMA failure mode (nu stuck ~16x low) without flaking
    assert abs(nu_got - nu_ref) / nu_ref < 0.02


def test_trainer_bf16_grads_path():
    """GRADS_DTYPE='bfloat16' threads through the Trainer: the step runs,
    params stay fp32 masters, and the loss matches the fp32-grads step
    within bf16 grad-rounding tolerance. COMPUTE_DTYPE is bf16 — the only
    combination verify() allows, and the one where the forward is
    bit-identical between the two arms."""
    shapes = benchlib.SMOKE_SHAPES
    losses = {}
    for grads_dtype in ('float32', 'bfloat16'):
        config = benchlib.headline_config(
            shapes, COMPUTE_DTYPE='bfloat16', GRADS_DTYPE=grads_dtype)
        config.verify()
        trainer, state = benchlib.build_trainer(config, shapes)
        feeds = benchlib.staged(trainer, benchlib.random_batches(shapes, 2))
        for i in range(3):
            state, loss = trainer.train_step_placed(
                state, feeds[i % len(feeds)])
        losses[grads_dtype] = float(loss)
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert leaf.dtype == jnp.float32
    # identical forward; grads differ only by one bf16 rounding, so after
    # 3 steps the trajectories must still agree to ~1e-2
    assert abs(losses['float32'] - losses['bfloat16']) \
        / max(abs(losses['float32']), 1e-6) < 0.02


def test_trainer_bf16_grads_differentiates_bf16_params():
    """The mechanism, not just the trajectory: under GRADS_DTYPE='bfloat16'
    the loss must be differentiated wrt PRE-CAST bf16 params (that is what
    makes the cotangents — and the table-grad scatters — bf16 in HBM). A
    regression that drops cast_for_grads would still pass the
    loss-proximity test above; this one records the param dtype the loss
    actually sees during tracing."""
    shapes = benchlib.SMOKE_SHAPES
    config = benchlib.headline_config(
        shapes, COMPUTE_DTYPE='bfloat16', GRADS_DTYPE='bfloat16')
    config.verify()
    trainer, state = benchlib.build_trainer(config, shapes)
    seen = []
    orig_loss_fn = trainer.backend.loss_fn

    def spy_loss_fn(params, arrays, dropout_rng, mesh=None):
        seen.append(params.token_embedding.dtype)
        return orig_loss_fn(params, arrays, dropout_rng, mesh=mesh)

    trainer.backend.loss_fn = spy_loss_fn
    trainer._build_steps()  # re-trace with the spy in place
    feeds = benchlib.staged(trainer, benchlib.random_batches(shapes, 1))
    trainer.train_step_placed(state, feeds[0])
    assert seen and all(dt == jnp.bfloat16 for dt in seen)


def test_bf16_mu_adam_trains():
    """ADAM_MU_DTYPE='bfloat16' stores the first moment in bf16 and still
    reduces the loss; the second moment is PINNED fp32 here, and
    checkpoint restore targets carry the same dtypes."""
    shapes = benchlib.SMOKE_SHAPES
    config = benchlib.headline_config(
        shapes, COMPUTE_DTYPE='float32', LEARNING_RATE=0.01,
        ADAM_MU_DTYPE='bfloat16', ADAM_NU_DTYPE='float32')
    trainer, state = benchlib.build_trainer(config, shapes)
    for field, dtype in (('mu', jnp.bfloat16), ('nu', jnp.float32)):
        assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(
            getattr(state.opt_state[0], field))} == {np.dtype(dtype)}

    feed = benchlib.staged(trainer, benchlib.random_batches(shapes, 1))[0]
    state, loss0 = trainer.train_step_placed(state, feed)
    loss = loss0
    for _ in range(20):
        state, loss = trainer.train_step_placed(state, feed)
    assert float(loss) < float(loss0)

    # resume consistency: abstract_state derives from the configured
    # optimizer, so the restore target must be bf16-mu too
    _, abstract_opt = trainer.abstract_state()
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        abstract_opt[0].mu)} == {np.dtype(jnp.bfloat16)}


def test_grads_dtype_rejects_fp32_compute():
    """bf16 grads require bf16 compute: under fp32 compute the pre-cast
    would silently bf16-round every weight in the training forward while
    eval uses the uncast params (code-review r5 finding)."""
    config = benchlib.headline_config(
        benchlib.SMOKE_SHAPES, COMPUTE_DTYPE='float32',
        GRADS_DTYPE='bfloat16')
    with pytest.raises(ValueError, match="COMPUTE_DTYPE"):
        config.verify()


@pytest.mark.parametrize('nu_dtype', ['float32', 'bfloat16'])
def test_trainer_consumes_adam_nu_dtype(nu_dtype):
    """Config.ADAM_NU_DTYPE threads through Trainer: the live opt_state's
    nu leaves carry the configured dtype and a train step runs."""
    shapes = benchlib.SMOKE_SHAPES
    config = benchlib.headline_config(
        shapes, COMPUTE_DTYPE='float32', ADAM_NU_DTYPE=nu_dtype)
    trainer, state = benchlib.build_trainer(config, shapes)
    nu = state.opt_state[0].nu
    want = jnp.bfloat16 if nu_dtype == 'bfloat16' else jnp.float32
    for leaf in jax.tree_util.tree_leaves(nu):
        assert leaf.dtype == want
    feeds = benchlib.staged(trainer, benchlib.random_batches(shapes, 1))
    state2, loss = trainer.train_step_placed(state, feeds[0])
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(state2.opt_state[0].nu):
        assert leaf.dtype == want
