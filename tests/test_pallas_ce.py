"""Fused softmax-CE kernel (ops/pallas_ce.py) vs the jnp reference path,
in interpreter mode on CPU: forward values, both gradients, vocab padding
masks, and the loss_and_aux integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import functional
from code2vec_tpu.ops import pallas_ce

# forced kernels reached through functional/Trainer carry no per-call
# interpret flag: the fixture turns the interpreter on for this module
pytestmark = pytest.mark.usefixtures('pallas_interpret')


def _case(rng, batch=16, dim=8, vocab=40, num_valid=None):
    code = rng.normal(size=(batch, dim)).astype(np.float32)
    w = rng.normal(size=(vocab, dim)).astype(np.float32)
    label = rng.integers(0, num_valid or vocab, (batch,)).astype(np.int32)
    weight = (rng.random(batch) > 0.2).astype(np.float32)
    return (jnp.asarray(code), jnp.asarray(w), jnp.asarray(label),
            jnp.asarray(weight))


def _reference(code, w, label, weight, num_valid, dtype=jnp.float32):
    params = functional.Code2VecParams(
        token_embedding=None, path_embedding=None, target_embedding=w,
        transform=None, attention=None)
    logits = functional.compute_logits(params, code, dtype=dtype,
                                       num_valid_targets=num_valid)
    return functional.weighted_ce_sums(logits, label, weight)


@pytest.mark.parametrize('num_valid', [40, 33])
def test_forward_matches_reference(num_valid):
    code, w, label, weight = _case(np.random.default_rng(0),
                                   num_valid=num_valid)
    want_ce, want_w = _reference(code, w, label, weight, num_valid)
    got_ce, got_w = pallas_ce.fused_weighted_ce_sums(
        w, code, label, weight, num_valid, interpret=True)
    np.testing.assert_allclose(float(got_ce), float(want_ce), rtol=1e-5)
    np.testing.assert_allclose(float(got_w), float(want_w))


@pytest.mark.parametrize('num_valid', [40, 33])
def test_gradients_match_reference(num_valid):
    code, w, label, weight = _case(np.random.default_rng(1),
                                   num_valid=num_valid)

    def ref_loss(c, t):
        ce_sum, w_sum = _reference(c, t, label, weight, num_valid)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    def fused_loss(c, t):
        ce_sum, w_sum = pallas_ce.fused_weighted_ce_sums(
            t, c, label, weight, num_valid, interpret=True)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    want_dc, want_dw = jax.grad(ref_loss, argnums=(0, 1))(code, w)
    got_dc, got_dw = jax.grad(fused_loss, argnums=(0, 1))(code, w)
    np.testing.assert_allclose(np.asarray(got_dc), np.asarray(want_dc),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw),
                               rtol=1e-4, atol=1e-6)


def test_vocab_padding_to_tile_multiple():
    """Vocab far below one VOCAB_TILE: the pad columns must not leak into
    lse and their dW must come back exactly zero-shaped (w's own shape)."""
    code, w, label, weight = _case(np.random.default_rng(2), vocab=40)
    got_ce, _ = pallas_ce.fused_weighted_ce_sums(
        w, code, label, weight, 40, interpret=True)
    want_ce, _ = _reference(code, w, label, weight, 40)
    np.testing.assert_allclose(float(got_ce), float(want_ce), rtol=1e-5)

    dw = jax.grad(lambda t: pallas_ce.fused_weighted_ce_sums(
        t, code, label, weight, 40, interpret=True)[0])(w)
    assert dw.shape == w.shape


def test_online_lse_across_many_blocks(monkeypatch):
    """Force multiple grid steps (tiny tile) so the online max/sumexp
    rescaling actually runs, with adversarial magnitude jumps between
    blocks."""
    monkeypatch.setattr(pallas_ce, 'VOCAB_TILE', 8)
    rng = np.random.default_rng(3)
    code, w, label, weight = _case(rng, vocab=64)
    # scale blocks very differently so the running max moves mid-stream
    scales = np.repeat([1.0, 30.0, 0.01, 10.0, 0.1, 20.0, 2.0, 5.0], 8)
    w = jnp.asarray(np.asarray(w) * scales[:, None])
    want_ce, _ = _reference(code, w, label, weight, 64)
    got_ce, _ = pallas_ce.fused_weighted_ce_sums(
        w, code, label, weight, 64, interpret=True)
    np.testing.assert_allclose(float(got_ce), float(want_ce), rtol=1e-5)


def test_loss_and_aux_integration():
    """loss_and_aux(use_fused_ce=True) equals the default path bit-close
    on the same inputs."""
    rng = np.random.default_rng(4)
    B, C, Vt, Vp, Vy, d, D = 8, 6, 30, 10, 20, 4, 12
    params = functional.init_params(
        jax.random.PRNGKey(0), token_vocab_size=Vt, path_vocab_size=Vp,
        target_vocab_size=Vy, token_dim=d, path_dim=d, code_dim=D)
    source = jnp.asarray(rng.integers(1, Vt, (B, C)).astype(np.int32))
    path = jnp.asarray(rng.integers(1, Vp, (B, C)).astype(np.int32))
    target = jnp.asarray(rng.integers(1, Vt, (B, C)).astype(np.int32))
    mask = jnp.ones((B, C), jnp.float32)
    label = jnp.asarray(rng.integers(1, Vy, (B,)).astype(np.int32))
    weight = jnp.ones((B,), jnp.float32)

    want, _ = functional.loss_and_aux(params, source, path, target, mask,
                                      label, weight, num_valid_targets=Vy)
    got, _ = functional.loss_and_aux(params, source, path, target, mask,
                                     label, weight, num_valid_targets=Vy,
                                     use_fused_ce=True)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    want_g = jax.grad(lambda p: functional.loss_and_aux(
        p, source, path, target, mask, label, weight,
        num_valid_targets=Vy)[0])(params)
    got_g = jax.grad(lambda p: functional.loss_and_aux(
        p, source, path, target, mask, label, weight,
        num_valid_targets=Vy, use_fused_ce=True)[0])(params)
    for name in ('target_embedding', 'transform', 'token_embedding'):
        np.testing.assert_allclose(
            np.asarray(getattr(got_g, name)),
            np.asarray(getattr(want_g, name)), rtol=1e-4, atol=1e-6)


def test_full_train_step_with_fused_ce():
    """A jitted Trainer step with USE_PALLAS_FUSED_CE produces the same
    losses as the default path (interpreter mode on CPU) — the kernel
    composes with donation, optimizer update, and the trainer jit."""
    from tests.test_sharding import _run_steps, _trainer

    one_device = dict(MESH_DEVICE_INDICES='0')
    _, dense = _run_steps(_trainer(1, 1, **one_device), n=2)
    _, fused = _run_steps(
        _trainer(1, 1, USE_PALLAS_FUSED_CE=True, **one_device), n=2)
    np.testing.assert_allclose(fused, dense, rtol=1e-5)


@pytest.mark.parametrize('num_valid', [64, 50, 20])
def test_sharded_matches_reference(monkeypatch, num_valid):
    """The shard_mapped kernel on a (4, 2) mesh: row-sharded table,
    batch-sharded code, online stats merged over the model axis. num_valid
    50 cuts mid-shard; 20 < V/m = 32 leaves shard 1 with zero valid rows
    (the degenerate-shard underflow path)."""
    from code2vec_tpu.parallel import mesh as mesh_lib
    from tests.test_sharding import _config

    monkeypatch.setattr(pallas_ce, 'VOCAB_TILE', 8)
    mesh = mesh_lib.create_mesh(_config(4, 2))
    code, w, label, weight = _case(np.random.default_rng(5), vocab=64,
                                   num_valid=num_valid)
    want_ce, want_w = _reference(code, w, label, weight, num_valid)
    got_ce, got_w = pallas_ce.sharded_fused_weighted_ce_sums(
        w, code, label, weight, num_valid, mesh, interpret=True)
    np.testing.assert_allclose(float(got_ce), float(want_ce), rtol=1e-5)
    np.testing.assert_allclose(float(got_w), float(want_w))

    def ref_loss(c, t):
        ce_sum, w_sum = _reference(c, t, label, weight, num_valid)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    def fused_loss(c, t):
        ce_sum, w_sum = pallas_ce.sharded_fused_weighted_ce_sums(
            t, c, label, weight, num_valid, mesh, interpret=True)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    want_dc, want_dw = jax.grad(ref_loss, argnums=(0, 1))(code, w)
    got_dc, got_dw = jax.grad(fused_loss, argnums=(0, 1))(code, w)
    np.testing.assert_allclose(np.asarray(got_dc), np.asarray(want_dc),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw),
                               rtol=1e-4, atol=1e-6)


def test_sharded_non_tile_multiple_shards(monkeypatch):
    """Per-shard rows NOT a VOCAB_TILE multiple (vshard=20, tile=8): the
    kernel pads each shard's block to 24 columns, so a neighbor shard's
    VALID weight-1 label (e.g. global 21 on shard 1) collides with shard
    0's pad window [20, 24) — the forward pick must gate that match out
    (regression: ungated, shard 0 psums the -1e30 sentinel into picked
    and the loss explodes)."""
    from code2vec_tpu.parallel import mesh as mesh_lib
    from tests.test_sharding import _config

    monkeypatch.setattr(pallas_ce, 'VOCAB_TILE', 8)
    mesh = mesh_lib.create_mesh(_config(4, 2))
    rng = np.random.default_rng(8)
    code, w, _, _ = _case(rng, vocab=40)
    # every global label index appears somewhere; all rows carry weight 1
    label = jnp.asarray((np.arange(16) + 14) % 40, dtype=jnp.int32)
    weight = jnp.ones((16,), jnp.float32)
    want_ce, _ = _reference(code, w, label, weight, 40)
    got_ce, _ = pallas_ce.sharded_fused_weighted_ce_sums(
        w, code, label, weight, 40, mesh, interpret=True)
    np.testing.assert_allclose(float(got_ce), float(want_ce), rtol=1e-5)

    def fused_loss(c, t):
        ce_sum, w_sum = pallas_ce.sharded_fused_weighted_ce_sums(
            t, c, label, weight, 40, mesh, interpret=True)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    def ref_loss(c, t):
        ce_sum, w_sum = _reference(c, t, label, weight, 40)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    want_dc, want_dw = jax.grad(ref_loss, argnums=(0, 1))(code, w)
    got_dc, got_dw = jax.grad(fused_loss, argnums=(0, 1))(code, w)
    np.testing.assert_allclose(np.asarray(got_dc), np.asarray(want_dc),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw),
                               rtol=1e-4, atol=1e-6)


def test_bfloat16_compute_close_to_xla_path():
    """The on-chip A/B (bench_fused_ce.py) runs the headline bfloat16
    config: the kernel's bf16 arms must track the XLA path's bf16 CE
    within bf16 tolerance, value and grads. The arms legitimately differ
    beyond rounding: compute_logits' bf16 matmul rounds its logits to
    bf16, while the kernel keeps fp32 accumulation — hence the loose
    tolerances."""
    code, w, label, weight = _case(np.random.default_rng(6), num_valid=40)

    def ref_loss(c, t):
        ce_sum, w_sum = _reference(c, t, label, weight, 40,
                                   dtype=jnp.bfloat16)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    def fused_loss(c, t):
        ce_sum, w_sum = pallas_ce.fused_weighted_ce_sums(
            t, c, label, weight, 40, dtype=jnp.bfloat16, interpret=True)
        return ce_sum / jnp.maximum(w_sum, 1.0)

    np.testing.assert_allclose(float(fused_loss(code, w)),
                               float(ref_loss(code, w)), rtol=2e-2)
    want_dc, want_dw = jax.grad(ref_loss, argnums=(0, 1))(code, w)
    got_dc, got_dw = jax.grad(fused_loss, argnums=(0, 1))(code, w)
    np.testing.assert_allclose(np.asarray(got_dc), np.asarray(want_dc),
                               rtol=5e-2, atol=5e-3)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw),
                               rtol=5e-2, atol=5e-3)


@pytest.mark.parametrize('shard_contexts', [False, True])
def test_full_train_step_with_fused_ce_on_mesh(shard_contexts):
    """End to end on the (4, 2) mesh: jitted train steps with the
    shard_mapped fused CE match the dense path's losses — the kernel
    composes with GSPMD around it (sharded tables, DP grad psum, and the
    contexts-axis sequence parallelism which also uses the model axis)."""
    from tests.test_sharding import _run_steps, _trainer

    _, dense = _run_steps(_trainer(4, 2, SHARD_CONTEXTS=shard_contexts), n=2)
    _, fused = _run_steps(_trainer(4, 2, USE_PALLAS_FUSED_CE=True,
                                   SHARD_CONTEXTS=shard_contexts), n=2)
    np.testing.assert_allclose(fused, dense, rtol=1e-5)


def test_target_table_padded_to_tile():
    """With the knob on, the target table allocation is a VOCAB_TILE
    multiple so the kernel's own pad is a no-op on the hot path."""
    from code2vec_tpu.models.backends import JaxBackend
    from code2vec_tpu.vocab import SizeOnlyVocabs
    from tests.test_sharding import _config

    config = _config(1, 1, USE_PALLAS_FUSED_CE=True, PARAM_ROW_ALIGNMENT=8)
    backend = JaxBackend(config, SizeOnlyVocabs(40, 12, 24))
    assert backend.sizes['target_vocab_size'] % pallas_ce.VOCAB_TILE == 0
    assert backend.num_valid_targets == 24


def test_vocab_tile_override_validation():
    """ADVICE r4: a bad PALLAS_CE_VOCAB_TILE must degrade to the default
    with a warning, never crash the import or silently pick an unrunnable
    tile; oversize tiles are accepted with a VMEM warning (Mosaic gives
    the real verdict)."""
    import warnings
    from code2vec_tpu.ops.pallas_ce import (_DEFAULT_VOCAB_TILE,
                                            _parse_vocab_tile)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        assert _parse_vocab_tile('abc') == _DEFAULT_VOCAB_TILE
        assert _parse_vocab_tile('100') == _DEFAULT_VOCAB_TILE
        assert _parse_vocab_tile('-256') == _DEFAULT_VOCAB_TILE
        assert _parse_vocab_tile('2048') == 2048
    assert len(caught) == 4
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        assert _parse_vocab_tile('256') == 256
        assert _parse_vocab_tile('1024') == 1024
