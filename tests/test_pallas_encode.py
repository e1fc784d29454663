"""Fused-encode Pallas kernel vs the plain jnp math (interpreter mode —
no TPU needed for correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.ops import pallas_encode

# forced kernels reached through functional/Trainer carry no per-call
# interpret flag: the fixture turns the interpreter on for this module
pytestmark = pytest.mark.usefixtures('pallas_interpret')


@pytest.mark.parametrize('n', [512, 1024, 700])  # incl. non-multiple of tile
def test_fused_matches_reference_math(n):
    rng = np.random.default_rng(0)
    token_dim, path_dim, code_dim = 16, 16, 48
    src = rng.standard_normal((n, token_dim)).astype(np.float32)
    path = rng.standard_normal((n, path_dim)).astype(np.float32)
    tgt = rng.standard_normal((n, token_dim)).astype(np.float32)
    transform = rng.standard_normal(
        (2 * token_dim + path_dim, code_dim)).astype(np.float32) * 0.1
    attention = rng.standard_normal((code_dim, 1)).astype(np.float32)

    x, scores = pallas_encode.fused_context_transform(
        src, path, tgt, transform, attention, interpret=True)

    ctx = np.concatenate([src, path, tgt], axis=1)
    ref_x = np.tanh(ctx @ transform)
    ref_scores = ref_x @ attention
    np.testing.assert_allclose(np.asarray(x), ref_x, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(scores), ref_scores, rtol=2e-5,
                               atol=1e-6)


def test_encode_with_pallas_flag_matches_plain_path():
    """``functional.encode(use_pallas=True)`` routes the kernel (here in
    the interpreter, via the module's ``pallas_interpret`` fixture) and
    matches the plain jnp path."""
    from code2vec_tpu.models import functional
    params = functional.init_params(
        jax.random.PRNGKey(0), token_vocab_size=20, path_vocab_size=10,
        target_vocab_size=8, token_dim=8, path_dim=8, code_dim=16)
    rng = np.random.default_rng(3)
    source = rng.integers(0, 20, (4, 6)).astype(np.int32)
    path = rng.integers(0, 10, (4, 6)).astype(np.int32)
    target = rng.integers(0, 20, (4, 6)).astype(np.int32)
    mask = np.ones((4, 6), np.float32)
    code_plain, attn_plain = functional.encode(
        params, source, path, target, mask)
    code_fused, attn_fused = functional.encode(
        params, source, path, target, mask, use_pallas=True)
    np.testing.assert_allclose(np.asarray(code_plain),
                               np.asarray(code_fused), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(attn_plain),
                               np.asarray(attn_fused), rtol=2e-5, atol=1e-6)


def test_fused_under_jit_composition():
    rng = np.random.default_rng(1)
    src = rng.standard_normal((256, 8)).astype(np.float32)
    path = rng.standard_normal((256, 8)).astype(np.float32)
    tgt = rng.standard_normal((256, 8)).astype(np.float32)
    transform = rng.standard_normal((24, 16)).astype(np.float32)
    attention = rng.standard_normal((16, 1)).astype(np.float32)

    @jax.jit
    def run(a, b, c):
        x, s = pallas_encode.fused_context_transform(
            a, b, c, transform, attention, interpret=True)
        return x.sum() + s.sum()

    value = float(run(src, path, tgt))
    ctx = np.concatenate([src, path, tgt], axis=1)
    ref_x = np.tanh(ctx @ transform)
    ref = ref_x.sum() + (ref_x @ attention).sum()
    np.testing.assert_allclose(value, ref, rtol=1e-4)


def test_fused_at_long_context_java14m_dims():
    """C=1024 long-context shape at the real java14m dims (d=128 each,
    code_dim=384): the kernel the watcher's pallas_c1024 stage measures
    on chip is logic-correct at exactly that row count and width — only
    the Mosaic compile/perf half stays chip-gated (VERDICT r4 weak #4)."""
    rng = np.random.default_rng(0)
    n = 4 * 1024                       # B=4 at MAX_CONTEXTS=1024
    src = rng.standard_normal((n, 128)).astype(np.float32)
    path = rng.standard_normal((n, 128)).astype(np.float32)
    tgt = rng.standard_normal((n, 128)).astype(np.float32)
    transform = (rng.standard_normal((384, 384)) * 0.05).astype(np.float32)
    attention = rng.standard_normal((384, 1)).astype(np.float32)

    x, scores = pallas_encode.fused_context_transform(
        src, path, tgt, transform, attention, interpret=True)

    ctx = np.concatenate([src, path, tgt], axis=1)
    ref_x = np.tanh(ctx @ transform)
    np.testing.assert_allclose(np.asarray(x), ref_x, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(scores), ref_x @ attention,
                               rtol=2e-4, atol=2e-5)
