"""reference.py against the program's models/functional.py at toy width on
the CPU, in float32: two independent writings of the same equations."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from code2vec_tpu.models import functional

SIZES = dict(token_vocab_size=40, path_vocab_size=24, target_vocab_size=16,
             token_dim=4, path_dim=6, code_dim=8)
N_TAGS = 13     # the last rows of the tag table are padding


def toy_batch(rng, batch=5, contexts=7):
    source = rng.integers(1, 40, (batch, contexts)).astype(np.int32)
    path = rng.integers(1, 24, (batch, contexts)).astype(np.int32)
    target = rng.integers(1, 40, (batch, contexts)).astype(np.int32)
    valid = rng.random((batch, contexts)) < 0.7
    valid[:, 0] = True
    source, path, target = (np.where(valid, a, 0)
                            for a in (source, path, target))
    label = rng.integers(1, N_TAGS, (batch,)).astype(np.int32)
    return source, path, target, valid, label


def test_forward_loss_and_top_k_agree_with_the_program():
    params = functional.init_params(jax.random.PRNGKey(0), **SIZES)
    tables = reference.Tables(
        value_vocab=params.token_embedding, path_vocab=params.path_embedding,
        tags_vocab=params.target_embedding, w=params.transform,
        a=params.attention, n_tags=N_TAGS)
    source, path, target, valid, label = toy_batch(np.random.default_rng(2))
    mask = valid.astype(np.float32)

    code, attention = functional.encode(params, source, path, target, mask,
                                        dtype=jnp.float32)
    logits = functional.compute_logits(params, code, dtype=jnp.float32,
                                       num_valid_targets=N_TAGS)
    ce_sum, weight_sum = functional.weighted_ce_sums(
        logits, label, jnp.ones((label.shape[0],)))

    v, alpha, ref_logits = reference.forward(tables, source, path, target,
                                             valid)
    np.testing.assert_allclose(v, code, atol=1e-6)
    np.testing.assert_allclose(alpha, attention, atol=1e-6)
    np.testing.assert_allclose(ref_logits[:, :N_TAGS], logits[:, :N_TAGS],
                               atol=1e-6)
    assert np.isneginf(np.asarray(ref_logits[:, N_TAGS:])).all()
    assert float(alpha[~valid].sum()) == 0.0
    np.testing.assert_allclose(
        reference.loss(tables, source, path, target, valid, label),
        ce_sum / weight_sum, atol=1e-6)

    values, indices, scores = reference.top_k(ref_logits, 3)
    want_values, want_indices = jax.lax.top_k(logits, 3)
    np.testing.assert_array_equal(indices, want_indices)
    np.testing.assert_allclose(scores, jax.nn.softmax(want_values, axis=-1),
                               atol=1e-6)


def test_parse_lines_by_its_own_rules():
    token = {'a': 1, 'b': 2}
    path = {'p': 1}
    tag = {'get|x': 3}
    oov = {'token': 0, 'path': 0, 'tag': 0}
    parsed = reference.parse_lines(
        ['get|x a,p,b  b,p,zzz c,q,a', 'unknown a,p,a'], token, path, tag,
        oov, max_contexts=3)
    # the doubled space is an empty slot, which still occupies slot 1; the
    # fourth part is beyond max_contexts
    assert parsed.valid.tolist() == [[True, False, True],
                                     [True, False, False]]
    assert parsed.source[0].tolist() == [1, 0, 2]
    assert parsed.target[0].tolist() == [2, 0, 0]       # zzz is unknown
    assert parsed.label.tolist() == [3, 0]
    assert parsed.contexts[0] == [('a', 'p', 'b'), ('b', 'p', 'zzz')]
