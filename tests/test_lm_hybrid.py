"""A decoder of linear-attention and block-sparse layers on the serving path,
at a tiny size on the CPU, every piece against the plain float32 reference
(``chipbench/reference_minicpm_sala.py``) or a brute-force statement of it:
the chunked scan, the selection, prefill and decode through state slots,
pooled keys and chosen blocks, resident sessions, the three kinds of cache
state, and the session traffic's determinism.

Heads of 8, four layers of both kinds out of a published list of eight,
blocks of 4 positions, a window of 8, top-4, ``dense_len`` 24, seeded
weights.  ``COMPUTE_DTYPE='float32'`` is the exact mode these tests hold to
1e-4.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import controls_minicpm_sala
from chipbench import reference_minicpm_sala as ref
from chipbench.runners import serve_lm, serve_lm_sessions
from chipbench.traffic import session_turns
from code2vec_tpu import model_api
from code2vec_tpu.config import Config
from code2vec_tpu.models import families
from code2vec_tpu.models import hybrid_decoder as hybrid_lib
from code2vec_tpu.ops import linear_attention, sparse_attention
from code2vec_tpu.serving import lm_cache

L, S = hybrid_lib.LIGHTNING, hybrid_lib.SPARSE
SPARSE_CONFIG = {'kernel_size': 4, 'kernel_stride': 2, 'block_size': 4,
                 'window_size': 8, 'topk': 4, 'init_blocks': 1,
                 'dense_len': 24}
TOLERANCE = 1e-4


def tiny_config(mixers=(S, L, L, L), **overrides):
    config = {
        'attention_bias': False, 'attn_use_rope': False, 'head_dim': 8,
        'hidden_act': 'silu', 'hidden_size': 32, 'intermediate_size': 64,
        'lightning_head_dim': 8, 'lightning_nh': 4, 'lightning_nkv': 4,
        'lightning_scale': '1/sqrt(d)', 'lightning_use_rope': True,
        # a published list of eight; layers 2 .. 2 + len(mixers) - 1 run
        'mixer_types': [L, L] + list(mixers) + [S] * (6 - len(mixers)),
        'first_hidden_layer': 2, 'num_hidden_layers': len(mixers),
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'qk_norm': True, 'rms_norm_eps': 1e-6, 'vocab_size': 64,
        'rope_theta': 10000, 'scale_emb': 12, 'scale_depth': 1.4,
        'dim_model_base': 8, 'tie_word_embeddings': False,
        'use_output_gate': True, 'use_output_norm': True,
        'attn_use_output_gate': True, 'sparse_config': dict(SPARSE_CONFIG)}
    config.update(overrides)
    return config


def build(tmp_path_factory, model_config, dtype='float32', **settings):
    path = tmp_path_factory.mktemp('lmhybrid') / 'config.json'
    path.write_text(json.dumps(model_config))
    keys = dict(MODEL_FAMILY='minicpm_sala', LM_CONFIG_PATH=str(path),
                LM_PARAM_SEED=3, LM_MAX_SEQS=3, LM_PAGE_SIZE=8,
                LM_PAGE_POOL_PAGES=48, LM_MAX_CONTEXT=128,
                LM_CHUNK_BUCKETS='4,8', COMPUTE_DTYPE=dtype)
    keys.update(settings)
    model = model_api.create_model(Config(**keys))
    return model, model.serving_engine()


def reference_logits(model, model_config, history, rows):
    weights = serve_lm_sessions.reference_weights(model.params, model_config)
    return np.asarray(ref.forward(model_config, weights, history,
                                  logit_positions=rows))


def program_logits(result):
    return np.stack([np.asarray(row) for row in result.logits])


def generate(engine, prompt, new, **kw):
    return engine.submit(prompt, tier='generate', max_new_tokens=new,
                         return_logits=True, **kw).result(timeout=300)


@pytest.fixture(scope='module')
def exact(tmp_path_factory):
    config = tiny_config()
    model, engine = build(tmp_path_factory, config)
    yield model, engine, config
    engine.close()


# ------------------------------------------------------------- the seam
def test_the_family_declares_what_the_engine_needs():
    family = families.FAMILIES['minicpm_sala']
    assert family.tiers == ('generate',)
    assert family.reference == 'chipbench/reference_minicpm_sala.py'
    assert 'session' in family.input_layout
    assert families.family_of(
        Config(MODEL_FAMILY='minicpm_sala')).name == 'minicpm_sala'


def test_the_model_declares_its_parameters():
    cfg = hybrid_lib.HybridConfig.from_dict(tiny_config())
    shapes = hybrid_lib.param_shapes(cfg)
    h, ff, d = 32, 64, 8
    lightning = 4 * h * 32 + 32 * h + 3 * h * ff + 2 * h + 3 * d
    sparse = h * (2 * 32 + 2 * 16) + 32 * h + 3 * h * ff + 2 * h + 2 * d
    assert cfg.parameters() == 3 * lightning + sparse + 2 * 64 * h + h
    assert cfg.mixer_types == (S, L, L, L) and cfg.first_layer == 2
    assert cfg.published_layers == 8
    specs = families.FAMILIES['minicpm_sala'].param_specs(shapes)
    assert jax.tree_util.tree_structure(specs) \
        == jax.tree_util.tree_structure(shapes)


@pytest.mark.parametrize('key,value', [
    ('mixer_types', [L, L, 'mamba2', L, L, L, S, S]),
    ('lightning_scale', '1/d'), ('use_output_gate', False),
    ('attn_use_output_gate', False), ('use_output_norm', False),
    ('lightning_nkv', 2), ('qk_norm', False), ('attn_use_rope', True),
    ('sparse_config', dict(SPARSE_CONFIG, kernel_size=6))])
def test_only_what_is_implemented_is_accepted(key, value):
    with pytest.raises(NotImplementedError) as refused:
        cfg = hybrid_lib.HybridConfig.from_dict(tiny_config(**{key: value}))
        cfg.sparse.check(8)
    assert key in str(refused.value) or 'kernel_size' in str(refused.value)


def test_a_cut_outside_the_published_list_is_refused():
    with pytest.raises(ValueError):
        hybrid_lib.HybridConfig.from_dict(
            tiny_config(first_hidden_layer=6))


# ------------------------------------------------- the linear-attention scan
def test_decay_rates_against_their_closed_form():
    rates = linear_attention.decay_rates(32, 9, 32)
    assert rates.shape == (32,)
    np.testing.assert_allclose(rates[0],
                               2 ** (-8 / 32) * (1 - 9 / 31 + 1e-5))
    np.testing.assert_allclose(rates[31], 2 ** -8 * (1 - 9 / 31 + 1e-5))
    # the last published layer decays almost nothing
    assert linear_attention.decay_rates(32, 31, 32).max() < 1e-5


def recurrence(q, k, v, rates, state):
    """Position by position, in float64."""
    out = []
    gamma = np.exp(-rates)[:, None, None]
    for t in range(q.shape[0]):
        state = gamma * state + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum('hd,hde->he', q[t], state)
                   / np.sqrt(q.shape[-1]))
    return np.stack(out), state


@pytest.mark.parametrize('tokens,bucket,block', [
    (1, 4, 4), (7, 8, 4), (16, 16, 4), (37, 48, 16), (37, 37, 128)],
    ids=['one', 'padded', 'whole-blocks', 'several-blocks', 'one-block'])
def test_chunked_scan_equals_the_recurrence(tokens, bucket, block):
    """A chunk of ``tokens`` in a bucket of ``bucket`` (the rest padding),
    a state carried in, fast and slow heads."""
    rng = np.random.default_rng(tokens)
    heads, d = 4, 8
    q, k, v = rng.standard_normal((3, bucket, heads, d))
    rates = np.asarray([1.5, 0.3, 0.01, 1e-5])
    state = rng.standard_normal((heads, d, d))
    valid = (np.arange(bucket) < tokens).astype(np.int32)
    got, after = linear_attention.chunk_scan(
        jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
        jnp.asarray(v, jnp.float32), jnp.asarray(rates, jnp.float32),
        jnp.asarray(valid), jnp.asarray(state, jnp.float32), block=block)
    want, want_after = recurrence(q[:tokens], k[:tokens], v[:tokens], rates,
                                  state)
    np.testing.assert_allclose(np.asarray(got)[:tokens], want, atol=2e-5)
    # the padding neither decayed the state nor added to it
    np.testing.assert_allclose(np.asarray(after), want_after, atol=2e-5)


def test_two_chunks_and_a_decode_step_carry_one_state():
    rng = np.random.default_rng(0)
    heads, d = 4, 8
    q, k, v = rng.standard_normal((3, 21, heads, d)).astype(np.float32)
    rates = jnp.asarray([0.8, 0.1, 0.02, 0.001], jnp.float32)
    ones = jnp.ones((8,), jnp.int32)
    state = jnp.zeros((heads, d, d), jnp.float32)
    first, state = linear_attention.chunk_scan(
        q[:8], k[:8], v[:8], rates, ones, state, block=4)
    second, state = linear_attention.chunk_scan(
        jnp.pad(q[8:20], ((0, 4), (0, 0), (0, 0))),
        jnp.pad(k[8:20], ((0, 4), (0, 0), (0, 0))),
        jnp.pad(v[8:20], ((0, 4), (0, 0), (0, 0))), rates,
        jnp.asarray(np.arange(16) < 12, jnp.int32), state, block=4)
    rows = jnp.stack([state, state + 7.0])
    last, rows = linear_attention.decode_update(
        jnp.stack([q[20], q[20]]), jnp.stack([k[20], k[20]]),
        jnp.stack([v[20], v[20]]), rates, jnp.asarray([1, 0]), rows)
    want, want_state = recurrence(q, k, v, np.asarray(rates),
                                  np.zeros((heads, d, d)))
    np.testing.assert_allclose(
        np.concatenate([first, np.asarray(second)[:12], last[:1]]), want,
        atol=2e-5)
    np.testing.assert_allclose(rows[0], want_state, atol=2e-5)
    # a row that holds no sequence leaves its state as it was
    np.testing.assert_array_equal(rows[1], np.asarray(state) + 7.0)


# ------------------------------------------------------------ the selection
GEO = sparse_attention.SparseGeometry(**SPARSE_CONFIG)


def brute_force_blocks(q, k, i, geo, kv_heads):
    """The blocks query ``q`` [heads, d] at position ``i`` chooses over keys
    ``k`` [i + 1 .., kv_heads, d], straight from the statement."""
    heads, d = q.shape
    group = heads // kv_heads
    n_blocks = i // geo.block_size + 1
    chosen = []
    for g in range(kv_heads):
        pooled = [j for j in range(10 ** 6)
                  if geo.kernel_stride * j + geo.kernel_size - 1 <= i]
        mass = np.zeros(len(pooled))
        for h in range(g * group, (g + 1) * group):
            scores = np.asarray([
                q[h] @ k[geo.kernel_stride * j:
                         geo.kernel_stride * j + geo.kernel_size, g].mean(0)
                for j in pooled]) / np.sqrt(d)
            if len(pooled):
                e = np.exp(scores - scores.max())
                mass += e / e.sum()
        r = geo.block_size // geo.kernel_stride
        score = np.full(n_blocks, -1.0)
        for b in range(n_blocks):
            near = [mass[j] for j in range(r * b - 1, r * b + r)
                    if 0 <= j < len(pooled)]
            if near:
                score[b] = max(near)
            if b < geo.init_blocks or \
                    b >= max(i - geo.window_size + 1, 0) // geo.block_size:
                score[b] = np.inf
        order = np.argsort(-score, kind='stable')[:geo.topk]
        chosen.append(sorted(int(b) for b in order))
    return chosen


@pytest.mark.parametrize('i', [2, 3, 4, 11, 30, 31, 57, 70])
def test_selection_equals_its_brute_force_statement(i):
    """Positions where no pooled key is whole yet (2), where the newest
    pooled key's window has just been completed (3, 11, 31) or not quite
    (4, 30), and long ones where scored blocks compete (57, 70)."""
    rng = np.random.default_rng(i)
    heads, kv_heads, d, n = 4, 2, 8, 72
    q = rng.standard_normal((heads, d)).astype(np.float32)
    k = rng.standard_normal((n, kv_heads, d)).astype(np.float32)
    stride = GEO.kernel_stride
    means = k.reshape(n // stride, stride, kv_heads, d).mean(1)
    # strides the query cannot have: poisoned, they must never be read
    means[(i + 1) // stride:] = 1e6
    scores = sparse_attention.block_scores(
        jnp.asarray(q)[None], jnp.asarray([i]), jnp.asarray(means), GEO)
    chosen = np.asarray(sparse_attention.choose(scores, GEO.topk))[0]
    want = brute_force_blocks(q, k, i, GEO, kv_heads)
    for g in range(kv_heads):
        assert sorted(np.flatnonzero(chosen[g])) == want[g]
    index, held = sparse_attention.listed(jnp.asarray(chosen), GEO.topk)
    for g in range(kv_heads):
        assert sorted(np.asarray(index)[g][np.asarray(held)[g]]) == want[g]


def test_the_choice_breaks_ties_towards_the_lower_block_and_is_exact():
    scores = jnp.asarray([
        [0.5, 0.25, 0.5, -np.inf, 0.5, 0.125, 0.5, np.inf],
        [np.inf, 1.0, 1.0, 1.0, 1.0, 1.0, -1.0, -np.inf],
        [-1.0, -1.0, -1.0, -1.0, -np.inf, -np.inf, -np.inf, -np.inf],
        [3.0, 2.0, -np.inf, -np.inf, -np.inf, -np.inf, -np.inf, -np.inf]],
        jnp.float32)
    chosen = np.asarray(sparse_attention.choose(scores, 4))
    assert [list(np.flatnonzero(row)) for row in chosen] == [
        [0, 2, 4, 7], [0, 1, 2, 3], [0, 1, 2, 3], [0, 1]]
    rng = np.random.default_rng(1)
    # many near-ties: against a stable sort
    scores = np.round(rng.random((64, 2, 300)), 2).astype(np.float32)
    chosen = np.asarray(sparse_attention.choose(jnp.asarray(scores), 64))
    order = np.argsort(-scores, axis=-1, kind='stable')[..., :64]
    want = np.zeros_like(chosen)
    np.put_along_axis(want, order, True, axis=-1)
    np.testing.assert_array_equal(chosen, want)


# ---------------------------------------------------- against the reference
@pytest.mark.parametrize('kinds', [(L, L), (S, S)],
                         ids=['lightning-alone', 'sparse-alone'])
def test_each_layer_kind_alone(tmp_path_factory, kinds):
    config = tiny_config(kinds)
    model, engine = build(tmp_path_factory, config)
    try:
        prompt = np.random.default_rng(1).integers(0, 64, 41)
        result = generate(engine, prompt, 5)
    finally:
        engine.close()
    history = np.concatenate([prompt, result.token_ids[:-1]])
    np.testing.assert_allclose(
        program_logits(result),
        reference_logits(model, config, history, 40 + np.arange(5)),
        atol=TOLERANCE)


@pytest.mark.parametrize('length,new', [
    (5, 3), (20, 9), (24, 1), (23, 4), (37, 12), (64, 9), (1, 3)],
    ids=['short', 'dense-into-sparse', 'last-dense-query', 'across-dense-len',
         'sparse', 'several-pages', 'one-token-in'])
def test_prefill_then_decode_equals_the_full_forward_pass(exact, length, new):
    """Chunks of 8 and 4 over pages of 8 and blocks of 4; contexts on each
    side of ``dense_len`` (24), a chunk and a decode that cross it, pooled
    keys completed in prefill and in decode."""
    model, engine, config = exact
    prompt = np.random.default_rng(length).integers(0, 64, length)
    result = generate(engine, prompt, new)
    assert result.token_ids.shape == (new,)
    history = np.concatenate([prompt, result.token_ids[:-1]])
    want = reference_logits(model, config, history,
                            length - 1 + np.arange(new))
    np.testing.assert_allclose(program_logits(result), want, atol=TOLERANCE)
    np.testing.assert_array_equal(result.token_ids, want.argmax(-1))


def test_a_turn_of_a_resident_session_equals_one_request_over_the_history(
        exact):
    """Three turns: the second and third start at the session's end, feed
    the token the turn before generated last, and agree with the reference
    over the joined history and with ONE request that carries it whole."""
    model, engine, config = exact
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, n) for n in (19, 13, 9)]
    news = (4, 5, 3)
    results = [generate(engine, prompt, new, session='joined')
               for prompt, new in zip(prompts, news)]
    lm = engine.stats()['lm']
    assert lm['sessions_resident'] == 1 and lm['state_pool_fill'] > 0
    parts = [x for prompt, result in zip(prompts, results)
             for x in (prompt, result.token_ids)]
    history = np.concatenate(parts)[:-1]
    at = 0
    for prompt, new, result in zip(prompts, news, results):
        rows = at + len(prompt) - 1 + np.arange(new)
        np.testing.assert_allclose(
            program_logits(result),
            reference_logits(model, config, history, rows), atol=TOLERANCE)
        at += len(prompt) + new
    assert engine.close_session('joined') is True
    assert engine.close_session('joined') is False
    whole = generate(engine, history[:at - news[-1]], news[-1])
    np.testing.assert_allclose(program_logits(whole),
                               program_logits(results[-1]), atol=1e-5)
    np.testing.assert_array_equal(whole.token_ids, results[-1].token_ids)
    lm = engine.stats()['lm']
    assert lm['sessions_resident'] == 0
    assert lm['state_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0


def test_the_tolerance_tells_a_float32_state_from_one_rounded_to_bfloat16(
        exact):
    """Between two turns the recurrent states are rounded to bfloat16 and
    back, as a cache that kept them in bfloat16 would hand them on: the
    next turn's logits leave the written tolerance, which the same turns
    with the state left alone hold."""
    model, engine, config = exact
    rng = np.random.default_rng(5)
    first, second = rng.integers(0, 64, 30), rng.integers(0, 64, 6)
    errors = {}
    for name in ('float32', 'bfloat16'):
        one = generate(engine, first, 3, session=name)
        if name == 'bfloat16':
            cache = engine.lm_runtime().cache      # idle: no step in flight
            cache['states'] = cache['states'].astype(
                jnp.bfloat16).astype(jnp.float32)
        two = generate(engine, second, 4, session=name)
        engine.close_session(name)
        history = np.concatenate([first, one.token_ids, second,
                                  two.token_ids])[:-1]
        rows = len(first) + 3 + len(second) - 1 + np.arange(4)
        errors[name] = np.abs(
            program_logits(two)
            - reference_logits(model, config, history, rows)).max()
    assert errors['float32'] <= TOLERANCE < errors['bfloat16']


@pytest.fixture(scope='module')
def rehearsal_turns(tmp_path_factory):
    """The committed configuration at its ``rehearsal`` widths, in the
    exact mode: a resident session of 150 positions takes two turns, as the
    cell's check reads them.  (the model's config, its weights, the
    session's history, the rows of it the turns' logits are at, the timed
    path's logits, the file's written tolerance)."""
    import os
    import types
    from chipbench import run
    from chipbench.runners import common
    with open(os.path.join(os.path.dirname(__file__), '..', 'chipbench',
                           'configs', 'minicpm-sala-9b-l16.json')) as f:
        published = json.load(f)
    spec = run.merged(published, published['rehearsal'])
    config = {k: spec[k] for k in serve_lm_sessions.MODEL_KEYS if k in spec}
    path = tmp_path_factory.mktemp('controls') / 'config.json'
    path.write_text(json.dumps(config))
    ctx = types.SimpleNamespace(
        settings=dict(spec['settings'], COMPUTE_DTYPE='float32'),
        cell=types.SimpleNamespace(config_name='minicpm-sala-9b-l16'))
    model = model_api.create_model(common.make_config(
        ctx, LM_CONFIG_PATH=str(path), LM_PARAM_SEED=5, VERBOSE_MODE=0))
    rng = np.random.default_rng(5)
    context = rng.integers(0, config['vocab_size'], 150)
    parts, rows, got = [], [], []
    with model.serving_engine() as engine:
        parts += [context, generate(engine, context, 1,
                                    session='s').token_ids]
        for length in (20, 13):
            prompt = rng.integers(0, config['vocab_size'], length)
            at = sum(len(part) for part in parts)
            result = generate(engine, prompt, 16, session='s')
            rows.append(at + length - 1 + np.arange(16))
            got.append(program_logits(result))
            parts += [prompt, result.token_ids]
    return (config, model.params, np.concatenate(parts)[:-1],
            np.concatenate(rows), np.concatenate(got),
            published['check']['tolerance'])


#: what the exact mode is held to: it reads under 1e-4 of the logits' spread
EXACT = {'relative_error': 1e-3, 'share_beyond': 0.0,
         'relative_error_cap': 1e-3}


def test_the_reference_proper_is_held_by_both_tolerances(rehearsal_turns):
    config, params, history, rows, got, written = rehearsal_turns
    want = controls_minicpm_sala.references(config, params)['reference'](
        history, rows)
    errors = serve_lm.compare_logits(got, want)
    assert serve_lm.judge(errors, EXACT) == []
    assert serve_lm.judge(errors, written) == []
    assert written['relative_error_cap'] < 0.35     # under the controls'


@pytest.mark.parametrize('control,by_the_written_one', [
    ('float8_weights', True), ('no_forced_blocks', True),
    ('bf16_state', False)])
def test_each_control_comes_out_not_correct(rehearsal_turns, control,
                                            by_the_written_one):
    """The reference with one thing wrong (``chipbench/
    controls_minicpm_sala.py``, the chip's control entry) through the
    cell's judge.  Matrices rounded to float8's mantissa and a selection
    without its forced blocks break the file's written tolerance even at
    this size; a state rounded to bfloat16 moves 200 positions' logits by a
    hundredth of their spread, which the exact mode's tolerance refuses and
    the written one only at the cell's tens of thousands of positions
    (PERF.md, section 4)."""
    config, params, history, rows, got, written = rehearsal_turns
    wrong = controls_minicpm_sala.references(config, params)[control](
        history, rows)
    errors = serve_lm.compare_logits(got, wrong)
    assert serve_lm.judge(errors, EXACT) != []
    assert bool(serve_lm.judge(errors, written)) == by_the_written_one


def test_neighbours_joining_and_leaving_change_nothing(exact):
    model, engine, config = exact
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 64, 33)
    alone = generate(engine, prompt, 10)
    others = [engine.submit(rng.integers(0, 64, n), tier='generate',
                            max_new_tokens=m) for n, m in ((9, 2), (30, 4))]
    beside = engine.submit(prompt, tier='generate', max_new_tokens=10,
                           return_logits=True)
    others += [engine.submit(rng.integers(0, 64, n), tier='generate',
                             max_new_tokens=m)
               for n, m in ((3, 6), (17, 1), (40, 3))]
    beside = beside.result(timeout=300)
    for other in others:
        other.result(timeout=300)
    np.testing.assert_array_equal(alone.token_ids, beside.token_ids)
    np.testing.assert_allclose(program_logits(alone),
                               program_logits(beside), atol=1e-5)


def test_bfloat16_stays_close_and_is_not_the_exact_mode(tmp_path_factory):
    config = tiny_config()
    model, engine = build(tmp_path_factory, config, dtype='bfloat16')
    errors = []
    try:
        for seed, (length, new) in enumerate([(37, 12), (60, 6)]):
            prompt = np.random.default_rng(seed).integers(0, 64, length)
            result = generate(engine, prompt, new)
            history = np.concatenate([prompt, result.token_ids[:-1]])
            errors.append(serve_lm.compare_logits(
                program_logits(result),
                reference_logits(model, config, history,
                                 length - 1 + np.arange(new))))
    finally:
        engine.close()
    errors = np.concatenate(errors)
    assert 1e-4 < np.median(errors) < 0.1
    assert errors.max() < 1.0


# ------------------------------------------------------- the cache's state
def test_cache_manager_with_three_kinds_of_state():
    """Slots (a ring in one model, a recurrent state in the other), pages
    (the pooled keys ride with them), and leases kept under a session."""
    g = lm_cache.CacheGeometry.make(page_size=4, window=0, slots=2,
                                    pool_pages=10, max_context=40,
                                    max_chunk=8)
    cache = lm_cache.CacheManager(g)
    a = cache.admit(13)                         # 4 pages
    cache.keep('a', a)
    assert cache.kept('a') is a and cache.sessions_kept == 1
    assert cache.extend(a, 24) and a.pages.shape == (6,)   # two more
    assert cache.extend(a, 22) and a.pages.shape == (6,)   # nothing to add
    b = cache.admit(16)                         # the other slot, 4 pages
    assert cache.fill() == (1.0, 1.0)
    assert not cache.extend(a, 25) and cache.held_total == 1   # full
    assert cache.admit(1) is None and cache.held_total == 2    # no slot
    cache.free(b)
    assert cache.extend(a, 25) and a.pages.shape == (7,)
    assert len(set(a.pages)) == 7
    assert cache.close_session('a') and not cache.close_session('a')
    assert cache.slots_in_use == 0 and cache.pages_in_use == 0
    assert cache.sessions_kept == 0 and cache.kept('a') is None
    # no page was lost or doubled on the way
    everything = cache.admit(40)
    assert sorted(everything.pages) == list(range(10))


def test_completed_strides_name_their_rows():
    g = lm_cache.CacheGeometry.make(page_size=8, window=0, slots=2,
                                    pool_pages=10, max_context=64,
                                    max_chunk=8)
    lease = lm_cache.Lease(slot=0, pages=np.asarray([5, 2, 9], np.int32))
    # strides of 2: positions 3..9 complete the strides that end at 3, 5,
    # 7 and 9, which start at 2, 4, 6 (page 5) and 8 (page 2)
    src, dst = lm_cache.completed_strides(g, lease, 3, 7, 2)
    np.testing.assert_array_equal(src, [5 * 8 + 2, 5 * 8 + 4, 5 * 8 + 6,
                                        2 * 8 + 0])
    np.testing.assert_array_equal(dst, [5 * 4 + 1, 5 * 4 + 2, 5 * 4 + 3,
                                        2 * 4 + 0])
    src, dst = lm_cache.completed_strides(g, lease, 4, 1, 2)
    assert src.shape == (0,) and dst.shape == (0,)
    src, _ = lm_cache.completed_strides(g, lease, 5, 1, 2)
    np.testing.assert_array_equal(src, [5 * 8 + 4])


def test_a_turn_waits_for_its_own_session_and_holds_nobody_back(
        tmp_path_factory):
    """Two turns of one session submitted together: the second waits for
    the first and starts at its end; a request behind them passes.  Closing
    a session with a turn undelivered is refused; afterwards nothing is
    leaked."""
    config = tiny_config()
    model, engine = build(tmp_path_factory, config)
    try:
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 64, n) for n in (40, 7, 12)]
        before = engine.stats()['lm']
        first = engine.submit(prompts[0], tier='generate', max_new_tokens=6,
                              return_logits=True, session=1)
        second = engine.submit(prompts[1], tier='generate',
                               max_new_tokens=4, return_logits=True,
                               session=1)
        with pytest.raises(RuntimeError):
            engine.close_session(1)
        other = engine.submit(prompts[2], tier='generate', max_new_tokens=2)
        other.result(timeout=300)
        first, second = first.result(timeout=300), second.result(timeout=300)
        history = np.concatenate([prompts[0], first.token_ids, prompts[1],
                                  second.token_ids])[:-1]
        rows = 40 + 6 + 7 - 1 + np.arange(4)
        np.testing.assert_allclose(
            program_logits(second),
            reference_logits(model, config, history, rows), atol=TOLERANCE)
        lm = engine.stats()['lm']
        assert lm['session_wait_ms']['count'] \
            - before['session_wait_ms']['count'] == 2
        assert lm['session_wait_ms']['max_ms'] > 0
        # the second turn found 40 + 6 - 1 positions resident
        assert lm['resident_positions_total'] \
            - before['resident_positions_total'] == 45
        assert lm['prefilled_positions_total'] \
            - before['prefilled_positions_total'] == 40 + 8 + 12
        assert lm['sparse_blocks_visible_total'] \
            > lm['sparse_blocks_chosen_total'] > 0
        assert lm['sparse_dense_branch_total'] > 0
        with pytest.raises(ValueError):     # would outgrow LM_MAX_CONTEXT
            engine.submit(rng.integers(0, 64, 80), tier='generate',
                          max_new_tokens=4, session=1)
        assert engine.close_session(1)
        lm = engine.stats()['lm']
        assert lm['state_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0
        assert lm['ring_pool_fill'] == 0.0
    finally:
        engine.close()


def test_a_failed_step_loses_its_sessions_and_says_so(tmp_path_factory):
    """A step fails while a resident session's turn runs and another of its
    turns waits in the queue: the running turn fails with the step's error,
    the waiting one and every later turn with ``SessionLost`` (served from
    position 0 they would answer without the session's history), until the
    caller closes the session.  A session that held no cache yet, and a
    request of no session, are served; no slot, page or lease is leaked."""
    from code2vec_tpu.serving.errors import SessionLost
    config = tiny_config()
    model, engine = build(tmp_path_factory, config)
    try:
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 64, n) for n in (30, 9, 5, 11, 6)]
        generate(engine, prompts[0], 3, session='kept')
        runtime = engine.lm_runtime()
        run, failing = runtime.run, [True]
        entered, release = threading.Event(), threading.Event()

        def fails_once(chunk, packed):
            if failing[0]:
                failing[0] = False
                entered.set()       # the dispatcher stands here while the
                release.wait(60)    # other requests are queued
                raise RuntimeError('injected: the step failed')
            return run(chunk, packed)
        runtime.run = fails_once
        second = engine.submit(prompts[1], tier='generate',
                               max_new_tokens=4, session='kept')
        assert entered.wait(60)
        third = engine.submit(prompts[2], tier='generate',
                              max_new_tokens=2, session='kept')
        fresh = engine.submit(prompts[3], tier='generate',
                              max_new_tokens=3, return_logits=True,
                              session='fresh')
        alone = engine.submit(prompts[4], tier='generate', max_new_tokens=2)
        release.set()
        with pytest.raises(RuntimeError, match='injected'):
            second.result(timeout=300)
        with pytest.raises(SessionLost):
            third.result(timeout=300)
        alone.result(timeout=300)
        fresh = fresh.result(timeout=300)       # from position 0: its own
        np.testing.assert_allclose(
            program_logits(fresh),
            reference_logits(model, config,
                             np.concatenate([prompts[3],
                                             fresh.token_ids[:-1]]),
                             10 + np.arange(3)), atol=TOLERANCE)
        with pytest.raises(SessionLost):
            engine.submit(prompts[1], tier='generate', max_new_tokens=2,
                          session='kept')
        lm = engine.stats()
        assert lm['queue_depth'] == 0
        assert lm['lm']['sessions_resident'] == 1       # 'fresh'
        assert engine.close_session('fresh') is True
        lm = engine.stats()['lm']
        assert lm['state_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0
        assert lm['running'] == 0 and lm['sessions_resident'] == 0
        # a turn booked before the loss and queued after it (submit takes
        # the lock twice) finds no session: it fails, the loop lives on
        from code2vec_tpu.serving.lm_scheduler import GenerateRequest
        orphan = GenerateRequest(prompts[2].astype(np.int32), 2, False,
                                 session='gone')
        with engine._cond:
            engine._queues['generate'].append(orphan)
            engine._pending_rows['generate'] += 1
            engine._cond.notify_all()
        with pytest.raises(SessionLost):
            orphan.future.result(timeout=300)
        # closing acknowledges the loss: the id starts anew, at position 0
        assert engine.close_session('kept') is True
        assert engine.close_session('kept') is False
        again = generate(engine, prompts[1], 4, session='kept')
        np.testing.assert_allclose(
            program_logits(again),
            reference_logits(model, config,
                             np.concatenate([prompts[1],
                                             again.token_ids[:-1]]),
                             8 + np.arange(4)), atol=TOLERANCE)
        assert engine.close_session('kept') is True
        lm = engine.stats()['lm']
        assert lm['state_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0
    finally:
        engine.close()


def test_step_log_says_what_each_step_carried(exact):
    model, engine, config = exact
    before = len(engine.lm_step_log())
    prompt = np.random.default_rng(9).integers(0, 64, 27)
    engine.submit(prompt, tier='generate',
                  max_new_tokens=4).result(timeout=300)
    steps = engine.lm_step_log()[before:]
    chunks = [s for s in steps if s['chunk_tokens']]
    assert [s['chunk_tokens'] for s in chunks] == [8, 8, 8, 3]
    assert [s['chunk_first'] for s in chunks] == [0, 8, 16, 24]
    # positions 0..23 take the dense branch, 24..26 choose blocks: 4 of 7
    assert [s['dense_tokens'] for s in chunks] == [8, 8, 8, 0]
    assert chunks[-1]['blocks_chosen'] == 3 * 2 * 4
    assert chunks[-1]['blocks_visible'] == 3 * 2 * 7
    decodes = [s for s in steps if len(s['decode_positions'])]
    assert [int(s['decode_positions'][0]) for s in decodes] == [27, 28, 29]
    assert all(s['blocks_chosen'] == 2 * 4 for s in decodes)


def test_the_kernel_counter_reads_the_queries_stage_2_ran_there(
        tmp_path_factory):
    """``serving/lm_sparse_kernel_queries_total`` is cataloged and in
    ``stats()['lm']``: 0 where the step programs run the ``jax.numpy``
    stage 2 (what the CPU's platform chooses), and the live sparse queries
    times the sparse layers where the test hands the runtime the kernel in
    the interpreter; both paths choose the same blocks and give the same
    logits."""
    from code2vec_tpu.serving.engine import ServingEngine
    from code2vec_tpu.serving.lm_scheduler import LMRuntime
    from code2vec_tpu.telemetry import catalog
    name = 'serving/lm_sparse_kernel_queries_total'
    assert name in catalog.CATALOG and name in hybrid_lib.COUNTERS
    config = tiny_config(mixers=(S, L, S, L))
    model, plain = build(tmp_path_factory, config, LM_CHUNK_BUCKETS='8')
    runtime = LMRuntime(model.config, model.decoder_config, model.params,
                        model.lib,
                        step_kernels={'sparse_stage2': 'interpret'})
    kernel = ServingEngine(model.config, None, model.params, None,
                           decode_table=None, lm_runtime=runtime,
                           log=model.log)
    try:
        prompt = np.random.default_rng(12).integers(0, 64, 30)
        before = {e: e.stats()['lm'] for e in (plain, kernel)}
        results = {e: generate(e, prompt, 4) for e in (plain, kernel)}
        after = {e: e.stats()['lm'] for e in (plain, kernel)}

        def grew(engine, key):
            return after[engine][key] - before[engine][key]
        assert after[plain]['step_kernels'] == {'sparse_stage2': 'jnp'}
        assert after[kernel]['step_kernels'] == {
            'sparse_stage2': 'interpret'}
        assert after[plain]['sparse_kernel_queries_total'] == 0
        # prompt positions 24..29 and decoded 30..32 are past dense_len 24,
        # in two sparse layers
        assert grew(kernel, 'sparse_kernel_queries_total') == (6 + 3) * 2
        assert grew(kernel, 'sparse_blocks_chosen_total') \
            == grew(plain, 'sparse_blocks_chosen_total') > 0
        np.testing.assert_array_equal(results[kernel].token_ids,
                                      results[plain].token_ids)
        np.testing.assert_allclose(program_logits(results[kernel]),
                                   program_logits(results[plain]),
                                   atol=TOLERANCE)
    finally:
        kernel.close()
        plain.close()


def test_the_stride_row_counter_reads_what_decode_rows_scored(exact):
    """``serving/lm_sparse_decode_stride_rows_total`` is cataloged and in
    ``stats()['lm']``: over the sparse layers, the stride rows of the
    pieces of its table each live decode row was scored over (up to the
    one that holds it), and nothing while no decode row is past
    ``dense_len``."""
    from code2vec_tpu.telemetry import catalog
    name = 'serving/lm_sparse_decode_stride_rows_total'
    assert name in catalog.CATALOG and name in hybrid_lib.COUNTERS
    model, engine, config = exact
    key = name[len('serving/lm_'):]
    g = engine.lm_runtime().geometry
    geo = sparse_attention.SparseGeometry(**SPARSE_CONFIG)
    piece = -(-g.pages_per_seq // sparse_attention.ROW_PIECES)
    strides_a_page = g.page_size // geo.kernel_stride
    sparse_layers = sum(kind == S for kind in
                        hybrid_lib.HybridConfig.from_dict(config).mixer_types)

    def run(length, new):
        before = (engine.stats()['lm'][key], len(engine.lm_step_log()))
        engine.submit(np.random.default_rng(length).integers(0, 64, length),
                      tier='generate', max_new_tokens=new).result(timeout=300)
        steps = engine.lm_step_log()[before[1]:]
        return engine.stats()['lm'][key] - before[0], steps

    # decode rows at positions 10..13: all within dense_len
    grew, steps = run(10, 5)
    assert grew == 0 and any(len(s['decode_positions']) for s in steps)
    # decode rows at 27..29, past dense_len 24
    grew, steps = run(27, 4)
    want = 0
    for step in steps:
        for at in step['decode_positions']:
            if at + 1 > geo.dense_len:
                pages = min(-(-(at // g.page_size + 1) // piece) * piece,
                            g.pages_per_seq)
                assert (at // geo.kernel_stride + 1
                        <= pages * strides_a_page
                        <= g.pages_per_seq * strides_a_page)
                want += pages * strides_a_page * sparse_layers
    assert grew == want > 0


# ------------------------------------------------------------ the traffic
MIX = {'rate_per_s': 3.0, 'lead_in_s': 6.0,
       'sessions': {'count': 8, 'median': 65536, 'sigma': 0.5, 'min': 32768,
                    'max': 131072},
       'turns': {'count': 64, 'median': 1024, 'sigma': 0.5, 'min': 256,
                 'max': 4096, 'new_tokens': 64}}


def test_turns_are_the_same_for_every_seed_but_for_the_ids():
    a = session_turns.generate(MIX, 11, 20.0, 73448)
    b = session_turns.generate(MIX, 2 ** 31 + 5, 20.0, 73448)
    for field in ('due_s', 'prompt_len', 'new_tokens', 'session',
                  'template', 'session_len'):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    ids_a = session_turns.prompt_ids(a, 20)
    ids_b = session_turns.prompt_ids(b, 20)
    assert ids_a.shape == ids_b.shape == (a.prompt_len[20],)
    assert (ids_a != ids_b).mean() > 0.99
    np.testing.assert_array_equal(ids_a, session_turns.prompt_ids(a, 20))
    context = session_turns.session_ids(a, 0)
    assert context.shape == (32768,) and context.max() < 73448
    assert (context[:64] != session_turns.session_ids(a, 1)[:64]).all()


def test_turns_are_the_cell_the_issue_names():
    schedule = session_turns.generate(MIX, 1, 20.0, 73448)
    np.testing.assert_array_equal(
        schedule.session_len, [32768, 42057, 51327, 60579, 70899, 83679,
                               102122, 131072])
    lengths = session_turns.cycle(MIX)
    assert lengths.shape == (64,)
    assert lengths.min() >= 256 and lengths.max() <= 4096
    assert abs(np.median(lengths) - 1024) < 40
    assert set(schedule.new_tokens) == {64}
    np.testing.assert_array_equal(schedule.session,
                                  np.arange(78) % 8)
    np.testing.assert_allclose(np.diff(schedule.due_s), 1 / 3)
    assert schedule.due_s[0] == -6.0 and (schedule.due_s >= 0).sum() == 60
    assert schedule.due_s[-1] < 20.0
