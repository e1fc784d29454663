"""A decoder-only language model on the serving path, at a tiny size on
the CPU, every piece against the plain float32 reference
(``chipbench/reference_mellum2.py``): the layer kinds, the YaRN table,
chunked prefill and decode through the ring and the pages, independence of
a sequence from its step's neighbours, the grouped expert product, the two
pools, and the replayed traffic's determinism.

Window 8, four layers (sliding x 3 + full), 8 experts top-2, seeded
weights.  ``COMPUTE_DTYPE='float32'`` is the exact mode these tests hold
to 1e-4; the bfloat16 mode is held to the two-part tolerance the
benchmark's check writes down.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_mellum2 as ref
from chipbench.runners import serve_lm
from chipbench.traffic import ide_replay
from code2vec_tpu import model_api
from code2vec_tpu.config import Config
from code2vec_tpu.models import decoder as decoder_lib
from code2vec_tpu.models import families
from code2vec_tpu.ops import grouped_experts
from code2vec_tpu.serving import lm_cache
from code2vec_tpu.serving.errors import EngineClosed

SLIDING, FULL = decoder_lib.SLIDING, decoder_lib.FULL
ROPE = {
    FULL: {'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 4,
           'original_max_position_embeddings': 16, 'beta_fast': 32,
           'beta_slow': 1, 'attention_factor': 1.1386},
    SLIDING: {'rope_type': 'default', 'rope_theta': 500000}}


def tiny_config(layer_types=(SLIDING, SLIDING, SLIDING, FULL)):
    return {
        'attention_bias': False, 'head_dim': 8, 'hidden_size': 32,
        'intermediate_size': 64, 'layer_types': list(layer_types),
        'mlp_layer_types': ['sparse'] * len(layer_types),
        'moe_intermediate_size': 16, 'norm_topk_prob': True,
        'num_attention_heads': 4, 'num_experts': 8,
        'num_experts_per_tok': 2, 'num_hidden_layers': len(layer_types),
        'num_key_value_heads': 2, 'rms_norm_eps': 1e-6,
        'rope_parameters': ROPE, 'sliding_window': 8,
        'tie_word_embeddings': False, 'vocab_size': 64}


def build(tmp_path_factory, model_config, dtype='float32', **settings):
    path = tmp_path_factory.mktemp('lm') / 'config.json'
    path.write_text(json.dumps(model_config))
    keys = dict(MODEL_FAMILY='mellum', LM_CONFIG_PATH=str(path),
                LM_PARAM_SEED=3, LM_MAX_SEQS=3, LM_PAGE_SIZE=4,
                LM_PAGE_POOL_PAGES=40, LM_MAX_CONTEXT=64,
                LM_CHUNK_BUCKETS='4,8', LM_WINDOW_SUBCHUNK=4,
                COMPUTE_DTYPE=dtype)
    keys.update(settings)
    model = model_api.create_model(Config(**keys))
    return model, model.serving_engine()


def reference_logits(model, model_config, prompt, result):
    weights = serve_lm.reference_weights(model.params, model_config)
    ids = np.concatenate([prompt, result.token_ids[:-1]])
    return np.asarray(ref.forward(model_config, weights, ids,
                                  first_logit=len(prompt) - 1))


def program_logits(result):
    return np.stack([np.asarray(row) for row in result.logits])


@pytest.fixture(scope='module')
def exact(tmp_path_factory):
    config = tiny_config()
    model, engine = build(tmp_path_factory, config)
    yield model, engine, config
    engine.close()


# ------------------------------------------------------------- the seam
def test_the_families_declare_what_the_engine_needs():
    from code2vec_tpu.training.trainer import PREDICT_TIERS
    assert families.FAMILIES['code2vec'].tiers == tuple(PREDICT_TIERS)
    assert families.FAMILIES['mellum'].tiers == ('generate',)
    for family in families.FAMILIES.values():
        assert family.input_layout and family.reference.endswith('.py')
    assert families.family_of(Config()).name == 'code2vec'
    with pytest.raises(ValueError):
        families.family_of(Config(MODEL_FAMILY='nope'))


def test_the_decoder_declares_its_parameters():
    cfg = decoder_lib.DecoderConfig.from_dict(tiny_config())
    shapes = decoder_lib.param_shapes(cfg)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) \
        == cfg.parameters()
    specs = families.FAMILIES['mellum'].param_specs(shapes)
    assert jax.tree_util.tree_structure(specs) \
        == jax.tree_util.tree_structure(shapes)


def test_only_what_is_implemented_is_accepted():
    dense = tiny_config()
    dense['mlp_layer_types'][0] = 'dense'
    with pytest.raises(NotImplementedError):
        decoder_lib.DecoderConfig.from_dict(dense)
    short = tiny_config()
    short['layer_types'] = short['layer_types'][:2]
    with pytest.raises(ValueError):
        decoder_lib.DecoderConfig.from_dict(short)


# ------------------------------------------------------------------ RoPE
def test_yarn_table_against_its_closed_form():
    """The published model's numbers: low and high of the ramp, and the
    blend at a dimension below, inside and above it."""
    rope = {'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
            'original_max_position_embeddings': 8192, 'beta_fast': 32,
            'beta_slow': 1, 'attention_factor': 1.2772588722239782}
    inv_freq, factor = decoder_lib.rope_inv_freq(rope, 128)
    assert factor == 1.2772588722239782
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    extrap = 500000.0 ** (-2 * np.arange(64) / 128)
    np.testing.assert_allclose(inv_freq[:low + 1], extrap[:low + 1])
    np.testing.assert_allclose(inv_freq[high:], extrap[high:] / 16)
    middle = (low + high) // 2
    ramp = (middle - low) / (high - low)
    np.testing.assert_allclose(
        inv_freq[middle],
        extrap[middle] / 16 * ramp + extrap[middle] * (1 - ramp))
    # the reference's own table is the same function written apart
    np.testing.assert_allclose(ref.inv_freq(rope, 128)[0], inv_freq)


def test_default_rope_is_the_plain_table():
    inv_freq, factor = decoder_lib.rope_inv_freq(ROPE[SLIDING], 8)
    assert factor == 1.0
    np.testing.assert_allclose(inv_freq, 500000.0 ** (-np.arange(4) / 4))


# ---------------------------------------------------- against the reference
@pytest.mark.parametrize('kinds', [(SLIDING, SLIDING), (FULL, FULL)],
                         ids=['sliding-alone', 'full-alone'])
def test_each_layer_kind_alone(tmp_path_factory, kinds):
    config = tiny_config(kinds)
    model, engine = build(tmp_path_factory, config)
    try:
        prompt = np.random.default_rng(1).integers(0, 64, 21)
        result = engine.submit(prompt, tier='generate', max_new_tokens=5,
                               return_logits=True).result(timeout=120)
    finally:
        engine.close()
    np.testing.assert_allclose(
        program_logits(result),
        reference_logits(model, config, prompt, result), atol=1e-4)


@pytest.mark.parametrize('length,new', [(37, 12), (8, 9), (5, 1), (1, 3)],
                         ids=['several-windows', 'exactly-a-window',
                              'one-token-out', 'one-token-in'])
def test_chunked_prefill_then_decode_equals_the_full_forward_pass(
        exact, length, new):
    """A prompt several windows long goes in as chunks of 8 and 4 (each
    cut into sub-sequences of 4 for the sliding layers), wraps the ring
    (7 pages of 4 positions) more than once, then decodes through ring
    and pages."""
    model, engine, config = exact
    prompt = np.random.default_rng(length).integers(0, 64, length)
    result = engine.submit(prompt, tier='generate', max_new_tokens=new,
                           return_logits=True).result(timeout=120)
    assert result.token_ids.shape == (new,)
    want = reference_logits(model, config, prompt, result)
    np.testing.assert_allclose(program_logits(result), want, atol=1e-4)
    np.testing.assert_array_equal(result.token_ids, want.argmax(-1))


def test_neighbours_joining_and_leaving_change_nothing(exact):
    """The same request alone, and beside others that arrive and finish
    while it prefills and decodes: the same logits."""
    model, engine, config = exact
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 64, 26)
    alone = engine.submit(prompt, tier='generate', max_new_tokens=10,
                          return_logits=True).result(timeout=120)
    others = [engine.submit(rng.integers(0, 64, n), tier='generate',
                            max_new_tokens=m)
              for n, m in ((9, 2), (30, 4))]
    beside = engine.submit(prompt, tier='generate', max_new_tokens=10,
                           return_logits=True)
    others += [engine.submit(rng.integers(0, 64, n), tier='generate',
                             max_new_tokens=m)
               for n, m in ((3, 6), (17, 1), (11, 3))]
    beside = beside.result(timeout=120)
    for other in others:
        other.result(timeout=120)
    np.testing.assert_array_equal(alone.token_ids, beside.token_ids)
    np.testing.assert_allclose(program_logits(alone),
                               program_logits(beside), atol=1e-5)


def test_a_session_keeps_ring_and_pages_between_turns(exact):
    """A request may name a session on this model too: the second turn
    starts at the first's end (ring slot and pages kept), feeds the token
    the first generated last, and equals one request over the joined
    history; closing the session returns both pools."""
    model, engine, config = exact
    rng = np.random.default_rng(21)
    first, second = rng.integers(0, 64, 23), rng.integers(0, 64, 10)
    one = engine.submit(first, tier='generate', max_new_tokens=5,
                        return_logits=True, session='s').result(timeout=120)
    two = engine.submit(second, tier='generate', max_new_tokens=6,
                        return_logits=True, session='s').result(timeout=120)
    lm = engine.stats()['lm']
    assert lm['ring_pool_fill'] > 0 and lm['sessions_resident'] == 1
    history = np.concatenate([first, one.token_ids, second])
    weights = serve_lm.reference_weights(model.params, config)
    want = np.asarray(ref.forward(
        config, weights, np.concatenate([history, two.token_ids[:-1]]),
        first_logit=len(history) - 1))
    np.testing.assert_allclose(program_logits(two), want, atol=1e-4)
    assert engine.close_session('s')
    whole = engine.submit(history, tier='generate', max_new_tokens=6,
                          return_logits=True).result(timeout=120)
    np.testing.assert_allclose(program_logits(whole), program_logits(two),
                               atol=1e-5)
    lm = engine.stats()['lm']
    assert lm['ring_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0
    assert lm['state_pool_fill'] == 0.0


def test_bfloat16_holds_the_written_two_part_tolerance(tmp_path_factory):
    """The mode the chip runs: products in bfloat16.  Most positions are
    within the bound; a position where a near-tie picked another expert
    is beyond it, and still inside the cap."""
    config = tiny_config()
    model, engine = build(tmp_path_factory, config, dtype='bfloat16')
    errors = []
    try:
        for seed, (length, new) in enumerate([(37, 12), (21, 9), (13, 6)]):
            prompt = np.random.default_rng(seed).integers(0, 64, length)
            result = engine.submit(
                prompt, tier='generate', max_new_tokens=new,
                return_logits=True).result(timeout=120)
            errors.append(serve_lm.compare_logits(
                program_logits(result),
                reference_logits(model, config, prompt, result)))
    finally:
        engine.close()
    errors = np.concatenate(errors)
    tolerance = {'relative_error': 0.1, 'share_beyond': 0.25,
                 'relative_error_cap': 1.0}
    assert serve_lm.judge(errors, tolerance) == []
    assert np.median(errors) > 1e-4      # it is not the exact mode
    # a dropped expert, or weights of a tenth the precision, break it
    assert serve_lm.judge(errors * 10, tolerance) != []


def test_router_flips_under_bfloat16_noise_are_few_and_counted():
    """The router's probabilities are float32, but the hidden state they
    are computed from carries bfloat16 rounding: how often that changes
    the chosen set among 64 experts top-8."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((4096, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 64)) / 16, jnp.bfloat16)
    _, exact_choice = grouped_experts.route(h, w, 8, True)
    noisy = h.astype(jnp.bfloat16).astype(jnp.float32)
    _, noisy_choice = grouped_experts.route(noisy, w, 8, True)
    flipped = np.mean([set(a) != set(b) for a, b in
                       zip(np.asarray(exact_choice),
                           np.asarray(noisy_choice))])
    assert 0.0 < flipped < 0.15, flipped


# ------------------------------------------------------- the expert layer
def test_grouped_expert_product_equals_the_per_expert_loop():
    """A router that sends most tokens to one expert and none to
    another: no token dropped, the empty expert never needed."""
    rng = np.random.default_rng(5)
    tokens, hidden, width, n_experts, top_k = 50, 32, 16, 8, 2
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    gate_up = jnp.asarray(
        rng.standard_normal((n_experts, hidden, 2 * width)) / 6,
        jnp.float32)
    down = jnp.asarray(
        rng.standard_normal((n_experts, width, hidden)) / 4, jnp.float32)
    router = np.zeros((hidden, n_experts), np.float32)
    router[:, 0] = 0.0
    bias_logits = rng.standard_normal((tokens, n_experts)).astype(
        np.float32)
    bias_logits[:, 0] += 6.0          # nearly everyone picks expert 0
    bias_logits[:, 5] -= 50.0         # nobody picks expert 5
    probs = jax.nn.softmax(jnp.asarray(bias_logits), axis=-1)
    picked, experts = jax.lax.top_k(probs, top_k)
    picked = picked / picked.sum(-1, keepdims=True)
    valid = jnp.asarray(np.arange(tokens) < 45)
    out, counted = grouped_experts.expert_ffn(
        x, picked, experts.astype(jnp.int32), gate_up, down, valid)
    want = np.zeros((tokens, hidden), np.float64)
    for t in range(tokens):
        for p, e in zip(np.asarray(picked)[t], np.asarray(experts)[t]):
            gu = np.asarray(x)[t] @ np.asarray(gate_up)[e]
            g, u = gu[:width], gu[width:]
            want[t] += p * ((g / (1 + np.exp(-g))) * u) \
                @ np.asarray(down)[e]
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    counted = np.asarray(counted)
    assert counted.sum() == 45 * top_k and counted[5] == 0
    assert counted[0] >= 40


# ------------------------------------------------------------- the pools
def test_geometry_of_the_two_pools():
    g = lm_cache.CacheGeometry.make(page_size=128, window=1024, slots=16,
                                    pool_pages=1706, max_context=24832,
                                    max_chunk=2048)
    assert g.ring_pages == 25 and g.pages_per_seq == 194
    # a slot does not grow with the context; a full layer's pages do
    rows = lm_cache.ring_rows(g, 3, np.asarray([0, 3199, 3200, 20000]))
    assert rows.min() >= 3 * 25 * 128 and rows.max() < 4 * 25 * 128
    assert rows[0] == rows[2]           # position 3200 reuses position 0's


@pytest.mark.parametrize('first,q_len', [(0, 1), (5, 1), (1023, 1),
                                         (5000, 1), (4096, 512),
                                         (20000, 512), (700, 512)])
def test_a_window_view_holds_exactly_the_keys_inside_the_window(first,
                                                                 q_len):
    g = lm_cache.CacheGeometry.make(page_size=128, window=1024, slots=16,
                                    pool_pages=64, max_context=24832,
                                    max_chunk=2048)
    width = g.window_table_pages(q_len)
    kv_len, pages = lm_cache.window_view(g, 2, first, q_len, width)
    start = (first + q_len - kv_len)        # the rebased position 0
    assert start % 128 == 0 and start <= max(0, first - 1023)
    assert max(0, first - 1023) - start < 128       # under a page of slack
    needed = -(-kv_len // 128)
    assert needed <= width and len(set(pages[:needed])) == needed
    # every position from the rebased start is where ring_rows put it
    at = np.arange(start, first + q_len)
    rows = lm_cache.ring_rows(g, 2, at)
    np.testing.assert_array_equal(
        rows, pages[(at - start) // 128] * 128 + at % 128)


def test_pools_free_everything_and_admission_waits_when_full():
    g = lm_cache.CacheGeometry.make(page_size=4, window=8, slots=2,
                                    pool_pages=10, max_context=40,
                                    max_chunk=8)
    cache = lm_cache.CacheManager(g)
    assert cache.fits_ever(40) and not cache.fits_ever(41)
    a = cache.admit(24)                     # 6 pages
    b = cache.admit(13)                     # 4 pages: the pool is full
    assert a and b and cache.fill() == (1.0, 1.0)
    assert cache.admit(1) is None and cache.held_total == 1
    cache.free(a)
    assert cache.admit(25) is None          # a slot, but 7 pages > 6 free
    c = cache.admit(24)
    assert c is not None and set(c.pages) == set(range(10)) - set(b.pages)
    cache.free(b)
    cache.free(c)
    assert cache.fill() == (0.0, 0.0)
    assert cache.slots_in_use == 0 and cache.pages_in_use == 0


def test_admission_waits_for_the_cache_and_delivery_returns_it(
        tmp_path_factory):
    """Two slots: the third request is held in the queue until one is
    delivered; afterwards both pools are empty again."""
    config = tiny_config()
    model, engine = build(tmp_path_factory, config, LM_MAX_SEQS=2,
                          LM_PAGE_POOL_PAGES=24)
    try:
        rng = np.random.default_rng(2)
        warm = engine.stats()['lm']     # warm-up served requests of its own
        futures = [engine.submit(rng.integers(0, 64, 20), tier='generate',
                                 max_new_tokens=6) for _ in range(5)]
        for future in futures:
            assert future.result(timeout=120).token_ids.shape == (6,)
        lm = engine.stats()['lm']
        assert lm['admit_held_total'] > warm['admit_held_total']
        assert lm['ring_pool_fill'] == 0.0 and lm['page_pool_fill'] == 0.0
        assert lm['generated_tokens_total'] \
            - warm['generated_tokens_total'] == 30
        assert lm['expert_tokens'].sum() == \
            lm['tokens_total'] * 2 * 4      # top-2 in each of four layers
        with pytest.raises(ValueError):     # can never be resident
            engine.submit(rng.integers(0, 64, 80), tier='generate',
                          max_new_tokens=4)
        with pytest.raises(ValueError):
            engine.submit(np.asarray([64]), tier='generate')
        with pytest.raises(ValueError):
            engine.submit(rng.integers(0, 64, 5), tier='topk')
    finally:
        engine.close()
    with pytest.raises(EngineClosed):
        engine.submit(np.asarray([1, 2]), tier='generate')


def test_close_fails_what_is_unfinished_and_drain_serves_it(
        tmp_path_factory):
    config = tiny_config()
    model, engine = build(tmp_path_factory, config)
    rng = np.random.default_rng(4)
    futures = [engine.submit(rng.integers(0, 64, 30), tier='generate',
                             max_new_tokens=8) for _ in range(6)]
    engine.close(drain=True)
    assert all(f.result(timeout=1).token_ids.shape == (8,)
               for f in futures)
    model, engine = build(tmp_path_factory, config)
    futures = [engine.submit(rng.integers(0, 64, 30), tier='generate',
                             max_new_tokens=30) for _ in range(8)]
    engine.close()
    outcomes = [f.exception(timeout=5) for f in futures]
    assert any(isinstance(o, EngineClosed) for o in outcomes)
    assert all(o is None or isinstance(o, EngineClosed) for o in outcomes)


def test_step_log_says_what_each_step_carried(exact):
    model, engine, config = exact
    before = len(engine.lm_step_log())
    prompt = np.random.default_rng(9).integers(0, 64, 19)
    engine.submit(prompt, tier='generate',
                  max_new_tokens=4).result(timeout=120)
    steps = engine.lm_step_log()[before:]
    chunks = [s for s in steps if s['chunk_tokens']]
    assert [s['chunk_tokens'] for s in chunks] == [8, 8, 3]
    assert [s['chunk_first'] for s in chunks] == [0, 8, 16]
    assert [s['bucket'] for s in chunks] == [8, 8, 4]
    decodes = [s for s in steps if len(s['decode_positions'])]
    assert [int(s['decode_positions'][0]) for s in decodes] == [19, 20, 21]
    assert all(s['experts_touched'].shape == (4,) for s in steps)


# ------------------------------------------------------------ the traffic
MIX = {'rate_per_s': 2.5, 'lead_in_s': 6.0, 'chat_every': 4, 'classes': {
    'complete': {'count': 48, 'median': 3072, 'sigma': 0.5, 'min': 1024,
                 'max': 8192, 'new_tokens': 48},
    'chat': {'count': 16, 'median': 8192, 'sigma': 0.5, 'min': 4096,
             'max': 24576, 'new_tokens': 192}}}


def test_replay_is_the_same_for_every_seed_but_for_the_ids():
    a = ide_replay.generate(MIX, 11, 20.0, 98304)
    b = ide_replay.generate(MIX, 2 ** 31 + 5, 20.0, 98304)
    for field in ('due_s', 'prompt_len', 'new_tokens', 'kind', 'template'):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    ids_a, ids_b = ide_replay.prompt_ids(a, 20), ide_replay.prompt_ids(b, 20)
    assert ids_a.shape == ids_b.shape == (a.prompt_len[20],)
    assert (ids_a != ids_b).mean() > 0.99
    np.testing.assert_array_equal(ids_a, ide_replay.prompt_ids(a, 20))
    assert (ide_replay.prompt_ids(a, 20)[:64]
            != ide_replay.prompt_ids(a, 21)[:64]).all()  # no shared prefix
    assert 0 <= ids_a.min() and ids_a.max() < 98304


def test_replay_is_the_cell_the_issue_names():
    prompt_len, new_tokens, kind = ide_replay.cycle(MIX)
    assert prompt_len.shape == (64,) and kind.sum() == 16
    assert (kind[3::4] == 1).all()                  # every fourth a chat
    assert set(new_tokens[kind == 0]) == {48}
    assert set(new_tokens[kind == 1]) == {192}
    complete = np.sort(prompt_len[kind == 0])
    assert complete[0] == 1024 and complete[-1] <= 8192
    assert abs(np.median(complete) - 3072) < 100
    chat = np.sort(prompt_len[kind == 1])
    assert chat[0] >= 4096 and chat[-1] <= 24576
    assert abs(np.median(chat) - 8192) < 400
    # bit-reversed visiting order: each quarter of the cycle holds short
    # and long completions
    for quarter in np.split(np.arange(64), 4):
        lengths = prompt_len[quarter][kind[quarter] == 0]
        assert lengths.min() < 2100 and lengths.max() > 4300
    replay = ide_replay.generate(MIX, 1, 20.0, 98304)
    np.testing.assert_allclose(np.diff(replay.due_s), 0.4)
    assert replay.due_s[0] == -6.0 and (replay.due_s >= 0).sum() == 50
    assert replay.due_s[-1] < 20.0
