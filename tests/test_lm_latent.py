"""A decoder of multi-head latent attention and a share of the experts on
the serving path, at a tiny size on the CPU, every piece against the plain
float32 reference (``chipbench/reference_mistral4.py``) or a brute-force
statement of it: prefill in chunks and decode through latent pages, the
absorbed and expanded forms of attention, the decode kernel, the expert
share and its shared expert, resident sessions, the counters, and the
controls the cell's tolerance is set against.

Four heads, a latent of 8 and a rope key of 4, pages of 8 positions, the
llama-4 query scale's ``original_max_position_embeddings`` cut to 16 so
that sequences cross it, 4 of 8 routed experts held from expert 2, top-2,
seeded weights.  ``COMPUTE_DTYPE='float32'`` is the exact mode these tests
hold to 1e-4.
"""
import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import controls_mistral4
from chipbench import reference_mistral4 as ref
from chipbench.runners import serve_lm, serve_lm_latent
from code2vec_tpu import model_api
from code2vec_tpu.config import Config
from code2vec_tpu.models import families
from code2vec_tpu.models import latent_decoder as latent_lib
from code2vec_tpu.ops import grouped_experts, latent_attention, pallas_latent

TOLERANCE = 1e-4
CELL_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'chipbench', 'configs',
    'mistral-small-4-119b-ep4-l6.json')


def tiny_config(**overrides):
    config = {
        'attention_bias': False, 'first_k_dense_replace': 0,
        'hidden_act': 'silu', 'hidden_size': 32, 'kv_lora_rank': 8,
        'mlp_bias': False, 'model_type': 'mistral4',
        'moe_intermediate_size': 16, 'n_group': 1, 'n_routed_experts': 4,
        'n_routed_experts_published': 8, 'first_held_expert': 2,
        'n_shared_experts': 1, 'norm_topk_prob': True,
        'num_attention_heads': 4, 'num_experts_per_tok': 2,
        'num_hidden_layers': 2, 'q_lora_rank': 16, 'qk_nope_head_dim': 8,
        'qk_rope_head_dim': 4, 'v_head_dim': 8, 'rms_norm_eps': 1e-6,
        'rope_interleave': True,
        'rope_parameters': {
            'beta_fast': 32, 'beta_slow': 1, 'factor': 4,
            'llama_4_scaling_beta': 0.1, 'mscale': 1, 'mscale_all_dim': 1,
            'original_max_position_embeddings': 16, 'rope_theta': 10000,
            'rope_type': 'yarn'},
        'routed_scaling_factor': 1, 'tie_word_embeddings': False,
        'topk_group': 1, 'vocab_size': 64}
    config.update(overrides)
    return config


def build(tmp_path_factory, model_config, dtype='float32', step_kernels=None,
          **settings):
    path = tmp_path_factory.mktemp('lmlatent') / 'config.json'
    path.write_text(json.dumps(model_config))
    keys = dict(MODEL_FAMILY='mistral4', LM_CONFIG_PATH=str(path),
                LM_PARAM_SEED=3, LM_MAX_SEQS=3, LM_PAGE_SIZE=8,
                LM_PAGE_POOL_PAGES=48, LM_MAX_CONTEXT=128,
                LM_CHUNK_BUCKETS='4,8', COMPUTE_DTYPE=dtype)
    keys.update(settings)
    model = model_api.create_model(Config(**keys))
    if step_kernels is None:
        return model, model.serving_engine()
    from code2vec_tpu.serving.engine import ServingEngine
    from code2vec_tpu.serving.lm_scheduler import LMRuntime
    runtime = LMRuntime(model.config, model.decoder_config, model.params,
                        model.lib, step_kernels=step_kernels)
    engine = ServingEngine(model.config, None, model.params, None,
                           decode_table=None, lm_runtime=runtime,
                           log=model.log)
    return model, engine


def reference_logits(model, model_config, history, rows):
    weights = serve_lm_latent.reference_weights(model.params, model_config)
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
    family = families.FAMILIES['mistral4']
    assert family.tiers == ('generate',)
    assert family.reference == 'chipbench/reference_mistral4.py'
    assert family.module == 'code2vec_tpu.models.latent_decoder'
    assert 'session' in family.input_layout
    assert families.family_of(
        Config(MODEL_FAMILY='mistral4')).name == 'mistral4'


def test_the_model_declares_its_parameters():
    cfg = latent_lib.LatentConfig.from_dict(tiny_config())
    h, heads = 32, 4
    attention = (h * (16 + 8 + 4) + 16 * heads * 12 + 8 * heads * 16
                 + heads * 8 * h + 16 + 8)
    experts = h * 8 + 4 * 3 * h * 16 + 3 * h * 16
    assert cfg.parameters() == 2 * (attention + experts + 2 * h) \
        + 2 * 64 * h + h
    assert (cfg.routed_experts, cfg.held_experts, cfg.first_held_expert) \
        == (8, 4, 2)
    assert cfg.latent_width == 12 and cfg.shared_width == 16
    specs = families.FAMILIES['mistral4'].param_specs(
        latent_lib.param_shapes(cfg))
    assert jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(
        latent_lib.param_shapes(cfg))


def test_the_cell_cut_counts_its_parameters():
    with open(CELL_CONFIG) as f:
        spec = json.load(f)
    cfg = latent_lib.LatentConfig.from_dict(spec)
    assert cfg.parameters() == spec['parameters'] == 5422771712
    assert spec['device_bytes']['weights_bfloat16'] == 2 * cfg.parameters()
    # the softmax's scale: 1/sqrt(128) times m^2, m = 0.1 ln(128) + 1
    m = 0.1 * np.log(128) + 1
    assert cfg.softmax_scale == pytest.approx(m * m / np.sqrt(128))
    whole = latent_lib.LatentConfig.from_dict(dict(
        spec, num_hidden_layers=36, n_routed_experts=128,
        vocab_size=131072))
    assert whole.parameters() == spec['parameters_published']


@pytest.mark.parametrize('key,value', [
    ('scoring_func', 'sigmoid'), ('topk_method', 'noaux_tc'),
    ('n_group', 8), ('topk_group', 4), ('first_k_dense_replace', 1),
    ('attention_bias', True), ('mlp_bias', True),
    ('tie_word_embeddings', True), ('rope_interleave', False),
    ('vision_config', {'hidden_size': 1024}), ('image_token_index', 10),
    ('hidden_act', 'gelu'),
    ('rope_parameters', {'rope_type': 'longrope', 'rope_theta': 1e4})])
def test_only_what_is_implemented_is_accepted(key, value):
    with pytest.raises(NotImplementedError) as refused:
        latent_lib.LatentConfig.from_dict(tiny_config(**{key: value}))
    named = 'rope_type' if key == 'rope_parameters' else key
    assert named in str(refused.value)


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError):
        latent_lib.LatentConfig.from_dict(tiny_config(first_held_expert=6))


def test_the_rope_table_is_the_reference_s_yarn():
    """The program's table (``models/decoder.py``'s YaRN with DeepSeek's
    scale of cos and sin) and the reference's own, at the cell's
    published rope parameters."""
    with open(CELL_CONFIG) as f:
        spec = json.load(f)
    cfg = latent_lib.LatentConfig.from_dict(spec)
    inv_freq, cos_scale, beta, original = latent_lib.rope_tables(cfg)
    np.testing.assert_allclose(
        np.asarray(inv_freq),
        ref.rope_frequencies(spec['rope_parameters'], 64), rtol=1e-6)
    assert (cos_scale, beta, original) == (1.0, 0.1, 8192.0)


# ---------------------------------------------------- against the reference
@pytest.mark.parametrize('length,new', [
    (5, 3), (8, 4), (15, 6), (37, 12), (64, 9), (1, 3)],
    ids=['short', 'one-page', 'across-the-query-scale', 'several-chunks',
         'several-pages', 'one-token-in'])
def test_prefill_then_decode_equals_the_full_forward_pass(exact, length, new):
    """Chunks of 8 and 4 over pages of 8: prompts inside a page, across
    page boundaries and past the query scale's 16 positions, then decode
    through latent pages."""
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
    model, engine, config = exact
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, n) for n in (19, 13, 9)]
    news = (4, 5, 3)
    results = [generate(engine, prompt, new, session='joined')
               for prompt, new in zip(prompts, news)]
    lm = engine.stats()['lm']
    assert lm['sessions_resident'] == 1 and lm['slot_fill'] > 0
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
    whole = generate(engine, history[:at - news[-1]], news[-1])
    np.testing.assert_allclose(program_logits(whole),
                               program_logits(results[-1]), atol=1e-5)
    lm = engine.stats()['lm']
    assert lm['sessions_resident'] == 0
    assert lm['slot_fill'] == 0.0 and lm['page_pool_fill'] == 0.0


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


def test_the_counters_and_the_step_log_say_what_each_step_did(exact):
    """A prompt of 27 in chunks of 8 and 3, then three decode steps: every
    token's two routing choices in both layers, the held ones counted on
    the device, the latents read by decode rows and up-projected by
    chunks."""
    model, engine, config = exact
    before = engine.stats()['lm']
    steps_before = len(engine.lm_step_log())
    prompt = np.random.default_rng(9).integers(0, 64, 27)
    engine.submit(prompt, tier='generate',
                  max_new_tokens=4).result(timeout=300)
    after = engine.stats()['lm']
    steps = engine.lm_step_log()[steps_before:]

    def grew(key):
        return after[key] - before[key]
    assert grew('routing_choices_total') == (27 + 3) * 2 * 2
    held = sum(s['held_choices'] for s in steps)
    assert grew('held_choices_total') == held
    assert 0 < held < (27 + 3) * 2 * 2
    assert (after['expert_tokens'] - before['expert_tokens']).sum() == held
    # decode rows at 27, 28, 29 read 28 + 29 + 30 latents, a layer
    assert grew('latent_positions_read_total') == (28 + 29 + 30) * 2
    # chunks ending at 8, 16, 24, 27 up-project their histories
    assert grew('latent_positions_upprojected_total') == \
        (8 + 16 + 24 + 27) * 2
    assert [s['chunk_tokens'] for s in steps if s['chunk_tokens']] == \
        [8, 8, 8, 3]
    assert after['step_kernels'] == {'latent_decode': 'jnp',
                                     'latent_prefill': 'jnp'}


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


# ------------------------------------------- the two forms of attention
def latent_case(seed, rows=3, heads=4, kv_lora=8, rope=4, nope=8, v=8,
                page=8, pages=40, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((pages, kv_lora + rope, page)),
                       dtype)
    w_kvb = jnp.asarray(rng.standard_normal((kv_lora, heads, nope + v))
                        / np.sqrt(kv_lora), dtype)
    q_nope = jnp.asarray(rng.standard_normal((rows, heads, nope)), dtype)
    q_rope = jnp.asarray(rng.standard_normal((rows, heads, rope)), dtype)
    return pool, w_kvb, q_nope, q_rope


@pytest.mark.parametrize('kv_len', [1, 8, 21, 40])
def test_the_absorbed_and_expanded_forms_agree(kv_len):
    """The same queries over the same latent pages: the decode rows' form
    (the query folded into the latent space, the output expanded after)
    and the chunk's (the history up-projected in blocks of two pages), the
    chunk's last rows at positions ``kv_len - rows ..``."""
    rows = min(3, kv_len)
    pool, w_kvb, q_nope, q_rope = latent_case(kv_len, rows=rows)
    table = jnp.asarray(np.random.default_rng(1).permutation(40)[:6],
                        jnp.int32)
    first = kv_len - rows
    expanded = latent_attention.expanded_chunk(
        q_nope, q_rope, jnp.int32(first), table, jnp.int32(kv_len), pool,
        w_kvb, kv_lora=8, scale=0.3, block=16)
    q = latent_attention.absorb_query(q_nope, q_rope, w_kvb[..., :8])
    o_lat = latent_attention.absorbed_reference(
        q, pool, first + 1 + jnp.arange(rows), jnp.tile(table, (rows, 1)),
        kv_lora=8, scale=0.3)
    absorbed = latent_attention.expand_output(o_lat, w_kvb[..., 8:],
                                              jnp.float32)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)


def test_the_expanded_form_is_causal_within_a_chunk():
    """Queries at positions 10..13 of one sequence see the keys at or
    before each: each row alone, by the absorbed form, agrees."""
    pool, w_kvb, q_nope, q_rope = latent_case(5, rows=4)
    table = jnp.arange(6, dtype=jnp.int32) + 3
    expanded = latent_attention.expanded_chunk(
        q_nope, q_rope, jnp.int32(10), table, jnp.int32(14), pool, w_kvb,
        kv_lora=8, scale=0.3, block=8)
    q = latent_attention.absorb_query(q_nope, q_rope, w_kvb[..., :8])
    o_lat = latent_attention.absorbed_reference(
        q, pool, 11 + jnp.arange(4), jnp.tile(table, (4, 1)), kv_lora=8,
        scale=0.3)
    np.testing.assert_allclose(
        np.asarray(latent_attention.expand_output(o_lat, w_kvb[..., 8:],
                                                  jnp.float32)),
        np.asarray(expanded), atol=1e-5)


@pytest.mark.parametrize('tokens,first,taken,block', [
    (8, 0, 8, 16), (8, 13, 5, 16), (4, 30, 4, 8), (16, 2, 16, 8),
    (16, 21, 9, 8), (16, 31, 16, 16)],
    ids=['from-zero', 'mid-page-padded', 'late', 'several-blocks',
         'whole-blocks-then-masked', 'block-ends-at-first'])
def test_the_prefill_kernel_equals_its_plain_form(monkeypatch, tokens, first,
                                                  taken, block):
    """The expanded kernel in the interpreter against ``expanded_chunk``:
    blocks of history copied and up-projected in fast memory, the chunk's
    own keys causal, its padding rows past ``taken``; the blocks wholly
    before the chunk's first position are taken unmasked."""
    monkeypatch.setattr(pallas_latent, 'PREFILL_BLOCK', block)
    pool, w_kvb, q_nope, q_rope = latent_case(first, rows=tokens)
    table = jnp.asarray(np.random.default_rng(3).permutation(40)[:6],
                        jnp.int32)
    kv_len = jnp.int32(first + taken)
    want = latent_attention.expanded_chunk(
        q_nope, q_rope, jnp.int32(first), table, kv_len, pool, w_kvb,
        kv_lora=8, scale=0.3, block=8)
    got = pallas_latent.expanded_prefill(
        q_nope, q_rope, jnp.int32(first), table, kv_len, pool, w_kvb,
        kv_lora=8, scale=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:taken],
                               np.asarray(want)[:taken], atol=1e-5)


@pytest.mark.parametrize('wave,lengths', [
    (8, (0, 37, 8)), (2, (17, 0, 40)), (1, (1, 9, 0)), (3, (40, 40, 40))],
    ids=['one-wave', 'several-waves', 'a-wave-a-page', 'ragged-waves'])
def test_the_decode_kernel_equals_its_plain_form(monkeypatch, wave, lengths):
    """The Pallas kernel in the interpreter: idle rows (length 0) return
    zeros, a row's last wave and last page are partial."""
    monkeypatch.setattr(pallas_latent, 'WAVE', wave)
    pool, w_kvb, q_nope, q_rope = latent_case(wave)
    q = latent_attention.absorb_query(q_nope, q_rope, w_kvb[..., :8])
    tables = jnp.asarray(np.random.default_rng(2).permutation(40)[:15]
                         .reshape(3, 5), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = latent_attention.absorbed_reference(q, pool, lengths, tables,
                                               kv_lora=8, scale=0.3)
    got = pallas_latent.absorbed_decode(q, pool, lengths, tables, kv_lora=8,
                                        scale=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    for row, length in enumerate(np.asarray(lengths)):
        if length == 0:
            assert not np.asarray(got)[row].any()


def test_the_step_programs_with_the_kernels_give_the_same_logits(
        tmp_path_factory):
    config = tiny_config()
    kernels = {'latent_decode': 'interpret', 'latent_prefill': 'interpret'}
    _, plain = build(tmp_path_factory, config, LM_CHUNK_BUCKETS='8')
    _, kernel = build(tmp_path_factory, config, LM_CHUNK_BUCKETS='8',
                      step_kernels=kernels)
    try:
        prompt = np.random.default_rng(12).integers(0, 64, 30)
        results = [generate(e, prompt, 5) for e in (plain, kernel)]
        assert kernel.stats()['lm']['step_kernels'] == kernels
    finally:
        kernel.close()
        plain.close()
    np.testing.assert_array_equal(results[0].token_ids, results[1].token_ids)
    np.testing.assert_allclose(program_logits(results[0]),
                               program_logits(results[1]), atol=TOLERANCE)


@pytest.mark.parametrize('first,count,chunk', [
    (0, 8, 8), (5, 4, 4), (6, 9, 16), (16, 1, 4)],
    ids=['page-aligned', 'across-a-page', 'three-pages', 'one-token'])
def test_a_chunk_s_latents_go_where_its_page_table_says(first, count, chunk):
    """A chunk's latents into its sequence's pages, whole pages read and
    written back; the slabs past its last page go to the spare page (11),
    and nothing else of the pool moves."""
    rng = np.random.default_rng(first)
    pool = jnp.asarray(rng.standard_normal((12, 3, 8)), jnp.float32)
    table = jnp.asarray([7, 2, 9, 4, 0], jnp.int32)
    latents = jnp.asarray(rng.standard_normal((chunk, 3)), jnp.float32)
    got = np.asarray(latent_attention.write_chunk(
        pool, table, jnp.int32(first), jnp.int32(count), latents, 11))
    want = np.asarray(pool).copy()
    for t in range(count):
        at = first + t
        want[int(table[at // 8]), :, at % 8] = np.asarray(latents[t])
    np.testing.assert_array_equal(got, want)


def test_decode_rows_write_one_position_each():
    """Two rows of sequences and two idle rows, which write the spare page
    (11): the rows' columns are written and every other page is as it
    was."""
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((12, 3, 8)), jnp.float32)
    latents = jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)
    rows = jnp.asarray([7 * 8 + 3, 2 * 8 + 7, 11 * 8, 11 * 8], jnp.int32)
    got = np.asarray(latent_attention.write_rows(pool, rows, latents))
    want = np.asarray(pool).copy()
    want[7, :, 3], want[2, :, 7] = np.asarray(latents[0]), \
        np.asarray(latents[1])
    np.testing.assert_array_equal(got[:11], want[:11])


# ------------------------------------------------------ the expert share
def moe_case(seed, tokens=24, hidden=16, width=8, routed=8, top_k=2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((hidden, routed)), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((routed, hidden, 2 * width))
                          / np.sqrt(hidden), jnp.float32)
    down = jnp.asarray(rng.standard_normal((routed, width, hidden))
                       / np.sqrt(width), jnp.float32)
    shared_gate_up = jnp.asarray(rng.standard_normal((hidden, 2 * width))
                                 / np.sqrt(hidden), jnp.float32)
    shared_down = jnp.asarray(rng.standard_normal((width, hidden))
                              / np.sqrt(width), jnp.float32)
    return x, router, gate_up, down, shared_gate_up, shared_down, top_k


def reference_layer(x, router, gate_up, down, shared_gate_up, shared_down,
                    top_k, first, held):
    """``reference_mistral4.moe`` over ``held`` experts from ``first``,
    the residual taken off."""
    width = down.shape[1]
    shared = shared_down.shape[0]
    layer = ref.LayerWeights(
        attn_norm=None, wq_a=None, q_norm=None, wq_b=None, wkv_a=None,
        kv_norm=None, wkv_b=None, wo=None,
        mlp_norm=jnp.ones((x.shape[1],)), router=router,
        w_gate=gate_up[first:first + held, :, :width],
        w_up=gate_up[first:first + held, :, width:],
        w_down=down[first:first + held],
        shared_gate=shared_gate_up[:, :shared],
        shared_up=shared_gate_up[:, shared:], shared_down=shared_down)
    config = {'rms_norm_eps': 1e-6, 'n_routed_experts': held,
              'first_held_expert': first, 'num_experts_per_tok': top_k,
              'norm_topk_prob': True, 'routed_scaling_factor': 1}
    return np.asarray(ref.moe(config, layer, x) - x)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four ranks holds a quarter of the routed experts and routes
    over all of them; the parts the ranks compute, with the shared expert
    (which every rank computes alike) counted once, add up to the uncut
    reference layer.  Each rank's part is the reference's for that share,
    and every routing choice is held by exactly one rank."""
    x, router, gate_up, down, s_gate_up, s_down, top_k = moe_case(0)
    z = ref.rms_norm(x, jnp.ones((x.shape[1],)), 1e-6)
    probs, chosen = grouped_experts.route(z, router, top_k, True)
    total, counted = 0.0, 0
    for rank in range(4):
        first = 2 * rank
        part, held = grouped_experts.expert_ffn(
            z, probs, chosen, gate_up[first:first + 2],
            down[first:first + 2], first=first)
        np.testing.assert_allclose(
            np.asarray(part) + np.asarray(grouped_experts.shared_expert(
                z, s_gate_up, s_down)),
            reference_layer(x, router, gate_up, down, s_gate_up, s_down,
                            top_k, first, 2), atol=1e-5)
        total = total + np.asarray(part)
        counted += int(held.sum())
    total = total + np.asarray(grouped_experts.shared_expert(z, s_gate_up,
                                                             s_down))
    np.testing.assert_allclose(
        total, reference_layer(x, router, gate_up, down, s_gate_up, s_down,
                               top_k, 0, 8), atol=1e-5)
    assert counted == x.shape[0] * top_k


def test_every_expert_held_is_the_layer_as_it_was():
    """Mellum's form: every expert held, no shared expert.  The share's
    path with ``first`` 0 and the layer with no share agree to the bit, and
    both are the per-token statement of the layer."""
    x, router, gate_up, down, _, _, _ = moe_case(1, tokens=40, hidden=24,
                                                 width=12, routed=16,
                                                 top_k=8)
    probs, chosen = grouped_experts.route(x, router, 8, True)
    valid = jnp.asarray(np.arange(40) < 37)
    plain, plain_counts = grouped_experts.expert_ffn(x, probs, chosen,
                                                     gate_up, down, valid)
    shared, shared_counts = grouped_experts.expert_ffn(
        x, probs, chosen, gate_up, down, valid, first=0)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(shared))
    np.testing.assert_array_equal(np.asarray(plain_counts),
                                  np.asarray(shared_counts))
    want = np.zeros((40, 24))
    for t in range(40):
        for p, e in zip(np.asarray(probs[t]), np.asarray(chosen[t])):
            h = np.asarray(x[t]) @ np.asarray(gate_up[e])
            inner = h[:12] / (1 + np.exp(-h[:12])) * h[12:]
            want[t] += p * inner @ np.asarray(down[e])
    np.testing.assert_allclose(np.asarray(plain), want, atol=1e-4)
    assert int(plain_counts.sum()) == 37 * 8


def test_the_grouped_product_s_tiles_stay_as_they_were_for_mellum():
    """The weight block of the grouped product is halved only where it
    would crowd fast memory: at Mellum's widths the tiles are as before."""
    def tile(k, n):
        tile_n = n // 2 if (n // 2) % 128 == 0 else n
        while k * tile_n * 2 > grouped_experts.WEIGHT_TILE_BYTES and \
                (tile_n // 2) % 128 == 0:
            tile_n //= 2
        return tile_n
    assert tile(2304, 1792) == 896 and tile(896, 2304) == 1152
    assert tile(4096, 4096) == 512 and tile(2048, 4096) == 1024


# ------------------------------------------- the cell's controls (tiny)
@pytest.fixture(scope='module')
def rehearsal_turns(tmp_path_factory):
    """The committed configuration at its ``rehearsal`` widths, in the
    exact mode: a resident session of 150 positions takes two turns, as the
    cell's check reads them."""
    import types
    from chipbench import run
    from chipbench.runners import common
    with open(CELL_CONFIG) as f:
        published = json.load(f)
    spec = run.merged(published, published['rehearsal'])
    config = {k: spec[k] for k in serve_lm_latent.MODEL_KEYS if k in spec}
    path = tmp_path_factory.mktemp('controls') / 'config.json'
    path.write_text(json.dumps(config))
    ctx = types.SimpleNamespace(
        settings=dict(spec['settings'], COMPUTE_DTYPE='float32'),
        cell=types.SimpleNamespace(config_name=published['name']))
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
            np.concatenate(rows), np.concatenate(got))


#: what the exact mode is held to: it reads under 1e-4 of the logits' spread
EXACT = {'relative_error': 1e-3, 'share_beyond': 0.0,
         'relative_error_cap': 1e-3}


def test_the_cell_s_judge_caps_the_worst_position_only_where_written():
    """The cell's tolerance writes no ``relative_error_cap``: one position
    far off passes where the share beyond ``relative_error`` holds, and a
    written cap still refuses it."""
    errors = np.array([0.01, 0.02, 0.03, 5.0])
    with open(CELL_CONFIG) as f:
        written = json.load(f)['check']['tolerance']
    assert 'relative_error_cap' not in written
    assert serve_lm_latent.judge(errors, written) == []
    assert serve_lm_latent.judge(errors, dict(written, share_beyond=0.2))
    assert serve_lm_latent.judge(errors, dict(written,
                                              relative_error_cap=1.0))


def test_the_runner_times_full_collections_only():
    hook = serve_lm_latent.FullCollections()
    gc.callbacks.append(hook)
    try:
        gc.collect(0)
        gc.collect(1)
        gc.collect()
    finally:
        gc.callbacks.remove(hook)
    assert len(hook.pauses) == 1
    assert hook.pauses[0][1] >= 0.0


def test_the_reference_proper_is_held(rehearsal_turns):
    config, params, history, rows, got = rehearsal_turns
    want = controls_mistral4.references(config, params)['reference'](
        history, rows)
    assert serve_lm.judge(serve_lm.compare_logits(got, want), EXACT) == []


@pytest.mark.parametrize('control', controls_mistral4.CONTROLS)
def test_each_control_comes_out_not_correct(rehearsal_turns, control):
    """The reference with one thing wrong (``chipbench/
    controls_mistral4.py``, the chip's control entry) through the judge:
    matrices or the latent cache rounded to float8's mantissa, the query
    scale left out, the rotate-half pairing.  Each moves the logits beyond
    what the exact mode holds; the cell's written limits are set against
    the same four on the chip (PERF.md, section 4)."""
    config, params, history, rows, got = rehearsal_turns
    wrong = controls_mistral4.references(config, params)[control](history,
                                                                  rows)
    errors = serve_lm.compare_logits(got, wrong)
    assert serve_lm.judge(errors, EXACT) != []
    assert np.median(errors) > 1e-2
