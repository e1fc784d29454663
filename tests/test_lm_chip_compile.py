"""The language model's TPU paths compiled at the published widths for a
described (not attached) TPU v5e: what the CPU tests cannot see.  The CPU
tests run ``jax.numpy`` forms of the attention and of the grouped expert
product; on the chip those are Pallas kernels, and the chip's compiler is
what refuses a tile that is not aligned, a kernel that needs more fast
memory than it may use, or a step that does not fit the device.  Nothing
runs here: a compile that passes is not a chip run.

All in this one file, the topology described inside a fixture (never while
a module is imported), so that one worker loads the TPU's library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from code2vec_tpu.models import decoder as decoder_lib
from code2vec_tpu.ops import grouped_experts, lm_attention
from code2vec_tpu.serving import lm_cache, lm_scheduler

PUBLISHED = {
    'head_dim': 128, 'hidden_size': 2304, 'moe_intermediate_size': 896,
    'norm_topk_prob': True, 'num_attention_heads': 32, 'num_experts': 64,
    'num_experts_per_tok': 8, 'num_key_value_heads': 4,
    'rms_norm_eps': 1e-6, 'sliding_window': 1024, 'vocab_size': 98304,
    'num_hidden_layers': 4,
    'layer_types': ['sliding_attention'] * 3 + ['full_attention'],
    'rope_parameters': {
        'full_attention': {
            'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
            'original_max_position_embeddings': 8192, 'beta_fast': 32,
            'beta_slow': 1, 'attention_factor': 1.2772588722239782},
        'sliding_attention': {'rope_type': 'default',
                              'rope_theta': 500000}}}


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:    # no TPU compiler here: nothing to check
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The code asks ``jax.default_backend()`` and would take its CPU
    branch: steer it in the test, not through an option of the program."""
    monkeypatch.setattr(lm_attention, 'on_tpu', lambda: True)
    monkeypatch.setattr(grouped_experts, 'on_tpu', lambda: True)


def shaped(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize('tokens,seqs,pages,window,prefill', [
    (2064, 20, 14, 1024, True),      # a 2,048 chunk beside 16 decode rows
    (2064, 17, 194, None, True),
    (16, 16, 10, 1024, False),       # decode rows only
    (16, 16, 194, None, False)],
    ids=['window-chunk', 'full-chunk', 'window-decode', 'full-decode'])
def test_paged_attention_compiles_at_published_widths(
        one_chip, as_on_the_chip, tokens, seqs, pages, window, prefill):
    def attend(q, kv, kv_lens, table, cu, n):
        return lm_attention.paged_attention(
            q, kv, kv_lens, table, cu, n, sm_scale=128 ** -0.5,
            sliding_window=window, prefill=prefill)
    compiled = jax.jit(attend).lower(
        shaped(one_chip, (tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (1707, 128, 8, 128), jnp.bfloat16),
        shaped(one_chip, (seqs,), jnp.int32),
        shaped(one_chip, (seqs, pages), jnp.int32),
        shaped(one_chip, (seqs + 1,), jnp.int32),
        shaped(one_chip, (1,), jnp.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.parametrize('tokens', [16, 2064], ids=['decode', 'chunk'])
def test_expert_layer_compiles_at_published_widths(one_chip, as_on_the_chip,
                                                   tokens):
    def experts(x, probs, chosen, gate_up, down):
        return grouped_experts.expert_ffn(x, probs, chosen, gate_up, down)
    compiled = jax.jit(experts).lower(
        shaped(one_chip, (tokens, 2304), jnp.bfloat16),
        shaped(one_chip, (tokens, 8), jnp.float32),
        shaped(one_chip, (tokens, 8), jnp.int32),
        shaped(one_chip, (64, 2304, 1792), jnp.bfloat16),
        shaped(one_chip, (64, 896, 2304), jnp.bfloat16)).compile()
    assert compiled.as_text().count('tpu_custom_call') == 2


def test_a_period_of_the_decode_step_compiles_and_updates_in_place(
        one_chip, as_on_the_chip):
    """One period (three sliding layers and a full one) of the decode-only
    step at the cell's pool sizes: both pools are donated and aliased, so
    no step copies a pool."""
    cfg = decoder_lib.DecoderConfig.from_dict(PUBLISHED)
    g = lm_cache.CacheGeometry.make(page_size=128, window=1024, slots=16,
                                    pool_pages=1706, max_context=24832,
                                    max_chunk=2048)
    shape = decoder_lib.StepShape(
        tokens=16, chunk=0, outputs=17, full_seqs=16,
        full_pages=g.pages_per_seq, window_seqs=16,
        window_pages=g.window_table_pages(1))
    step = decoder_lib.make_step(cfg, shape, g.ring_layer_pages,
                                 g.pool_layer_pages)
    layout = lm_scheduler.pack_layout(shape)

    def run(params, cache, prev_ids, packed):
        return step(params, cache, prev_ids,
                    lm_scheduler.unpack_batch(packed, layout))
    params = jax.tree_util.tree_map(
        lambda s: shaped(one_chip, s.shape, s.dtype),
        decoder_lib.param_shapes(cfg))
    cache = {name: shaped(one_chip, dims, jnp.bfloat16)
             for name, dims in decoder_lib.cache_shapes(
                 cfg, g.ring_layer_pages, g.pool_layer_pages, 128).items()}
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, shaped(one_chip, (17,), jnp.int32),
        shaped(one_chip, (layout[''][0],), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    pools = sum(int(np.prod(c.shape)) * 2 for c in cache.values())
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < 256 * 2 ** 20
    assert compiled.as_text().count('tpu_custom_call') == 3 * 4
