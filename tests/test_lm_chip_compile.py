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
import re

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
    shape = decoder_lib.step_shape(cfg, g, 0, 512)
    assert shape == decoder_lib.StepShape(
        tokens=16, chunk=0, outputs=17, full_seqs=16,
        full_pages=g.pages_per_seq, window_seqs=16,
        window_pages=g.window_table_pages(1))
    step = decoder_lib.make_step(cfg, shape, g)
    layout = lm_scheduler.pack_layout(decoder_lib.batch_shapes(shape))

    def run(params, cache, prev_ids, packed):
        return step(params, cache, prev_ids,
                    lm_scheduler.unpack_batch(packed, layout))
    params = jax.tree_util.tree_map(
        lambda s: shaped(one_chip, s.shape, s.dtype),
        decoder_lib.param_shapes(cfg))
    cache = {name: shaped(one_chip, dims, jnp.bfloat16)
             for name, dims in decoder_lib.cache_shapes(cfg, g).items()}
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, shaped(one_chip, (17,), jnp.int32),
        shaped(one_chip, (layout[''][0],), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    pools = sum(int(np.prod(c.shape)) * 2 for c in cache.values())
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < 256 * 2 ** 20
    assert compiled.as_text().count('tpu_custom_call') == 3 * 4


# ---------------------------------------------------------------------------
# the linear-attention and block-sparse layers (models/hybrid_decoder.py)
from code2vec_tpu.models import hybrid_decoder as hybrid_lib  # noqa: E402
from chipbench.layer_metrics import lmhybridkernels  # noqa: E402
from code2vec_tpu.ops import linear_attention, pallas_sparse  # noqa: E402
from code2vec_tpu.ops import sparse_attention  # noqa: E402

SALA = {
    'attention_bias': False, 'attn_use_rope': False, 'head_dim': 128,
    'hidden_act': 'silu', 'hidden_size': 4096, 'intermediate_size': 16384,
    'lightning_head_dim': 128, 'lightning_nh': 32, 'lightning_nkv': 32,
    'lightning_scale': '1/sqrt(d)', 'lightning_use_rope': True,
    'mixer_types': (['minicpm4'] + ['lightning-attn'] * 8 + ['minicpm4']
                    + ['lightning-attn'] * 6 + ['minicpm4'] * 2
                    + ['lightning-attn'] * 4 + ['minicpm4']
                    + ['lightning-attn'] * 6 + ['minicpm4'] * 3),
    'num_attention_heads': 32, 'num_key_value_heads': 2, 'qk_norm': True,
    'rms_norm_eps': 1e-6, 'vocab_size': 73448, 'rope_theta': 10000,
    'scale_emb': 12, 'scale_depth': 1.4, 'dim_model_base': 256,
    'tie_word_embeddings': False, 'use_output_gate': True,
    'use_output_norm': True, 'attn_use_output_gate': True,
    # one period: a sparse layer and the three lightning layers after it
    'first_hidden_layer': 9, 'num_hidden_layers': 4}
SALA_GEO = sparse_attention.SparseGeometry(**hybrid_lib.SPARSE_DEFAULTS)
#: the cell's pools: 8 sessions, 5,600 pages of 128, contexts up to 152K
SALA_SLOTS, SALA_POOL_PAGES, SALA_SEQ_PAGES = 8, 5600, 1216


def test_the_published_depth_counts_its_parameters():
    cfg = hybrid_lib.HybridConfig.from_dict(
        dict(SALA, first_hidden_layer=0, num_hidden_layers=32))
    assert cfg.mixer_types.count('lightning-attn') == 24
    assert 9.47e9 < cfg.parameters() < 9.49e9
    cut = hybrid_lib.HybridConfig.from_dict(
        dict(SALA, first_hidden_layer=9, num_hidden_layers=16))
    assert cut.mixer_types.count('minicpm4') == 4
    assert 5.038e9 < cut.parameters() < 5.041e9


@pytest.mark.parametrize('tokens', [2048, 256], ids=['chunk', 'short-chunk'])
def test_chunked_scan_compiles_at_published_widths(one_chip, tokens):
    compiled = jax.jit(linear_attention.chunk_scan).lower(
        shaped(one_chip, (tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (32,), jnp.float32),
        shaped(one_chip, (tokens,), jnp.int32),
        shaped(one_chip, (32, 128, 128), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_decode_update_compiles_at_published_widths(one_chip):
    compiled = jax.jit(linear_attention.decode_update).lower(
        shaped(one_chip, (8, 32, 128), jnp.bfloat16),
        shaped(one_chip, (8, 32, 128), jnp.bfloat16),
        shaped(one_chip, (8, 32, 128), jnp.bfloat16),
        shaped(one_chip, (32,), jnp.float32),
        shaped(one_chip, (8,), jnp.int32),
        shaped(one_chip, (8, 32, 128, 128), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_selection_compiles_at_published_widths(one_chip):
    """Stage 1 and the exact choice for a tile of 32 queries against the
    stride rows of a 152K context."""
    def select(q, positions, means):
        scores = sparse_attention.block_scores(q, positions, means,
                                               SALA_GEO)
        return sparse_attention.choose(scores, SALA_GEO.topk)
    compiled = jax.jit(select).lower(
        shaped(one_chip, (32, 32, 128), jnp.bfloat16),
        shaped(one_chip, (32,), jnp.int32),
        shaped(one_chip, (SALA_SEQ_PAGES * 8, 2, 128),
               jnp.bfloat16)).compile()
    assert 'sort(' not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize('tokens', [2048, 1], ids=['chunk', 'decode-row'])
def test_block_sparse_attention_compiles_at_published_widths(one_chip,
                                                             tokens):
    """Both stages over the cell's pool; the pool is read where it lies:
    no copy of it in another layout."""
    pool = (2, (SALA_POOL_PAGES + 1) * 2, 2, 64, 128)

    def attend(q, positions, live, means, table, pages):
        return sparse_attention.sparse_attention_chunk(
            q, positions, live, means, table, pages, SALA_GEO, 128)
    compiled = jax.jit(attend).lower(
        shaped(one_chip, (tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (tokens,), jnp.int32),
        shaped(one_chip, (tokens,), jnp.int32),
        shaped(one_chip, (SALA_SEQ_PAGES * 8, 2, 128), jnp.bfloat16),
        shaped(one_chip, (SALA_SEQ_PAGES,), jnp.int32),
        shaped(one_chip, pool, jnp.bfloat16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < int(np.prod(pool)) * 2 // 2


@pytest.fixture
def kernels_on_the_chip(monkeypatch):
    """The kernel asks the platform of ``jax.devices()``, here the CPU's,
    whether it may run compiled: steer it in the test."""
    monkeypatch.setattr(pallas_sparse, 'resolve_interpret',
                        lambda interpret, what, mesh=None: False)


def kernel_scopes(text: str) -> set:
    """What the benchmark's reader makes of the Pallas calls of a compiled
    program: the kernel each custom call's instruction is counted under."""
    scopes = lmhybridkernels.scopes_of(text)
    calls = [re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+) = ', line)
             for line in text.splitlines() if 'tpu_custom_call' in line]
    assert calls and all(calls)
    return {scopes.get(call.group(1)) for call in calls}


@pytest.mark.parametrize('rows,tokens', [(32, 32), (8, 1)],
                         ids=['chunk-1024', 'decode-rows-8'])
def test_stage_2_kernel_compiles_at_published_widths(
        one_chip, kernels_on_the_chip, rows, tokens):
    """The kernel alone over the cell's pool: a 1,024 chunk's 32 tiles of
    32 queries, or eight decode rows; the benchmark's reader counts its
    instruction under ``sparse_attention``."""
    pool = (2, (SALA_POOL_PAGES + 1) * 2, 2, 64, 128)
    span = (SALA_GEO.window_size + tokens) // 64 + 2
    slots = SALA_GEO.topk - SALA_GEO.window_size // 64

    def attend(q, live, near, mask, far, count, pages):
        plan = sparse_attention.BlockPlan(live, near, mask, far, count)
        with jax.named_scope('sparse_attention'):
            return pallas_sparse.attend_planned(q, plan, pages)
    compiled = jax.jit(attend).lower(
        shaped(one_chip, (rows, tokens, 32, 128), jnp.bfloat16),
        shaped(one_chip, (rows,), jnp.bool_),
        shaped(one_chip, (rows, span), jnp.int32),
        shaped(one_chip, (rows, 2, tokens, span * 64), jnp.int8),
        shaped(one_chip, (rows, tokens, 2, slots), jnp.int32),
        shaped(one_chip, (rows, tokens, 2), jnp.int32),
        shaped(one_chip, pool, jnp.bfloat16)).compile()
    assert kernel_scopes(compiled.as_text()) == {'sparse_attention'}
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_decode_rows_choose_their_blocks_in_a_loop_at_published_widths(
        one_chip, kernels_on_the_chip):
    """The decode rows' sparse branch at the cell's sizes (8 rows, tables
    of 1,280 pages: 10,240 stride rows and 2,560 blocks, top-64), stage 2
    the kernel: the live rows are one loop and a row's pieces another, not
    unrolled, so the program holds one gather of stride rows (a piece of
    one row's table) where the eight rows' whole tables were one gather of
    42 MB; and the kernel is counted under ``sparse_attention``."""
    pool = (2, (SALA_POOL_PAGES + 1) * 2, 2, 64, 128)
    pooled = (SALA_POOL_PAGES + 1, 8, 2, 128)

    def rows(q, positions, live, pooled, tables, pages):
        return sparse_attention.sparse_attention_rows(
            q, positions, live, pooled, tables, pages, SALA_GEO, 128,
            kernel=pallas_sparse.attend_planned)
    lowered = jax.jit(rows).lower(
        shaped(one_chip, (SALA_SLOTS, 32, 128), jnp.bfloat16),
        shaped(one_chip, (SALA_SLOTS,), jnp.int32),
        shaped(one_chip, (SALA_SLOTS,), jnp.int32),
        shaped(one_chip, pooled, jnp.bfloat16),
        shaped(one_chip, (SALA_SLOTS, 1280), jnp.int32),
        shaped(one_chip, pool, jnp.bfloat16))
    text = lowered.as_text()
    piece = 1280 // sparse_attention.ROW_PIECES
    gathers = re.findall(r'slice_sizes = array<i64: 1, 8, 2, 128>', text)
    assert len(gathers) == 1
    assert 'tensor<%dx8x2x128xbf16>' % piece in text
    assert len(text) < 500_000
    compiled = lowered.compile()
    assert kernel_scopes(compiled.as_text()) == {'sparse_attention'}
    # one piece of one row's stride rows is 1.3 MB; eight rows' tables 42
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


@pytest.mark.parametrize('chunk', [2048, 0], ids=['chunk', 'decode-only'])
def test_a_period_of_the_hybrid_step_compiles_and_fits(
        one_chip, kernels_on_the_chip, chunk):
    """A sparse layer and three lightning layers, a 2,048 chunk beside 8
    decode rows (or the rows alone) at the cell's pool sizes, with the
    kernels a TPU's step programs take: every pool is donated and aliased,
    no step copies or re-lays one, the temporaries leave the chip room for
    sixteen layers' weights, and stage 2 is the kernel, counted under
    ``sparse_attention``."""
    cfg = hybrid_lib.HybridConfig.from_dict(SALA)
    g = lm_cache.CacheGeometry.make(
        page_size=128, window=0, slots=SALA_SLOTS,
        pool_pages=SALA_POOL_PAGES, max_context=SALA_SEQ_PAGES * 128,
        max_chunk=2048)
    shape = hybrid_lib.step_shape(cfg, g, chunk, 512)
    assert shape == hybrid_lib.StepShape(
        tokens=SALA_SLOTS + chunk, chunk=chunk, outputs=SALA_SLOTS + 1,
        slots=SALA_SLOTS, full_seqs=SALA_SLOTS + (1 if chunk else 0),
        full_pages=g.pages_per_seq,
        strides=SALA_SLOTS + (chunk // 16 + 1 if chunk else 0))
    step = hybrid_lib.make_step(cfg, shape, g,
                                **hybrid_lib.step_kernels('tpu'))
    layout = lm_scheduler.pack_layout(hybrid_lib.batch_shapes(shape))

    def run(params, cache, prev_ids, packed):
        return step(params, cache, prev_ids,
                    lm_scheduler.unpack_batch(packed, layout))
    params = jax.tree_util.tree_map(
        lambda s: shaped(one_chip, s.shape, s.dtype),
        hybrid_lib.param_shapes(cfg))
    dtypes = hybrid_lib.cache_dtypes(jnp.bfloat16)
    cache = {name: shaped(one_chip, dims, dtypes[name])
             for name, dims in hybrid_lib.cache_shapes(cfg, g).items()}
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, shaped(one_chip, (SALA_SLOTS + 1,), jnp.int32),
        shaped(one_chip, (layout[''][0],), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    pools = sum(int(np.prod(c.shape)) * np.dtype(c.dtype).itemsize
                for c in cache.values())
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < (640 if chunk else 64) * 2 ** 20
    assert kernel_scopes(compiled.as_text()) == {'sparse_attention'}


# ---------------------------------------------------------------------------
# latent attention and a share of the experts (models/latent_decoder.py)
from chipbench.layer_metrics import lmlatentkernels  # noqa: E402
from code2vec_tpu.models import latent_decoder as latent_lib  # noqa: E402
from code2vec_tpu.ops import pallas_latent  # noqa: E402

MISTRAL4 = {
    'attention_bias': False, 'first_k_dense_replace': 0, 'hidden_act': 'silu',
    'hidden_size': 4096, 'kv_lora_rank': 256, 'mlp_bias': False,
    'moe_intermediate_size': 2048, 'n_group': 1, 'n_routed_experts': 32,
    'n_routed_experts_published': 128, 'first_held_expert': 0,
    'n_shared_experts': 1, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 4, 'q_lora_rank': 1024, 'qk_nope_head_dim': 64,
    'qk_rope_head_dim': 64, 'v_head_dim': 128, 'rms_norm_eps': 1e-6,
    'rope_interleave': True, 'routed_scaling_factor': 1,
    'tie_word_embeddings': False, 'topk_group': 1, 'vocab_size': 32768,
    'rope_parameters': {
        'beta_fast': 32, 'beta_slow': 1, 'factor': 128,
        'llama_4_scaling_beta': 0.1, 'mscale': 1, 'mscale_all_dim': 1,
        'original_max_position_embeddings': 8192, 'rope_theta': 10000,
        'rope_type': 'yarn'},
    # one layer: every layer is the same
    'num_hidden_layers': 1}
#: the cell's pool: 16 decode rows, 8,000 pages of 128, contexts to 144K
MISTRAL4_POOL_PAGES, MISTRAL4_SEQ_PAGES = 8000, 1152


@pytest.fixture
def latent_kernel_on_the_chip(monkeypatch):
    monkeypatch.setattr(pallas_latent, 'resolve_interpret',
                        lambda interpret, what, mesh=None: False)
    monkeypatch.setattr(grouped_experts, 'on_tpu', lambda: True)


def test_latent_decode_kernel_compiles_at_published_widths(
        one_chip, latent_kernel_on_the_chip):
    """The absorbed kernel alone over six layers' pool: sixteen decode
    rows, the pool read where it lies, no copy of it."""
    def decode(q, pool, lengths, tables):
        with jax.named_scope('lm/latent_decode'):
            return pallas_latent.absorbed_decode(q, pool, lengths, tables,
                                                 kv_lora=256, scale=0.19)
    compiled = jax.jit(decode).lower(
        shaped(one_chip, (16, 32, 320), jnp.bfloat16),
        shaped(one_chip, (6 * (MISTRAL4_POOL_PAGES + 1), 320, 128),
               jnp.bfloat16),
        shaped(one_chip, (16,), jnp.int32),
        shaped(one_chip, (16, MISTRAL4_SEQ_PAGES), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    calls = [re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+) = ', line).group(1)
             for line in text.splitlines() if 'tpu_custom_call' in line]
    scopes = lmlatentkernels.scopes_of(text)
    assert {scopes.get(call) for call in calls} == {'latent_decode'}


@pytest.mark.parametrize('tokens', [2048, 1536, 384, 256],
                         ids=['chunk-2048', 'chunk-1536', 'chunk-384',
                              'chunk-256'])
def test_latent_prefill_kernel_compiles_at_published_widths(
        one_chip, latent_kernel_on_the_chip, tokens):
    """The expanded kernel alone over a layer's pool and the longest page
    table: its scores stay in fast memory (no temporaries but the inputs'
    head-major copies)."""
    def prefill(q_nope, q_rope, first, table, kv_len, pool, w_kvb):
        with jax.named_scope('lm/latent_prefill'):
            return pallas_latent.expanded_prefill(
                q_nope, q_rope, first, table, kv_len, pool, w_kvb,
                kv_lora=256, scale=0.19)
    compiled = jax.jit(prefill).lower(
        shaped(one_chip, (tokens, 32, 64), jnp.bfloat16),
        shaped(one_chip, (tokens, 32, 64), jnp.bfloat16),
        shaped(one_chip, (), jnp.int32),
        shaped(one_chip, (MISTRAL4_SEQ_PAGES,), jnp.int32),
        shaped(one_chip, (), jnp.int32),
        shaped(one_chip, (MISTRAL4_POOL_PAGES + 1, 320, 128), jnp.bfloat16),
        shaped(one_chip, (256, 32, 192), jnp.bfloat16)).compile()
    text = compiled.as_text()
    calls = [re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+) = ', line).group(1)
             for line in text.splitlines() if 'tpu_custom_call' in line]
    scopes = lmlatentkernels.scopes_of(text)
    assert {scopes.get(call) for call in calls} == {'latent_prefill'}
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize('chunk', [2048, 768, 0],
                         ids=['chunk', 'chunk-768', 'decode-only'])
def test_a_layer_of_the_latent_step_compiles_and_fits(
        one_chip, latent_kernel_on_the_chip, chunk):
    """One layer (every layer is the same) of the step at the cell's pool
    and page table, a 2,048 chunk beside 16 decode rows or the rows alone:
    the latent pool is donated and aliased, no step copies or re-lays it
    (a position's column written alone made the compiler lay the whole
    pool out position-major, padded, and copy it every step), and the
    benchmark's reader counts the two latent kernels and the held experts'
    grouped products under their scopes."""
    cfg = latent_lib.LatentConfig.from_dict(MISTRAL4)
    g = lm_cache.CacheGeometry.make(
        page_size=128, window=0, slots=16, pool_pages=MISTRAL4_POOL_PAGES,
        max_context=MISTRAL4_SEQ_PAGES * 128, max_chunk=2048)
    shape = latent_lib.step_shape(cfg, g, chunk, 512)
    step = latent_lib.make_step(cfg, shape, g,
                                **latent_lib.step_kernels('tpu'))
    layout = lm_scheduler.pack_layout(latent_lib.batch_shapes(shape))

    def run(params, cache, prev_ids, packed):
        return step(params, cache, prev_ids,
                    lm_scheduler.unpack_batch(packed, layout))
    params = jax.tree_util.tree_map(
        lambda s: shaped(one_chip, s.shape, s.dtype),
        latent_lib.param_shapes(cfg))
    cache = {name: shaped(one_chip, dims, jnp.bfloat16)
             for name, dims in latent_lib.cache_shapes(cfg, g).items()}
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, shaped(one_chip, (17,), jnp.int32),
        shaped(one_chip, (layout[''][0],), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    pool = int(np.prod(cache['latents'].shape)) * 2
    assert memory.alias_size_in_bytes >= pool
    assert memory.temp_size_in_bytes < (320 if chunk else 32) * 2 ** 20
    text = compiled.as_text()
    scopes = lmlatentkernels.scopes_of(text)
    calls = [re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+) = ', line).group(1)
             for line in text.splitlines() if 'tpu_custom_call' in line]
    assert {scopes.get(call) for call in calls} == {
        'latent_decode', 'experts'} | ({'latent_prefill'} if chunk else set())
