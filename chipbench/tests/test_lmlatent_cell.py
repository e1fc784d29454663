"""The latent-attention cell (``serve-mistral4-docqa``): it was added as new
files and appended entries only; its rehearsal prints every metric a CPU
can give; ``work_mistral4.py`` counts what a hand count gives; the reader of
the kernels' seconds knows an instruction by its scope."""
import numpy as np

from chipbench import manifest, work_lm, work_mistral4
from chipbench.layer_metrics import lmlatentkernels
from chipbench.tests.test_rehearsal import check_rehearsal

ROOT = manifest.ROOT
CELL = 'serve-mistral4-docqa'


def test_the_cell_is_as_specified():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ('mistral-small-4-119b-ep4-l6', 'docqa-turns', 1)
    config, mix = cell.config, cell.traffic
    assert config['source']['url'] == (
        'https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/'
        'main/config.json')
    published = {'hidden_size': 4096, 'num_attention_heads': 32,
                 'q_lora_rank': 1024, 'kv_lora_rank': 256,
                 'qk_nope_head_dim': 64, 'qk_rope_head_dim': 64,
                 'v_head_dim': 128, 'moe_intermediate_size': 2048,
                 'n_shared_experts': 1, 'num_experts_per_tok': 4,
                 'first_k_dense_replace': 0, 'rope_interleave': True}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config['num_hidden_layers'], config['n_routed_experts'],
            config['vocab_size']) == (6, 32, 32768)
    assert config['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                 'vocab_size']
    assert config['settings']['num_hidden_layers'] == 6
    assert (config['n_routed_experts_published'],
            config['first_held_expert']) == (128, 0)
    assert config['parameters'] == 5422771712
    assert {'router_scoring', 'softmax_scale', 'query_scale',
            'vision_tower'} <= set(config['assumed'])
    arrivals = mix['arrivals']
    assert (4 * arrivals['rate_per_s']).is_integer()   # a quarter turn/s
    assert arrivals['sessions'] == {'count': 16, 'median': 40960,
                                    'sigma': 0.6, 'min': 16384,
                                    'max': 131072}
    assert arrivals['turns'] == {'count': 64, 'median': 512, 'sigma': 0.5,
                                 'min': 128, 'max': 2048, 'new_tokens': 32}
    assert mix['generator'] == 'session_turns' and mix['check_turns'] == 2
    wanted = {m['name'] for m in cell.per_layer}
    for name in ('lmlatent.ttft_ms_p50', 'lmlatent.prefill_chunk_ms_p50',
                 'lmlatent.decode_step_ms_p50', 'lmlatent.tokens_per_step',
                 'lmlatent.page_pool_fill_share',
                 'lmlatent.held_choice_share',
                 'lmlatent.expert_load_max_over_mean', 'lmlatent.step_mfu',
                 'lmlatentkernels.latent_decode_roofline',
                 'lmlatentkernels.latent_prefill_roofline',
                 'lmlatentkernels.experts_roofline',
                 'engine.queue_depth_mean', 'engine.p95_ms',
                 'engine.warmup_s', 'loadgen.offered_per_s',
                 'device.peak_hbm_bytes-serve'):
        assert name in wanted, name
    assert not any(name.startswith(('lm.', 'lmkernels.', 'lmhybrid'))
                   for name in wanted)
    for metric in cell.per_layer:
        if metric['name'].startswith('lmlatent'):
            assert metric['workloads'] == [CELL]
    assert {m['name'] for m in cell.end_to_end} == {'serve_p50_ms',
                                                    'setup_s'}


def test_rehearsal_gives_every_metric_a_cpu_can(tmp_path):
    cell = manifest.load_cell(CELL)
    plain, traced = check_rehearsal(cell, str(tmp_path / 'jax_cache'))
    assert set(plain['metrics']) == {'serve_p50_ms', 'setup_s'}
    device_only = {m['name'] for m in cell.per_layer
                   if m['source'] == 'device_trace'}
    assert set(traced['metrics']) == \
        {m['name'] for m in cell.per_layer} - device_only
    assert 10 < traced['metrics']['lmlatent.held_choice_share']['value'] \
        < 50


# ------------------------------------------------------------ hand counts
CONFIG = {'num_hidden_layers': 6, 'num_attention_heads': 32,
          'kv_lora_rank': 256, 'qk_nope_head_dim': 64,
          'qk_rope_head_dim': 64, 'v_head_dim': 128, 'hidden_size': 4096,
          'q_lora_rank': 1024, 'moe_intermediate_size': 2048,
          'n_shared_experts': 1, 'n_routed_experts': 32,
          'n_routed_experts_published': 128, 'vocab_size': 32768}
PEAKS = {'flops_per_s_bf16': 197e12, 'hbm_bytes_per_s': 819e9}


def test_a_decode_step_by_hand():
    """Two decode rows at 40,000 and 99,999 in six layers; 10 held
    choices over the layers, 9 experts touched."""
    step = {'chunk_tokens': 0, 'chunk_first': 0,
            'decode_positions': np.asarray([40000, 99999]),
            'held_choices': 10, 'experts_touched': [2, 1, 2, 1, 2, 1]}
    work = work_mistral4.step_work(CONFIG, step)
    keys = 40001 + 100000
    scores = 2 * 32 * (320 + 256) * keys
    absorb = 2 * 2 * 32 * 256 * (64 + 128)
    assert work['latent_decode']['flops'] == 6 * (scores + absorb)
    # every key's 320 latents once for all heads, W_kvb once, q in and o
    # out a row
    assert work['latent_decode']['hbm_bytes'] == 6 * (
        2 * 320 * keys + 2 * 256 * 32 * 192 + 2 * 32 * (2 * 128 + 4 * 128))
    assert work['latent_prefill'] == {'flops': 0.0, 'hbm_bytes': 0.0}
    assert work['experts']['flops'] == 2 * 3 * 4096 * 2048 * 10
    assert work['experts']['hbm_bytes'] == 2 * (9 * 3 * 4096 * 2048
                                                + 10 * (2 * 4096 + 3 * 2048))
    dense = 6 * 2 * 2 * (4096 * (1024 + 320) + 1024 * 32 * 128
                         + 32 * 128 * 4096 + 4096 * 128 + 3 * 4096 * 2048) \
        + 2 * 2 * 4096 * 32768
    assert work['step']['flops'] == dense + sum(
        work[name]['flops'] for name in work_mistral4.KERNELS)
    floor = work_lm.least_seconds(work['latent_decode'], PEAKS)
    assert floor['bound'] == 'hbm'          # the latents, once


def test_a_chunk_step_by_hand():
    """A chunk of 512 at positions 48,000.. and no decode row: the history
    up-projected once, every query against the keys at or before it."""
    step = {'chunk_tokens': 512, 'chunk_first': 48000,
            'decode_positions': np.asarray([], np.int64),
            'held_choices': 512 * 6, 'experts_touched': [32] * 6}
    work = work_mistral4.step_work(CONFIG, step)
    history = 48512
    keys = 512 * 48000 + 512 * 513 / 2
    assert work['latent_prefill']['flops'] == 6 * (
        2 * history * 256 * 32 * 192 + 2 * 32 * (64 + 64 + 128) * keys)
    assert work['latent_prefill']['hbm_bytes'] == 6 * (
        2 * 320 * history + 2 * 256 * 32 * 192
        + 512 * 32 * (2 * 128 + 4 * 128))
    floor = work_lm.least_seconds(work['latent_prefill'], PEAKS)
    assert floor['bound'] == 'compute'
    total = work_mistral4.total_work(CONFIG, [step, step])
    assert total['step']['flops'] == 2 * work['step']['flops']


# --------------------------------------------------- the kernels' seconds
def test_an_instruction_is_known_by_its_scope():
    text = '''
  %latent_decode.3 = f32[16,32,256]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(lmlatent_step_0)/lm/latent_decode/jit(_decode)/latent_decode"}
  %fusion.4 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmlatent_step_256)/lm/latent_prefill/while/body/dot_general"}
  %gmm.5 = bf16[128,4096]{1,0} custom-call(%p), metadata={op_name="jit(lmlatent_step_256)/lm/experts/jit(gmm)/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmlatent_step_256)/lm/router/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmlatent_step_256)/lm/shared_expert/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmlatent_step_256)/dot_general"}
'''
    assert lmlatentkernels.scopes_of(text) == {
        'latent_decode.3': 'latent_decode', 'fusion.4': 'latent_prefill',
        'gmm.5': 'experts'}
    read = {'ops': sorted([(0.0, 2.0, 'latent_decode.3'),
                           (2.0, 5.0, 'fusion.4'), (5.0, 6.0, 'gmm.5'),
                           (6.0, 6.5, 'fusion.7'),
                           (20.0, 21.0, 'fusion.4')],
                          key=lambda e: (e[0], -e[1]))}
    runs = [(0.0, 9.0, 256)]
    assert lmlatentkernels.kernel_seconds(read, {256: text}, runs) == {
        'latent_decode': 2.0, 'latent_prefill': 3.0, 'experts': 1.0}
