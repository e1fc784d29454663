"""The language-model cell (``serve-mellum2-ide``): it was added as new
files and appended entries only; its rehearsal prints every metric a CPU can
give; ``work_lm.py`` counts what a hand count gives."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import manifest, work_lm
from chipbench.tests.test_rehearsal import check_rehearsal

ROOT = manifest.ROOT
CELL = 'serve-mellum2-ide'
#: the manifest as PR 31 left it (3 configurations, 3 cells, 3 end-to-end
#: and 43 per-layer metrics), entry by entry with sorted keys
ACCEPTED = '41699cb08b66ad2a139ead68d7c3ab191d091a5e993c59f97fd4ac00d90bbc3a'
ACCEPTED_COUNTS = {'configs': 3, 'workloads': 3, 'end_to_end': 3,
                   'per_layer': 43}


@pytest.fixture(scope='module')
def spec():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_nothing_that_was_there_changed(spec):
    """What the manifest held before this cell, with the cell's name taken
    off the ``workloads`` lists it was appended to, is what it held."""
    digest = hashlib.sha256()
    for key in ('command', 'paths', 'run_seconds'):
        digest.update(json.dumps(spec[key], sort_keys=True).encode())
    for key, count in ACCEPTED_COUNTS.items():
        for entry in spec[key][:count]:
            entry = dict(entry)
            if entry.get('workloads', [None])[-1] == CELL:
                entry['workloads'] = entry['workloads'][:-1]
            assert CELL not in entry.get('workloads', [])  # appended last
            digest.update(json.dumps(entry, sort_keys=True).encode())
    assert digest.hexdigest() == ACCEPTED


def test_the_cell_is_as_the_issue_names_it(spec):
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ('mellum2-12b-a2.5b', 'ide-replay', 1)
    config, mix = cell.config, cell.traffic
    assert config['reduced'] == ['num_hidden_layers']
    assert config['num_hidden_layers'] == 12 == \
        config['settings']['num_hidden_layers']
    published = {'hidden_size': 2304, 'head_dim': 128,
                 'num_attention_heads': 32, 'num_key_value_heads': 4,
                 'num_experts': 64, 'num_experts_per_tok': 8,
                 'moe_intermediate_size': 896, 'intermediate_size': 7168,
                 'sliding_window': 1024, 'vocab_size': 98304,
                 'max_position_embeddings': 131072, 'rms_norm_eps': 1e-06}
    assert {k: config[k] for k in published} == published
    assert config['layer_types'][:4] == ['sliding_attention'] * 3 \
        + ['full_attention'] and len(config['layer_types']) == 28
    assert config['rope_parameters']['full_attention']['factor'] == 16
    assert config['parameters'] == 12 * 417747456 + 452984832 + 2304
    arrivals = mix['arrivals']
    assert isinstance(arrivals['rate_per_s'], float)
    assert arrivals['lead_in_s'] == 6.0 and arrivals['chat_every'] == 4
    assert arrivals['classes']['complete'] == {
        'count': 48, 'median': 3072, 'sigma': 0.5, 'min': 1024,
        'max': 8192, 'new_tokens': 48}
    assert arrivals['classes']['chat'] == {
        'count': 16, 'median': 8192, 'sigma': 0.5, 'min': 4096,
        'max': 24576, 'new_tokens': 192}
    assert mix['drain_s'] == 15.0 and 'work' not in mix
    wanted = {m['name'] for m in cell.per_layer}
    assert {'lm.step_mfu', 'lmkernels.experts_roofline',
            'lmkernels.window_attention_roofline',
            'lmkernels.full_attention_roofline',
            'lmkernels.decode_attention_roofline',
            'engine.queue_depth_mean', 'engine.p95_ms', 'engine.p99_ms',
            'engine.warmup_s', 'loadgen.late_ms_p99',
            'device.idle_share-serve'} <= wanted
    for metric in cell.per_layer:
        if metric['name'].startswith(('lm.', 'lmkernels.')):
            assert metric['workloads'] == [CELL]
            assert metric['moves'] == 'serve_p50_ms'


def test_rehearsal_gives_every_metric_a_cpu_can(tmp_path):
    cell = manifest.load_cell(CELL)
    plain, traced = check_rehearsal(cell, str(tmp_path / 'jax_cache'))
    assert set(plain['metrics']) == {'serve_p50_ms', 'setup_s'}
    device_only = {m['name'] for m in cell.per_layer
                   if m['source'] == 'device_trace'}
    assert set(traced['metrics']) == \
        {m['name'] for m in cell.per_layer} - device_only


# ------------------------------------------------------------ hand counts
CONFIG = {'num_hidden_layers': 4,
          'layer_types': ['sliding_attention'] * 3 + ['full_attention'],
          'head_dim': 128, 'num_attention_heads': 32,
          'num_key_value_heads': 4, 'hidden_size': 2304,
          'moe_intermediate_size': 896, 'num_experts': 64,
          'num_experts_per_tok': 8, 'sliding_window': 1024,
          'vocab_size': 98304}


def test_a_decode_step_by_hand():
    step = {'chunk_tokens': 0, 'chunk_first': 0,
            'decode_positions': np.asarray([99, 4999]),
            'experts_touched': np.asarray([14, 15, 16, 13])}
    work = work_lm.step_work(CONFIG, step)
    # keys seen: 100 and 5000 in the full layer; 100 and 1024 in each of
    # the three sliding ones; 4 x 128 x 32 operations a key
    per_key = 4 * 128 * 32
    assert work['decode_attention']['flops'] == \
        per_key * ((100 + 5000) + 3 * (100 + 1024))
    kv = 2 * 4 * 128 * 2        # K and V of one position, bfloat16
    qo = 2 * 32 * 128 * 2       # one query in, one output out
    assert work['decode_attention']['hbm_bytes'] == \
        kv * ((100 + 5000) + 3 * (100 + 1024)) + 4 * 2 * qo
    assert work['window_attention']['flops'] == 0
    # experts: 2 tokens x 8 choices x 3 products of 2304 x 896, 4 layers
    assert work['experts']['flops'] == 4 * 2 * 3 * 2304 * 896 * 16
    weights = 3 * 2304 * 896 * 2
    rows = 16 * (2 * 2304 + 3 * 896) * 2
    assert work['experts']['hbm_bytes'] == \
        (14 + 15 + 16 + 13) * weights + 4 * rows
    dense = 4 * 2 * 2 * 2304 * (2 * 32 * 128 + 2 * 4 * 128 + 64) \
        + 2 * 2 * 2304 * 98304
    assert work['step']['flops'] == dense + work['experts']['flops'] \
        + work['decode_attention']['flops']


def test_a_chunk_step_by_hand():
    """A chunk of 512 at positions 2048.. beside one decode row: a sliding
    layer's queries see 1,024 keys each and read the 1,023 before the
    chunk and the chunk; the full layer's see all before them."""
    step = {'chunk_tokens': 512, 'chunk_first': 2048,
            'decode_positions': np.asarray([10]),
            'experts_touched': np.asarray([64, 64, 64, 64])}
    work = work_lm.step_work(CONFIG, step)
    per_key = 4 * 128 * 32
    full_keys = sum(range(2049, 2049 + 512)) + 11
    assert work['full_attention']['flops'] == per_key * full_keys
    assert work['window_attention']['flops'] == \
        3 * per_key * (512 * 1024 + 11)
    kv, qo = 2 * 4 * 128 * 2, 2 * 32 * 128 * 2
    assert work['window_attention']['hbm_bytes'] == \
        3 * (kv * (1023 + 512 + 11) + 513 * qo)
    assert work['full_attention']['hbm_bytes'] == \
        kv * (2048 + 512 + 11) + 513 * qo
    assert work['decode_attention']['flops'] == 0
    total = work_lm.total_work(CONFIG, [step, step])
    assert total['experts']['flops'] == 2 * work['experts']['flops']
    floor = work_lm.least_seconds(
        total['experts'], {'flops_per_s_bf16': 197e12,
                           'hbm_bytes_per_s': 819e9})
    assert floor['bound'] == 'hbm'      # 513 tokens: the weights' read
    assert floor['seconds'] == pytest.approx(
        total['experts']['hbm_bytes'] / 819e9)
