"""The session cell (``serve-sala-repo-sessions``): it was added as new
files and appended entries only; its rehearsal prints every metric a CPU can
give; ``work_minicpm_sala.py`` counts what a hand count gives; the reader
of the kernels' seconds takes a loop's time off the loop that holds it."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import manifest, work_lm, work_minicpm_sala
from chipbench.layer_metrics import lmhybridkernels
from chipbench.tests.test_rehearsal import check_rehearsal

ROOT = manifest.ROOT
CELL = 'serve-sala-repo-sessions'
#: the manifest as PR 34 left it (4 configurations, 4 cells, 3 end-to-end
#: and 56 per-layer metrics), entry by entry with sorted keys
ACCEPTED = 'fc287a1de6180da274360d16ab4084655e0fb525296e30d514b991bb8fd449b3'
ACCEPTED_COUNTS = {'configs': 4, 'workloads': 4, 'end_to_end': 3,
                   'per_layer': 56}
ACCEPTED_CELLS = ('train-corpus', 'serve-open', 'train-corpus-dp4',
                  'serve-mellum2-ide')


@pytest.fixture(scope='module')
def spec():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_nothing_that_was_there_changed(spec):
    """What the manifest held before this cell is what it held: every name
    after the accepted cells' is taken off a ``workloads`` list before it
    is hashed, so a later cell appended there does not break this pin."""
    digest = hashlib.sha256()
    for key in ('command', 'paths', 'run_seconds'):
        digest.update(json.dumps(spec[key], sort_keys=True).encode())
    for key, count in ACCEPTED_COUNTS.items():
        for entry in spec[key][:count]:
            entry = dict(entry)
            if 'workloads' in entry:
                kept = [w for w in entry['workloads'] if w in ACCEPTED_CELLS]
                # appended, never put first or in the middle
                assert entry['workloads'][:len(kept)] == kept
                entry['workloads'] = kept
            digest.update(json.dumps(entry, sort_keys=True).encode())
    assert digest.hexdigest() == ACCEPTED


def test_the_cell_is_as_the_issue_names_it(spec):
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ('minicpm-sala-9b-l16', 'session-turns', 1)
    config, mix = cell.config, cell.traffic
    with open('/opt/skills/guides/model-configs/architectures.jsonl') as f:
        catalog = next(row for row in map(json.loads, f)
                       if row['name'] == 'MiniCPM-SALA')
    assert config['source']['url'] == catalog['source_url']
    for key, value in catalog['config'].items():
        if key != 'num_hidden_layers':
            assert config[key] == value, key
    assert config['reduced'] == ['num_hidden_layers']
    assert config['num_hidden_layers'] == 16 == \
        config['settings']['num_hidden_layers']
    assert config['first_hidden_layer'] == 9 == \
        config['settings']['first_hidden_layer']
    run = config['mixer_types'][9:25]
    assert len(config['mixer_types']) == 32
    assert (run.count('minicpm4'), run.count('lightning-attn')) == (4, 12)
    assert config['parameters'] == 5039400448
    assert config['parameters_published_depth'] == 9477110784
    assert config['sparse_config'] == work_minicpm_sala.SPARSE_CONFIG
    assert {'lightning_decay', 'lightning_gate', 'sparse_config',
            'topk_ties', 'dense_branch', 'weights'} <= set(config['assumed'])
    assert 12e9 < config['device_bytes']['peak_measured'] < 15.7e9
    arrivals = mix['arrivals']
    assert isinstance(arrivals['rate_per_s'], float)
    assert (4 * arrivals['rate_per_s']).is_integer()   # a quarter turn/s
    assert arrivals['lead_in_s'] == 6.0
    assert arrivals['sessions'] == {'count': 8, 'median': 65536,
                                    'sigma': 0.5, 'min': 32768,
                                    'max': 131072}
    assert arrivals['turns'] == {'count': 64, 'median': 1024, 'sigma': 0.5,
                                 'min': 256, 'max': 4096, 'new_tokens': 64}
    assert mix['check_turns'] == 2 and 'work' not in mix
    # each limit between its own two readings (PERF.md, section 4)
    tolerance = config['check']['tolerance']
    assert 0.250 < tolerance['share_beyond'] < 0.648
    assert 0.132 < tolerance['relative_error_cap'] < 0.358
    wanted = {m['name'] for m in cell.per_layer}
    assert {'lmhybrid.ttft_ms_p50', 'lmhybrid.prefill_chunk_ms_p50',
            'lmhybrid.decode_step_ms_p50', 'lmhybrid.tokens_per_step',
            'lmhybrid.page_pool_fill_share', 'lmhybrid.admit_wait_ms_p50',
            'lmhybrid.state_pool_fill_share',
            'lmhybrid.resident_positions_share',
            'lmhybrid.blocks_read_share', 'lmhybrid.session_wait_ms_p50',
            'lmhybrid.step_mfu', 'lmhybrid.new_kernels_time_share',
            'lmhybridkernels.linear_prefill_roofline',
            'lmhybridkernels.linear_decode_roofline',
            'lmhybridkernels.sparse_select_roofline',
            'lmhybridkernels.sparse_attention_roofline',
            'engine.queue_depth_mean', 'engine.p95_ms', 'engine.p99_ms',
            'engine.warmup_s', 'loadgen.late_ms_p99',
            'loadgen.offered_per_s', 'device.idle_share-serve',
            'device.peak_hbm_bytes-serve',
            'device.reserved_bytes-serve'} <= wanted
    assert not any(name.startswith(('lm.', 'lmkernels.')) for name in wanted)
    for metric in cell.per_layer:
        if metric['name'].startswith(('lmhybrid.', 'lmhybridkernels.')):
            assert metric['workloads'] == [CELL]
            assert metric['moves'] == 'serve_p50_ms'
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


# ------------------------------------------------------------ hand counts
CONFIG = {'num_hidden_layers': 4, 'first_hidden_layer': 9,
          'mixer_types': ['lightning-attn'] * 9 + ['minicpm4']
          + ['lightning-attn'] * 3,
          'head_dim': 128, 'num_attention_heads': 32,
          'num_key_value_heads': 2, 'hidden_size': 4096,
          'intermediate_size': 16384, 'lightning_nh': 32,
          'lightning_head_dim': 128, 'vocab_size': 73448}
PEAKS = {'flops_per_s_bf16': 197e12, 'hbm_bytes_per_s': 819e9}


def test_a_decode_step_by_hand():
    """Two rows, one within ``dense_len`` and one at 100,000, through one
    sparse layer and three lightning layers."""
    step = {'chunk_tokens': 0, 'chunk_first': 0,
            'decode_positions': np.asarray([4999, 100000])}
    work = work_minicpm_sala.step_work(CONFIG, step)
    state = 32 * 128 * 128
    assert work['linear_decode']['flops'] == 3 * 2 * 5 * state
    # each row's state in and out (float32), its q, k, v in and o out
    assert work['linear_decode']['hbm_bytes'] == \
        3 * 2 * (2 * 4 * state + 4 * 2 * 32 * 128)
    assert work['linear_prefill']['flops'] == 0
    # stage 1: the row at 100,000 sees 100001 // 16 - 1 pooled keys with
    # 32 heads; the dense row selects nothing
    pooled = 100001 // 16 - 1
    assert work['sparse_select']['flops'] == 2 * 128 * 32 * pooled
    assert work['sparse_select']['hbm_bytes'] == \
        2 * (2 * 128 * (100001 // 16) + 32 * 128)
    # stage 2: 63 whole blocks and 100000 % 64 + 1 keys of its own block;
    # the dense row every one of its 5,000 keys
    keys = 63 * 64 + 100000 % 64 + 1
    assert work['sparse_attention']['flops'] == \
        4 * 128 * 32 * (keys + 5000)
    kv, qo = 2 * 2 * 128 * 2, 2 * 32 * 128 * 2
    assert work['sparse_attention']['hbm_bytes'] == \
        kv * (keys + 5000) + 2 * qo
    dense = 2 * 2 * 4096 * (3 * (5 * 4096 + 3 * 16384)
                            + (3 * 4096 + 2 * 256 + 3 * 16384)) \
        + 2 * 2 * 4096 * 73448
    assert work['step']['flops'] == dense + sum(
        work[name]['flops'] for name in work_minicpm_sala.KERNELS)
    floor = work_lm.least_seconds(work['linear_decode'], PEAKS)
    assert floor['bound'] == 'hbm'          # the states' read and write


def test_a_chunk_step_by_hand():
    """A chunk of 512 at positions 65,536.. beside one decode row."""
    step = {'chunk_tokens': 512, 'chunk_first': 65536,
            'decode_positions': np.asarray([40000])}
    work = work_minicpm_sala.step_work(CONFIG, step)
    state = 32 * 128 * 128
    assert work['linear_prefill']['flops'] == 3 * 512 * 5 * state
    # ONE state in and out for the whole chunk
    assert work['linear_prefill']['hbm_bytes'] == \
        3 * (2 * 4 * state + 512 * 4 * 2 * 32 * 128)
    at = 65536 + np.arange(512)
    pooled = ((at + 1) // 16 - 1).sum() + (40001 // 16 - 1)
    assert work['sparse_select']['flops'] == 2 * 128 * 32 * pooled
    # the chunk's queries share one read of the sequence's pooled rows
    assert work['sparse_select']['hbm_bytes'] == \
        2 * (2 * 128 * ((65536 + 512) // 16) + 512 * 32 * 128) \
        + 2 * (2 * 128 * (40001 // 16) + 32 * 128)
    keys = (63 * 64 + at % 64 + 1).sum()
    row = 63 * 64 + 40000 % 64 + 1
    assert work['sparse_attention']['flops'] == \
        4 * 128 * 32 * (keys + row)
    # between them the chunk's queries read at most the sequence once
    kv, qo = 2 * 2 * 128 * 2, 2 * 32 * 128 * 2
    assert work['sparse_attention']['hbm_bytes'] == \
        kv * (65536 + 512) + 512 * qo + kv * row + qo
    assert work['linear_decode']['flops'] == 3 * 5 * state
    total = work_minicpm_sala.total_work(CONFIG, [step, step])
    assert total['step']['flops'] == 2 * work['step']['flops']
    floor = work_lm.least_seconds(total['sparse_attention'], PEAKS)
    assert floor['bound'] == 'compute'


# --------------------------------------------------- the kernels' seconds
def test_an_event_counts_its_own_time_less_its_childrens():
    ops = sorted([(0.0, 10.0, 'cond.1'), (1.0, 9.0, 'while.2'),
                  (1.5, 3.5, 'fusion.3'), (4.0, 8.0, 'fusion.4'),
                  (11.0, 12.0, 'fusion.5')], key=lambda e: (e[0], -e[1]))
    own = {name: seconds
           for name, _, seconds in lmhybridkernels.own_seconds(ops)}
    assert own == {'cond.1': 2.0, 'while.2': 2.0, 'fusion.3': 2.0,
                   'fusion.4': 4.0, 'fusion.5': 1.0}


def test_an_instruction_is_known_by_its_innermost_scope():
    text = '''
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(lmhybrid_step_256)/lmhybrid/sparse_prefill/cond/branch_1_fun/while/body/sparse_select/mul" stack_frame_id=3}
  ROOT %fusion.4 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmhybrid_step_256)/lmhybrid/sparse_prefill/cond/branch_1_fun/while/body/sparse_attention/dot_general"}
  %cond.1 = (f32[8]{0}) conditional(%p), metadata={op_name="jit(lmhybrid_step_256)/lmhybrid/sparse_prefill/cond"}
  %while.9 = (f32[8]{0}) while(%p), metadata={op_name="jit(lmhybrid_step_256)/lmhybrid/linear_prefill/while"}
  %fusion.7 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmhybrid_step_256)/lmhybrid/sparse_pool/scatter"}
  %fusion.8 = f32[8]{0} fusion(%p), metadata={op_name="jit(lmhybrid_step_256)/dot_general"}
'''
    assert lmhybridkernels.scopes_of(text) == {
        'fusion.3': 'sparse_select', 'fusion.4': 'sparse_attention',
        'cond.1': 'sparse_attention', 'while.9': 'linear_prefill',
        'fusion.7': 'sparse_select'}
    read = {'ops': sorted([(0.0, 6.0, 'cond.1'), (1.0, 3.0, 'fusion.3'),
                           (3.0, 6.0, 'fusion.4'), (7.0, 8.0, 'fusion.8'),
                           (20.0, 21.0, 'fusion.3')],
                          key=lambda e: (e[0], -e[1]))}
    runs = [(0.0, 9.0, 256)]        # the step program's; not take_row's
    assert lmhybridkernels.kernel_seconds(read, {256: text}, runs) == {
        'sparse_attention': 4.0, 'sparse_select': 2.0}


def test_a_run_of_a_step_program_is_known_by_its_step():
    """Runs follow each other in the steps' order; a host event that says
    step 41's tokens arrived names the last run that had ended by then; the
    step log's buckets have to agree with the runs' programs."""
    runs = [(0.0, 1.0, 0), (1.0, 2.0, 256), (2.0, 3.5, 0), (3.5, 4.5, 0)]
    buckets = {39: 256, 40: 0, 41: 256, 42: 0, 43: 0}
    done = [(1.02, 40), (2.01, 41), (3.52, 42)]
    assert lmhybridkernels.steps_of_runs(runs, done, buckets) \
        == [40, 41, 42, 43]
    # a host clock a run late still lines up by the buckets
    late = [(2.02, 40), (3.51, 41), (4.6, 42)]
    assert lmhybridkernels.steps_of_runs(runs, late + [(1.01, 40)],
                                         buckets) == [40, 41, 42, 43]
    # a log that says otherwise: nothing is read
    assert lmhybridkernels.steps_of_runs(
        runs, done, {**buckets, 41: 0}) is None
    assert lmhybridkernels.steps_of_runs(runs, [], buckets) is None


def test_kernel_seconds_are_of_the_kept_runs_alone():
    text = ('  %fusion.1 = f32[8] fusion(), metadata={op_name='
            '"jit(lmhybrid_step_0)/lmhybrid/linear_decode/mul"}\n')
    read = {'ops': [(0.1, 0.3, 'fusion.1'), (1.1, 1.2, 'fusion.1'),
                    (2.1, 2.5, 'fusion.1')]}
    runs = [(0.0, 1.0, 0), (1.0, 2.0, 0), (2.0, 3.0, 0)]
    seconds = lmhybridkernels.kernel_seconds(read, {0: text}, runs[1:])
    assert seconds == {'linear_decode': pytest.approx(0.5)}


def test_the_controls_entry_runs_on_the_cpu():
    """``chipbench/controls_minicpm_sala.py`` at the rehearsal's sizes: the
    path (the window, the timed logits, the reference proper and the three
    controls through the judge), not the limits."""
    import subprocess
    import sys
    done = subprocess.run(
        [sys.executable, '-m', 'chipbench.controls_minicpm_sala', '--seed',
         '5', '--seconds', '2', '--rehearse-on-cpu'],
        cwd=os.path.dirname(manifest.PACKAGE_DIR), capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    for name in ('reference', 'float8_weights', 'bf16_state',
                 'no_forced_blocks'):
        assert 'control %s: ' % name in done.stdout
    assert 'the reference proper is held' in done.stdout
