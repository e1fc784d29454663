"""Every runner end to end at a tiny size on the CPU, through the command
the manifest names, and a fifth cell added as new files only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import manifest

ROOT = manifest.ROOT
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


@pytest.fixture(scope='module')
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp('jax_cache'))


def run_cell(workload, trace, cache_dir, cwd=ROOT, devices=1, extra=(),
             seconds='2'):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               PYTHONPATH=os.pathsep.join([cwd, ROOT]))
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=%d' % devices
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        command = json.load(f)['command']
    command = [sys.executable if command[0] == 'python3' else command[0]] \
        + command[1:]
    return subprocess.run(
        command + ['--workload', workload, '--seed', '5', '--seconds',
                   seconds, '--trace', str(trace)] + list(extra),
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


#: a later PR's schedule generator, as the new file it would add: the
#: general one's requests at instants that come in bursts
BURSTS_PY = '''"""Arrivals in bursts: `factor` x the off-phase rate for
`on_s` of every `period_s`, the mean rate unchanged."""
import numpy as np

from chipbench.traffic import arrivals


def generate(params, seed, seconds, n_lines):
    rng = np.random.default_rng([int(seed), 0xB065])
    n = int(round(params['rate_per_s'] * seconds))
    burst = params['burst']
    period, on, factor = burst['period_s'], burst['on_s'], burst['factor']
    # seconds of off-phase traffic that each instant of a period is worth
    grid = np.linspace(0.0, seconds, 20001)
    weight = np.where(grid % period < on, factor, 1.0)
    cumulative = np.concatenate([[0.0], np.cumsum(weight[:-1])])
    due = np.interp(np.sort(rng.random(n)) * cumulative[-1], cumulative, grid)
    rows, tier, first_line, names = arrivals.requests(rng, params, n, n_lines)
    return arrivals.Schedule(due_s=due, rows=rows, tier=tier,
                             first_line=first_line, tiers=names)
'''


def snapshot(package):
    out = {}
    for directory, _, files in os.walk(package):
        if '.data' in directory or '__pycache__' in directory:
            continue
        for name in files:
            path = os.path.join(directory, name)
            with open(path, 'rb') as f:
                out[path] = f.read()
    return out


@pytest.fixture(scope='module')
def extended(tmp_path_factory):
    """A copy of the benchmark to which a later PR's additions are made as
    new files and new entries only: a cell (serve-burst) with a traffic
    mix, a schedule generator and a per-layer metric of its own."""
    root = tmp_path_factory.mktemp('later_pr')
    package = root / 'chipbench'
    shutil.copytree(manifest.PACKAGE_DIR, package,
                    ignore=shutil.ignore_patterns('.data', '__pycache__'))
    before = snapshot(package)

    with open(package / 'traffic' / 'open-poisson.json') as f:
        mix = json.load(f)
    mix['name'] = 'open-burst'
    mix['generator'] = 'bursts'
    (package / 'traffic' / 'bursts.py').write_text(BURSTS_PY)
    mix['arrivals']['burst'] = {'factor': 4.0, 'on_s': 0.5, 'period_s': 2.5}
    mix['rehearsal']['arrivals']['burst'] = {'factor': 3.0, 'on_s': 0.2,
                                             'period_s': 1.0}
    with open(package / 'traffic' / 'open-burst.json', 'w') as f:
        json.dump(mix, f)
    (package / 'layer_metrics' / 'engine.shed_share.py').write_text(
        'def read(run):\n'
        '    serve = run["obs"]["serve"]\n'
        '    return {"engine.shed_share":\n'
        '            100.0 * serve["shed"] / serve["requests"]}\n')
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['workloads'].append({
        'name': 'serve-burst', 'config': 'java14m-release',
        'traffic': 'open-burst', 'chips': 1, 'why': 'bursts'})
    for metric in spec['end_to_end'] + spec['per_layer']:
        if 'serve-open' in metric.get('workloads', []):
            metric['workloads'].append('serve-burst')
    spec['per_layer'].append({
        'name': 'engine.shed_share', 'unit': '%', 'better': 'lower',
        'source': 'program_counter', 'layer': 'engine',
        'moves': 'serve_p50_ms', 'workloads': ['serve-burst']})
    with open(root / 'BENCHMARK.json', 'w') as f:
        json.dump(spec, f)
    return {'root': str(root), 'package': package, 'before': before,
            'manifest': str(root / 'BENCHMARK.json')}


def check_rehearsal(cell, cache_dir, devices=1, cwd=ROOT, extra=()):
    extra = ['--rehearse-on-cpu'] + list(extra)
    plain = last_line(run_cell(cell.name, 0, cache_dir, devices=devices,
                               cwd=cwd, extra=extra))
    assert set(plain) == RESULT_KEYS | {'rehearsal'}
    assert plain['rehearsal'] is True and plain['correct'] is True
    assert plain['attempted'] > 0 and plain['failed'] == 0
    assert set(plain['metrics']) == {m['name'] for m in cell.end_to_end}
    for name, metric in plain['metrics'].items():
        assert metric['value'] > 0 and metric['unit']
    assert plain['device']['platform'] == 'cpu'
    assert plain['device']['count'] == devices
    traced = last_line(run_cell(cell.name, 1, cache_dir, devices=devices,
                                cwd=cwd, extra=extra))
    wanted = {m['name'] for m in cell.per_layer}
    assert set(traced['metrics']) <= wanted
    # no chip: what only a device trace or the chip's peaks can give is
    # absent, never a number from the CPU under a device metric's name
    device_only = {m['name'] for m in cell.per_layer
                   if m['source'] == 'device_trace'}
    assert not device_only & set(traced['metrics'])
    assert 'busy_s' not in traced['device']
    assert {n for n in wanted if n.startswith('lifecycle.')} \
        <= set(traced['metrics'])
    return plain, traced


def shipped_cells():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return [(w['name'], w['chips']) for w in json.load(f)['workloads']]


@pytest.mark.parametrize('workload,devices', shipped_cells())
def test_rehearsal_prints_the_contract_line(workload, devices, cache_dir):
    check_rehearsal(manifest.load_cell(workload), cache_dir, devices=devices)


def test_a_fifth_cell_is_new_files_and_entries_only(extended, cache_dir):
    """A later PR adds a cell with a traffic mix, a schedule generator and
    a per-layer metric of its own: new files under chipbench/ and new
    entries in BENCHMARK.json. run.py and every file that was there stay
    byte for byte."""
    cell = manifest.load_cell('serve-burst', extended['manifest'])
    plain, traced = check_rehearsal(
        cell, cache_dir, cwd=extended['root'],
        extra=['--manifest', extended['manifest']])
    assert 'serve_p50_ms' in plain['metrics']
    assert traced['metrics']['engine.shed_share']['value'] == 0.0
    assert 'engine.rows_per_batch' in traced['metrics']
    after = snapshot(extended['package'])
    assert {p: after[p] for p in extended['before']} == extended['before']
    assert len(after) == len(extended['before']) + 3


def test_a_window_that_stalls_is_not_correct(tmp_path, cache_dir):
    """The median over log windows cannot see a stall; the time the whole
    window took beyond its steps at the median pace can. An allowance under
    any such time makes every run one that stalled."""
    package = tmp_path / 'chipbench'
    shutil.copytree(manifest.PACKAGE_DIR, package,
                    ignore=shutil.ignore_patterns('.data', '__pycache__'))
    mix = manifest.read_json(package / 'traffic' / 'corpus-epochs.json')
    mix['epoch_turn_allowance_s'] = -1.0
    with open(package / 'traffic' / 'corpus-strict.json', 'w') as f:
        json.dump(mix, f)
    spec = manifest.read_json(os.path.join(ROOT, 'BENCHMARK.json'))
    spec['workloads'].append({
        'name': 'train-strict', 'config': 'java14m',
        'traffic': 'corpus-strict', 'chips': 1, 'why': 'no stall allowed'})
    for metric in spec['end_to_end']:
        if 'train-corpus' in metric.get('workloads', []):
            metric['workloads'].append('train-strict')
    with open(tmp_path / 'BENCHMARK.json', 'w') as f:
        json.dump(spec, f)
    proc = run_cell('train-strict', 0, cache_dir, cwd=str(tmp_path), extra=[
        '--rehearse-on-cpu', '--manifest', str(tmp_path / 'BENCHMARK.json')])
    assert last_line(proc)['correct'] is False
    assert 'NOT CORRECT: the window took' in proc.stdout


def test_off_a_tpu_there_is_no_result(cache_dir):
    proc = run_cell('train-corpus', 0, cache_dir)
    assert proc.returncode != 0
    assert 'no result' in proc.stderr
    assert not any(line.startswith('{')
                   for line in proc.stdout.splitlines())


def test_a_directory_with_only_the_benchmark_has_no_result(tmp_path):
    shutil.copytree(manifest.PACKAGE_DIR, tmp_path / 'chipbench',
                    ignore=shutil.ignore_patterns('.data', '__pycache__'))
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    proc = subprocess.run(
        [sys.executable, '-m', 'chipbench.run', '--workload', 'train-corpus',
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith('{')
                   for line in proc.stdout.splitlines())
