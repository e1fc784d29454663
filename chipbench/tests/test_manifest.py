"""BENCHMARK.json against the rules a benchmark's files are held to, so
that a broken manifest fails here and not on the chip."""
import json
import os
import re

import pytest

from chipbench import manifest

ROOT = manifest.ROOT
NAME = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$')
LAYER = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


@pytest.fixture(scope='module')
def spec():
    path = os.path.join(ROOT, 'BENCHMARK.json')
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level(spec):
    assert set(spec) == {'command', 'paths', 'run_seconds', 'configs',
                         'workloads', 'end_to_end', 'per_layer'}
    assert spec['paths'] == ['chipbench']
    assert 1 <= spec['run_seconds'] <= 51
    assert len(spec['command']) <= 32
    for part in spec['command']:
        assert not part.startswith('/') and '..' not in part


def test_names_are_plain_and_used_once(spec):
    names = [e['name'] for key in ('configs', 'workloads', 'end_to_end',
                                   'per_layer') for e in spec[key]]
    for name in names:
        assert NAME.match(name), name
    assert len(set(names)) == len(names)
    for key in ('configs', 'workloads'):
        for entry in spec[key]:
            assert len(entry['why']) <= 200, entry['name']


def test_configurations(spec):
    used = {w['config'] for w in spec['workloads']}
    files = [c['file'] for c in spec['configs']]
    assert len(set(files)) == len(files)
    for config in spec['configs']:
        assert config['name'] in used
        assert config['file'].startswith('chipbench/')
        assert config['source'].startswith('https://')
        parsed = manifest.read_json(os.path.join(ROOT, config['file']))
        assert parsed['name'] == config['name']
        assert parsed['reduced'] == config['reduced']
        for key in config['reduced']:
            assert key in parsed['settings']
            assert not re.search(r'(_dim|_rank|_SIZE|SIZE)$', key) or \
                key.endswith('BATCH_SIZE'), '%s names a width' % key


def test_cells(spec):
    cells = spec['workloads']
    assert 2 <= len(cells) <= 24
    pairs = [(w['config'], w['traffic']) for w in cells]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in cells if w['chips'] == 4]
    assert all(w['chips'] in (1, 4) for w in cells)
    assert len(four) <= max(1, len(cells) // 4)
    for cell in cells:
        loaded = manifest.load_cell(cell['name'])
        assert loaded.config['chips'] == cell['chips']
        runner = loaded.traffic['runner']
        assert os.path.isfile(os.path.join(manifest.PACKAGE_DIR, 'runners',
                                           runner + '.py'))


def test_metrics(spec):
    end_to_end = {m['name']: m for m in spec['end_to_end']}
    assert 'setup_s' in end_to_end and end_to_end['setup_s']['bound'] == 0.1
    assert 'workloads' not in end_to_end['setup_s']
    for metric in spec['end_to_end']:
        assert 0.01 <= metric['bound'] <= 0.1
        assert metric['source'] in ('host_clock', 'device_trace')
        assert metric['better'] in ('lower', 'higher')
    cells = {w['name'] for w in spec['workloads']}
    for metric in spec['per_layer']:
        assert metric['source'] in SOURCES
        assert LAYER.match(metric['layer']), metric['layer']
        assert 'bound' not in metric
        assert metric['moves'] in end_to_end
        assert set(metric.get('workloads', cells)) <= cells
    for cell in cells:
        loaded = manifest.load_cell(cell)
        reported = {m['name'] for m in loaded.end_to_end}
        assert 'setup_s' in reported and len(reported) >= 2
        assert loaded.per_layer
        for metric in loaded.per_layer:
            # a per-layer metric is reported only where what it moves is
            assert metric['moves'] in reported, (cell, metric['name'])
        readers = manifest.layer_readers(loaded.per_layer)
        for metric in loaded.per_layer:
            name = metric['name']
            assert name in readers or name.split('.', 1)[0] in readers, name


def test_every_file_under_paths_has_a_plain_name():
    plain = re.compile(r'^[A-Za-z0-9_./-]+$')
    for directory, subdirs, files in os.walk(manifest.PACKAGE_DIR):
        subdirs[:] = [d for d in subdirs
                      if d not in ('.data', '__pycache__')]
        for name in files:
            path = os.path.relpath(os.path.join(directory, name), ROOT)
            assert plain.match(path) and len(path) <= 200, path
