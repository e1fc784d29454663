"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
The configuration is the JSON file its ``configs`` entry names; the mix is
``traffic/<traffic>.json``, which names its generator (``traffic/<name>.py``)
and its runner (``runners/<name>.py``); a per-layer metric ``<layer>.<rest>``
is read by ``layer_metrics/<layer>.<rest>.py`` if that file exists and by
``layer_metrics/<layer>.py`` otherwise. So a new cell, mix, runner or metric
is new files plus new entries, and no file that is there changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, NamedTuple

PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PACKAGE_DIR)
DEFAULT_MANIFEST = os.path.join(ROOT, 'BENCHMARK.json')


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file, as parsed
    traffic_name: str
    traffic: dict           # the traffic mix's file, as parsed
    end_to_end: List[dict]  # the manifest's metric entries this cell reports
    per_layer: List[dict]


def read_json(path: str) -> dict:
    with open(path, 'r') as f:
        return json.load(f)


def _reported_by(metrics: List[dict], cell_name: str) -> List[dict]:
    """The metrics a cell reports: those with no ``workloads`` list, and
    those whose list names the cell."""
    return [m for m in metrics
            if 'workloads' not in m or cell_name in m['workloads']]


def load_cell(workload: str, manifest_path: str = DEFAULT_MANIFEST) -> Cell:
    manifest = read_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w['name']: w for w in manifest['workloads']}
    if workload not in cells:
        raise SystemExit('chipbench: no workload %r in %s (has: %s)'
                         % (workload, manifest_path, ', '.join(sorted(cells))))
    entry = cells[workload]
    configs = {c['name']: c for c in manifest['configs']}
    config = read_json(os.path.join(root, configs[entry['config']]['file']))
    traffic = read_json(os.path.join(root, manifest['paths'][0], 'traffic',
                                     entry['traffic'] + '.json'))
    return Cell(name=workload, chips=int(entry['chips']),
                config_name=entry['config'], config=config,
                traffic_name=entry['traffic'], traffic=traffic,
                end_to_end=_reported_by(manifest['end_to_end'], workload),
                per_layer=_reported_by(manifest['per_layer'], workload))


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module; ``name`` may hold dots."""
    path = os.path.join(PACKAGE_DIR, kind, name + '.py')
    if not os.path.isfile(path):
        raise SystemExit('chipbench: %s %r needs the file %s'
                         % (kind, name, os.path.relpath(path, ROOT)))
    spec = importlib.util.spec_from_file_location(
        'chipbench.%s.%s' % (kind, name.replace('.', '_')), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_readers(per_layer: List[dict]) -> Dict[str, object]:
    """{reader file's name: module} for a cell's per-layer metrics. A
    metric with no reader file is left to the harness to report missing."""
    readers: Dict[str, object] = {}
    for metric in per_layer:
        name = metric['name']
        for candidate in (name, name.split('.', 1)[0]):
            path = os.path.join(PACKAGE_DIR, 'layer_metrics',
                                candidate + '.py')
            if os.path.isfile(path):
                if candidate not in readers:
                    readers[candidate] = load_module('layer_metrics',
                                                     candidate)
                break
    return readers
