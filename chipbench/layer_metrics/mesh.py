"""Mesh: the collectives XLA's partitioner puts in (parallel/mesh.py)."""
from chipbench.reduce.trace import top_module


def read(run):
    trace = run['trace']
    module = top_module(trace) if trace else None
    if module is None:
        return {}
    devices = list(trace['devices'].values())
    collective = sum(d['collective_s'] for d in devices) / len(devices)
    exposed = sum(d['collective_exposed_s'] for d in devices) / len(devices)
    if collective <= 0:
        return {}
    return {
        'mesh.collective_ms_per_step': 1e3 * collective / module[1],
        # the part of the collectives during which nothing else ran
        'mesh.collective_exposed_share': 100.0 * exposed / collective,
    }
