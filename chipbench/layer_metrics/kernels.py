"""Kernels: the step program against the chip's roofline.

Per-kernel shares need names inside the jitted code (``jax.named_scope``),
which the program does not have yet (PERF.md, Open questions); until then
the one share is that of the whole step program.
"""
from chipbench.reduce.trace import top_module
from chipbench.work import least_seconds


def read(run):
    module = top_module(run['trace']) if run['trace'] else None
    if module is None or run['work'] is None or not run['peaks']:
        return {}
    _, runs, seconds = module
    floor = least_seconds(run['work'], run['peaks'])
    run['log']('kernels: least time %.3f ms per step, bound by %s (%s)'
               % (1e3 * floor['seconds'], floor['bound'],
                  ', '.join('%s %.3f ms' % (k, 1e3 * v)
                            for k, v in floor['bounds'].items())))
    return {'kernels.step_roofline':
            100.0 * floor['seconds'] / (seconds / runs)}
