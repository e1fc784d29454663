"""Model: the step program as a whole (models/functional.py and below)."""
from chipbench.layer_metrics import present
from chipbench.reduce.trace import top_module


def read(run):
    out = {}
    module = top_module(run['trace']) if run['trace'] else None
    if module is not None:
        name, runs, seconds = module
        run['log']('model: program %s ran %d times on each device in the '
                   'trace, %.3f ms each' % (name, runs, 1e3 * seconds / runs))
        # device time of the program that took most of it, per run
        out['model.device_ms_per_step'] = 1e3 * seconds / runs
    obs, work = run['obs'], run['work']
    if work is not None and run['peaks'] and \
            'examples_per_sec_per_chip' in obs:
        # the algorithm's operations at the traced run's own rate, over the
        # chip's peak: an end-to-end utilization, not a roofline share
        steps_per_s = (obs['examples_per_sec_per_chip']
                       / obs['examples_per_step_per_chip'])
        out['model.mfu'] = (100.0 * work['flops'] * steps_per_s
                            / run['peaks']['flops_per_s_bf16'])
    return present(out)
