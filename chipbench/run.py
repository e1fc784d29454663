"""Runs one cell of ``BENCHMARK.json`` once, in this process, on this
machine's chips: set-up, warm-up, a measured window, the check, one last
line, exit.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``; with ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics (and ``device`` gains ``busy_s``/``window_s``, the line a
``breakdown``). Off a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result. ``--rehearse-on-cpu`` is the explicit
tiny-size rehearsal of the same path on the CPU; its line is labelled and is
never a result.

Nothing in this file knows a cell, a configuration, a mix or a metric by
name: each is found through ``chipbench/manifest.py``.
"""
import time

_T0 = time.perf_counter()   # before the heavy imports: they are set-up too

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import NamedTuple, Optional  # noqa: E402

from chipbench import manifest  # noqa: E402


def parse_args(argv):
    parser = argparse.ArgumentParser(prog='python3 -m chipbench.run',
                                     description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), required=True)
    parser.add_argument('--manifest', default=manifest.DEFAULT_MANIFEST,
                        help='another BENCHMARK.json (tests add cells)')
    parser.add_argument('--rehearse-on-cpu', action='store_true',
                        help='tiny-size rehearsal on the CPU; labelled, '
                             'never a result')
    return parser.parse_args(argv)


def merged(base: dict, overrides: dict) -> dict:
    """``base`` with ``overrides`` laid over it, dictionaries merged."""
    out = dict(base)
    for key, value in overrides.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


class Started(NamedTuple):
    """A process made ready for one cell: what ``main`` and the knee sweep
    (``chipbench/sweep.py``) share."""
    ctx: object
    runner: object
    readers: dict
    compiles: object
    device: dict
    used: list
    peaks: Optional[dict]
    age_at_t0: float


def start(workload: str, seed: int, trace: bool, rehearse: bool,
          manifest_path: str) -> Optional[Started]:
    """Finds the cell's files, points JAX at the persistent cache, checks
    the devices and builds the run's context and runner; None (after a line
    on standard error) where the machine is not what the cell asks for."""
    cell = manifest.load_cell(workload, manifest_path)
    runner_module = manifest.load_module('runners', cell.traffic['runner'])
    readers = manifest.layer_readers(cell.per_layer) if trace else {}

    # the program itself: an ImportError here is the bare-directory case
    from code2vec_tpu import compile_cache
    from chipbench.runners import common

    age_at_t0 = common.process_age_s() - (time.perf_counter() - _T0)
    import jax
    # every program into the persistent cache, also the many that compile in
    # under a second (JAX's default threshold keeps those out: PERF.md)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    cache_dir = compile_cache.configure()
    compiles = common.CompileCounter()

    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices)}
    wanted = 'cpu' if rehearse else 'tpu'
    print('chipbench: %s seed %d, trace %d; platform=%s kind=%r devices=%d '
          'jax=%s cache=%s%s'
          % (cell.name, seed, trace, device['platform'], device['kind'],
             device['count'], jax.__version__, cache_dir,
             ' [REHEARSAL: CPU, tiny sizes: not a result]'
             if rehearse else ''), flush=True)
    if device['platform'] != wanted or device['count'] < cell.chips:
        print('chipbench: %s needs %d %s device(s); JAX found %d of platform '
              '%r: no result' % (cell.name, cell.chips, wanted,
                                 device['count'], device['platform']),
              file=sys.stderr)
        return None
    used = devices[:cell.chips]

    config, traffic = dict(cell.config), dict(cell.traffic)
    if rehearse:
        config = merged(config, config.get('rehearsal', {}))
        traffic = merged(traffic, traffic.get('rehearsal', {}))
        peaks = None    # a CPU has none: readers that need them say nothing
    else:
        table = manifest.read_json(os.path.join(manifest.PACKAGE_DIR,
                                                'peaks.json'))
        if device['kind'] not in table:
            raise SystemExit('chipbench: no peaks for device kind %r in '
                             'chipbench/peaks.json' % device['kind'])
        peaks = table[device['kind']]
    settings = dict(config['settings'])
    if len(devices) > cell.chips:
        settings['MESH_DEVICE_INDICES'] = ','.join(
            str(i) for i in range(cell.chips))

    data_root = os.path.join(manifest.PACKAGE_DIR, '.data')
    run_dir = os.path.join(data_root, 'runs', '%s-%d-t%d'
                           % (cell.name, seed, trace))
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = common.Context(cell=cell, seed=seed, trace=trace,
                         rehearsal=rehearse, data_root=data_root,
                         run_dir=run_dir, config=config, settings=settings,
                         traffic=traffic)
    # the interpreter, the imports and the TPU runtime coming up
    ctx.spans['lifecycle.start_s'] = age_at_t0 + (time.perf_counter() - _T0)
    return Started(ctx=ctx, runner=runner_module.Runner(ctx, compiles),
                   readers=readers, compiles=compiles, device=device,
                   used=used, peaks=peaks, age_at_t0=age_at_t0)


def read_layers(started: Started, obs: dict, result: dict) -> dict:
    """A traced run's per-layer values: reduces the trace (busy and window
    seconds into ``device``, the breakdown into ``result``), counts the
    step's work where the mix names a work function, and asks the cell's
    readers."""
    from chipbench import work as work_lib
    from chipbench.reduce import trace as trace_lib
    ctx, runner, compiles = started.ctx, started.runner, started.compiles
    reduced = trace_lib.reduce_trace(ctx.trace_dir)
    if reduced:
        started.device['busy_s'] = reduced['busy_s']
        started.device['window_s'] = reduced['window_s']
        result['breakdown'] = {'device_ops': reduced['device_ops'],
                               'idle_gaps': reduced['idle_gaps']}
        ctx.log('trace: host spans %s' % reduced['host_span_counts'])
    work = None
    model = getattr(runner, 'model', None)
    if 'work' in ctx.traffic and model is not None:
        examples = obs['examples_per_step_per_chip']
        work = getattr(work_lib, ctx.traffic['work'])(
            work_lib.shapes_from(model.backend.sizes, model.config),
            examples, examples * obs['mean_contexts'], chips=ctx.cell.chips)
    run = {'cell': ctx.cell, 'obs': obs, 'spans': ctx.spans,
           'trace': reduced, 'work': work, 'peaks': started.peaks,
           'compiles': {'total': compiles.value,
                        'cache_hits': compiles.cache_hits,
                        'cache_misses': compiles.cache_misses},
           'device': started.device, 'log': ctx.log}
    values = {}
    for module in started.readers.values():
        values.update(module.read(run))
    return values


def main(argv=None) -> int:
    args = parse_args(argv)
    started = start(args.workload, args.seed, bool(args.trace),
                    args.rehearse_on_cpu, args.manifest)
    if started is None:
        return 3
    from chipbench.runners import common
    ctx, runner, compiles, device = (started.ctx, started.runner,
                                     started.compiles, started.device)
    cell = ctx.cell

    runner.setup()
    try:
        runner.warm()
        obs = runner.measure(args.seconds)
        setup_s = started.age_at_t0 + (obs['window_start'] - _T0)
        memory = common.device_memory(started.used)
        obs['memory_at_window_end'] = memory
        with ctx.span('check.after_s'):
            check = runner.check()
    finally:
        runner.close()

    faults = list(check['faults'])
    if obs['compiles_in_window']:
        faults.append('%d program(s) compiled or loaded inside the measured '
                      'window' % obs['compiles_in_window'])
    for fault in faults:
        ctx.log('NOT CORRECT: %s' % fault)
    ctx.log('spans %s' % {k: round(v, 3) for k, v in ctx.spans.items()})
    ctx.log('programs built or loaded %d, persistent cache hits %d, misses '
            '%d; set-up %.3f s' % (compiles.value, compiles.cache_hits,
                                   compiles.cache_misses, setup_s))

    # set-up and window; what the check against the reference holds beside
    # them belongs to the yardstick, and goes on an earlier line
    device['memory_peak_bytes'] = memory['peak_bytes']
    ctx.log('memory: %s at the window\'s end; after the check %s'
            % (memory, started.used[0].memory_stats()))
    result = {'correct': not faults, 'attempted': int(obs['attempted']),
              'failed': int(obs['failed'])}
    if args.rehearse_on_cpu:
        result['rehearsal'] = True
    if not args.trace:
        values = dict(obs['end_to_end'], setup_s=setup_s)
        wanted_metrics = cell.end_to_end
    else:
        values = read_layers(started, obs, result)
        wanted_metrics = cell.per_layer
    metrics = {}
    for metric in wanted_metrics:
        # `<name>-<qualifier>` tells apart copies of one per-layer metric
        # that move different end-to-end metrics; readers give `<name>`
        name = metric['name']
        value = values.get(name, values.get(name.rsplit('-', 1)[0]))
        if value is None:
            ctx.log('no value for %s' % name)
        else:
            metrics[name] = {'value': float(value), 'unit': metric['unit']}
    if not args.trace and len(metrics) != len(wanted_metrics):
        raise SystemExit('chipbench: an end-to-end metric has no value')
    result['metrics'] = metrics
    result['device'] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
