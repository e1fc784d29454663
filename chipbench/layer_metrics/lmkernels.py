"""The decoder's kernels against the chip's roofline, from the device trace.

The step programs wrap each kernel in a ``jax.named_scope``
(``lm/window_attention``, ``lm/full_attention``, ``lm/decode_attention``,
``lm/experts``), but the profiler's device events carry the HLO
instruction and not its scope, so a kernel is known by what the trace does
print: the Pallas kernels' own names (``ragged_paged_attention_kernel``,
``gmm``) and their order inside a run of the step program.  The attention
calls of one program run come in layer order, so the *i*-th is layer *i*'s
and ``layer_types`` says whether it is a sliding or a full layer's; a run
whose attention output has as many rows as the engine has decode slots is a
decode-only step (``decode_attention``), any other carries a chunk
(``window_attention`` / ``full_attention``).  Every ``gmm`` call is the
expert layer's (``experts``).  A kernel's seconds are the summed durations
of its events in the traced slice.

Its work is ``work_lm.py``'s count over the steps that finished inside the
slice (the program's ``serving/lm_prefill_chunk`` and ``serving/lm_decode``
events carry the step's number; the step log says what each carried); its
share is the least time the chip's peaks allow that work over the seconds
it took.

A program without these kernels or events (the parent commit), a run with
no trace and a CPU rehearsal (no peaks) give nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, List, Optional

from chipbench import work_lm
from chipbench.reduce import trace as trace_lib

STEP_DONE = ('serving/lm_prefill_chunk', 'serving/lm_decode')
ATTENTION = re.compile(
    r'^%?ragged_paged_attention_kernel[.\d]* = \w+\[(\d+),')
EXPERTS = re.compile(r'^%?gmm[.\d]* = ')


def attention_kernel(index: int, rows: int, layer_types: List[str],
                     slots: int) -> Optional[str]:
    """Which kernel the ``index``-th attention call of a program run is,
    its output ``rows`` tokens long."""
    if index >= len(layer_types):
        return None
    if rows == slots:
        return 'decode_attention'
    return ('window_attention'
            if layer_types[index] == 'sliding_attention'
            else 'full_attention')


@functools.lru_cache(maxsize=2)
def read_file(path: str) -> dict:
    """{'attention': [(start, seconds, rows)], 'experts_seconds',
    'modules': [(start, end, name)], 'steps_done': [step numbers]} of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    attention, modules, steps = [], [], []
    experts = 0.0
    for plane in ProfileData.from_file(path).planes:
        device = trace_lib.DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith('/host:')
        if not device and not host:
            continue
        for line in plane.lines:
            ops = device and line.name == trace_lib.OPS_LINE
            runs = device and line.name == trace_lib.MODULES_LINE
            if not (ops or runs or host):
                continue
            for event in line.events:
                name = event.name
                if ops:
                    match = ATTENTION.match(name)
                    if match:
                        attention.append((event.start_ns * 1e-9,
                                          event.duration_ns * 1e-9,
                                          int(match.group(1))))
                    elif EXPERTS.match(name):
                        experts += event.duration_ns * 1e-9
                elif runs:
                    start = event.start_ns * 1e-9
                    modules.append((start, start + event.duration_ns * 1e-9,
                                    name.split('(', 1)[0]))
                elif name in STEP_DONE:
                    stats = dict(event.stats)
                    if 'step' in stats:
                        steps.append(int(stats['step']))
    return {'attention': sorted(attention), 'experts_seconds': experts,
            'modules': sorted(modules),
            'steps_done': sorted(steps)}


def kernel_seconds(read: dict, layer_types: List[str], slots: int
                   ) -> Dict[str, float]:
    """Seconds by kernel: every attention call placed in the program run
    that holds it and numbered in time order there."""
    seconds = {'experts': read['experts_seconds']}
    starts = [m[0] for m in read['modules']]
    seen: Dict[int, int] = {}
    for start, duration, rows in read['attention']:
        run = bisect.bisect_right(starts, start) - 1
        if run < 0 or start > read['modules'][run][1]:
            continue
        index = seen.get(run, 0)
        seen[run] = index + 1
        kernel = attention_kernel(index, rows, layer_types, slots)
        if kernel:
            seconds[kernel] = seconds.get(kernel, 0.0) + duration
    return seconds


_LOGGED = set()


def of_run(run: dict) -> dict:
    """{} or {'kernel_seconds', 'step_seconds', 'work', 'steps'} of a
    traced run on a chip."""
    lm = run['obs'].get('lm')
    if not lm or not run['peaks'] or not run['trace']:
        return {}
    path = trace_lib.find_xplane(lm['trace_dir'])
    if path is None:
        return {}
    read = read_file(path)
    # the first step that finished in the slice began before it
    done = set(read['steps_done'][1:])
    steps = [s for s in lm['step_log'] if s['step'] in done]
    if not steps:
        return {}
    config = lm['model_config']
    layer_types = config['layer_types'][:int(config['num_hidden_layers'])]
    by_program: Dict[str, float] = {}
    for start, end, name in read['modules']:
        by_program[name] = by_program.get(name, 0.0) + end - start
    out = {'kernel_seconds': kernel_seconds(read, layer_types,
                                            int(lm['slots'])),
           # the step programs are the only programs of the window that
           # hold an attention kernel; the row slices beside them are
           # microseconds
           'step_seconds': sum(by_program.values()),
           'work': work_lm.total_work(config, steps), 'steps': len(steps)}
    if path not in _LOGGED:
        _LOGGED.add(path)
        run['log']('lm kernels: %d steps in the slice; seconds by kernel '
                   '%s; by program %s'
                   % (len(steps), {k: round(v, 4) for k, v in
                                   out['kernel_seconds'].items()},
                      {k: round(v, 4) for k, v in by_program.items()}))
    return out


def read(run):
    traced = of_run(run)
    values = {}
    for kernel in work_lm.KERNELS:
        seconds = traced.get('kernel_seconds', {}).get(kernel, 0.0)
        if not seconds > 0:
            continue
        floor = work_lm.least_seconds(traced['work'][kernel], run['peaks'])
        run['log']('lm kernels: %s took %.4f s over %d steps; least %.4f s, '
                   'bound by %s'
                   % (kernel, seconds, traced['steps'], floor['seconds'],
                      floor['bound']))
        values['lmkernels.%s_roofline' % kernel] = \
            100.0 * floor['seconds'] / seconds
    return values
