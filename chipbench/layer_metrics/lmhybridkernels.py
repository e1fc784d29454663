"""The linear-attention and block-sparse kernels against the chip's
roofline, from the device trace.

The step programs (``models/hybrid_decoder.py``) wrap each kernel in a
``jax.named_scope``, but the profiler's device events carry the HLO
instruction and not its scope.  The compiled program's text does name every
instruction's scope (``metadata={op_name="jit(..)/lmhybrid/<scope>/.."}``),
so the runner hands over the text of each step program
(``obs['lmhybrid']['programs']``) and an event is known by its program (the
``XLA Modules`` run that holds it, ``jit_lmhybrid_step_<chunk>``) and its
instruction's name.  A loop or a conditional is an event that holds its
body's events: every event counts its own time less its children's, under
the innermost scope its instruction names.

==================  ====================================================
kernel              scopes
==================  ====================================================
linear_prefill      ``linear_prefill``: the chunked scan of a prompt chunk
linear_decode       ``linear_decode``: a state update a decode row
sparse_select       ``sparse_select`` (stage 1 and the exact choice) and
                    ``sparse_pool`` (keys, values and stride rows written)
sparse_attention    ``sparse_attention`` (stage 2 over the chosen blocks),
                    ``dense_attention`` (a query within ``dense_len``) and
                    what ``sparse_prefill`` / ``sparse_decode`` hold beside
==================  ====================================================

Work and seconds are of the same steps.  A step is one run of a step
program (an ``XLA Modules`` event), and the runs follow each other in the
steps' order; the program's ``serving/lm_prefill_chunk`` and
``serving/lm_decode`` events carry a step's number and end when its tokens
reached the host, so each says which run was the last to have ended by
then, and the step log's bucket of every step so numbered has to be its
run's program (``steps_of_runs``).  The steps kept are those that finished
inside the slice but for the first (it began before the slice); the work is
``work_minicpm_sala.py``'s count over them (the step log says what each
carried), the seconds are their runs' and the kernels' own inside those
runs; a kernel's share is the least time the chip's peaks allow its work
over the seconds it took.

A program without these scopes or texts (the parent commit), a run with no
trace and a CPU rehearsal (no peaks) give nothing.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
from typing import Dict, List, Optional

from chipbench import work_lm, work_minicpm_sala
from chipbench.reduce import trace as trace_lib

STEP_DONE = ('serving/lm_prefill_chunk', 'serving/lm_decode')
PROGRAM = re.compile(r'^jit_lmhybrid_step_(\d+)')
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')
SCOPES = {'linear_prefill': 'linear_prefill',
          'linear_decode': 'linear_decode',
          'sparse_select': 'sparse_select', 'sparse_pool': 'sparse_select',
          'sparse_attention': 'sparse_attention',
          'dense_attention': 'sparse_attention',
          'sparse_prefill': 'sparse_attention',
          'sparse_decode': 'sparse_attention'}


def kernel_of(op_name: str) -> Optional[str]:
    """The kernel an instruction's ``op_name`` path belongs to: by the
    innermost of its components that is a scope."""
    for part in reversed(op_name.split('/')):
        if part in SCOPES:
            return SCOPES[part]
    return None


@functools.lru_cache(maxsize=8)
def scopes_of(text: str) -> Dict[str, str]:
    """{instruction's name: kernel} of one compiled program's text."""
    found = {}
    for line in text.splitlines():
        match = INSTRUCTION.match(line)
        if match:
            kernel = kernel_of(match.group(2))
            if kernel:
                found[match.group(1)] = kernel
    return found


@functools.lru_cache(maxsize=2)
def read_file(path: str) -> dict:
    """{'ops': [(start, end, instruction's name)], 'modules': [(start, end,
    name)], 'steps_done': [(when its tokens reached the host, step
    number)]} of one ``.xplane.pb``, device 0."""
    from jax.profiler import ProfileData
    ops, modules, steps = [], [], []
    for plane in ProfileData.from_file(path).planes:
        device = trace_lib.DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith('/host:')
        if not (device and device.group(1) == '0') and not host:
            continue
        for line in plane.lines:
            for event in line.events:
                start = event.start_ns * 1e-9
                end = start + event.duration_ns * 1e-9
                if device and line.name == trace_lib.OPS_LINE:
                    ops.append((start, end, trace_lib.op_name(event.name)))
                elif device and line.name == trace_lib.MODULES_LINE:
                    modules.append((start, end,
                                    event.name.split('(', 1)[0]))
                elif host and event.name in STEP_DONE:
                    stats = dict(event.stats)
                    if 'step' in stats:
                        steps.append((end, int(stats['step'])))
    return {'ops': sorted(ops, key=lambda e: (e[0], -e[1])),
            'modules': sorted(modules), 'steps_done': sorted(steps)}


def own_seconds(ops: List[tuple]) -> List[tuple]:
    """(instruction's name, start, seconds of its own): every event's
    duration less that of the events it holds.  ``ops`` sorted by start,
    the longer first."""
    out, open_events = [], []       # open: [end, index into out]
    for start, end, name in ops:
        while open_events and open_events[-1][0] <= start:
            open_events.pop()
        if open_events:
            holder = open_events[-1][1]
            out[holder][2] -= end - start
        out.append([name, start, end - start])
        open_events.append((end, len(out) - 1))
    return [(name, start, max(seconds, 0.0)) for name, start, seconds in out]


def steps_of_runs(runs: List[tuple], done: List[tuple],
                  buckets: Dict[int, int]) -> Optional[List[int]]:
    """The step number of each of ``runs`` [(start, end, chunk bucket of
    its program)], in time order: consecutive numbers, anchored where the
    ``done`` events [(end on the host, step)] place them (each names the
    last run that had ended by then; they vote) and held to the step log's
    ``buckets`` {step: bucket}.  None where no anchor agrees with the log."""
    ends = [end for _, end, _ in runs]
    votes: collections.Counter = collections.Counter()
    for at, step in done:
        last = bisect.bisect_right(ends, at) - 1
        if last >= 0:
            votes[step - last] += 1
    for first, _ in votes.most_common():
        steps = [first + i for i in range(len(runs))]
        if all(buckets.get(step, chunk) == chunk
               for step, (_, _, chunk) in zip(steps, runs)):
            return steps
    return None


def kernel_seconds(read: dict, programs: Dict[int, str],
                   runs: List[tuple]) -> Dict[str, float]:
    """Seconds by kernel inside ``runs`` [(start, end, chunk bucket)] of
    the step programs."""
    seconds: Dict[str, float] = {}
    starts = [start for start, _, _ in runs]
    for name, start, own in own_seconds(read['ops']):
        run = bisect.bisect_right(starts, start) - 1
        if run < 0 or start > runs[run][1] or runs[run][2] not in programs:
            continue
        kernel = scopes_of(programs[runs[run][2]]).get(name)
        if kernel:
            seconds[kernel] = seconds.get(kernel, 0.0) + own
    return seconds


_LOGGED = set()


def of_run(run: dict) -> dict:
    """{} or {'kernel_seconds', 'step_seconds', 'work', 'steps'} of a
    traced run on a chip: all four of the same steps."""
    material = run['obs'].get('lmhybrid')
    if not material or not run['peaks'] or not run['trace']:
        return {}
    path = trace_lib.find_xplane(material['trace_dir'])
    if path is None:
        return {}
    read = read_file(path)
    runs = [(start, end, int(PROGRAM.match(name).group(1)))
            for start, end, name in read['modules'] if PROGRAM.match(name)]
    log = {s['step']: s for s in material['step_log']}
    numbered = steps_of_runs(runs, read['steps_done'],
                             {step: s['bucket'] for step, s in log.items()})
    if numbered is None:
        run['log']('lmhybrid kernels: the trace\'s %d runs of the step '
                   'programs do not line up with the step log: nothing read'
                   % len(runs))
        return {}
    # the first step that finished in the slice began before it
    done = {step for _, step in read['steps_done'][1:]}
    kept = [(step, one) for step, one in zip(numbered, runs)
            if step in done and step in log]
    if not kept:
        return {}
    by_program: Dict[int, float] = {}
    for _, (start, end, chunk) in kept:
        by_program[chunk] = by_program.get(chunk, 0.0) + end - start
    programs = {int(chunk): text
                for chunk, text in material.get('programs', {}).items()}
    out = {'kernel_seconds': kernel_seconds(read, programs,
                                            [one for _, one in kept]),
           'step_seconds': sum(by_program.values()),
           'work': work_minicpm_sala.total_work(
               material['model_config'], [log[step] for step, _ in kept]),
           'steps': len(kept)}
    if path not in _LOGGED:
        _LOGGED.add(path)
        run['log']('lmhybrid kernels: %d of the trace\'s %d runs kept '
                   '(steps %d..%d); seconds by kernel %s; by program %s'
                   % (len(kept), len(runs), kept[0][0], kept[-1][0],
                      {k: round(v, 4) for k, v in
                       out['kernel_seconds'].items()},
                      {k: round(v, 4) for k, v in by_program.items()}))
    return out


def read(run):
    traced = of_run(run)
    values = {}
    for kernel in work_minicpm_sala.KERNELS:
        seconds = traced.get('kernel_seconds', {}).get(kernel, 0.0)
        if not seconds > 0:
            continue
        floor = work_lm.least_seconds(traced['work'][kernel], run['peaks'])
        run['log']('lmhybrid kernels: %s took %.4f s over %d steps; least '
                   '%.4f s, bound by %s'
                   % (kernel, seconds, traced['steps'], floor['seconds'],
                      floor['bound']))
        values['lmhybridkernels.%s_roofline' % kernel] = \
            100.0 * floor['seconds'] / seconds
    return values
