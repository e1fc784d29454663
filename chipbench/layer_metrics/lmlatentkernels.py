"""The latent-attention and expert kernels against the chip's roofline, from
the device trace.

The step programs (``models/latent_decoder.py``, ``jit_lmlatent_step_<chunk>``
in the trace) wrap each part in a ``jax.named_scope``, and the compiled
program's text names every instruction's scope, so an event is known by
its program and its instruction's name as ``lmhybridkernels.py`` knows it
(whose trace reading, run numbering and own-time rule this reuses): every
event counts its own time less its children's, under the innermost scope
its instruction names.

==================  ====================================================
kernel              scope
==================  ====================================================
latent_decode       ``lm/latent_decode``: the decode rows' absorbed query,
                    the Pallas kernel ``latent_decode`` over their latent
                    pages (``ops/pallas_latent.py``) and the up-projection
                    of its output
latent_prefill      ``lm/latent_prefill``: a chunk's expanded attention,
                    the history up-projected a block at a time
experts             ``lm/experts``: the held experts' grouped products
                    (``megablox.gmm``); the router (``lm/router``) and the
                    shared expert (``lm/shared_expert``) are the step's
==================  ====================================================

Work and seconds are of the same steps: those that finished inside the
slice but for the first, numbered as ``lmhybridkernels.steps_of_runs``
numbers them; the work is ``work_mistral4.py``'s count over them; a
kernel's share is the least time the chip's peaks allow its work over the
seconds it took.

A program without these scopes or texts (the parent commit), a run with no
trace and a CPU rehearsal (no peaks) give nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, List, Optional

from chipbench import work_lm, work_mistral4
from chipbench.layer_metrics.lmhybridkernels import (INSTRUCTION, own_seconds,
                                                     read_file,
                                                     steps_of_runs)
from chipbench.reduce import trace as trace_lib

PROGRAM = re.compile(r'^jit_lmlatent_step_(\d+)')
SCOPES = {'latent_decode': 'latent_decode',
          'latent_prefill': 'latent_prefill', 'experts': 'experts',
          'router': None, 'shared_expert': None}


def kernel_of(op_name: str) -> Optional[str]:
    """The kernel an instruction's ``op_name`` path belongs to: by the
    innermost of its components that is a scope (the router's and the
    shared expert's belong to none)."""
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
    material = run['obs'].get('lmlatent')
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
        run['log']('lmlatent kernels: the trace\'s %d runs of the step '
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
           'work': work_mistral4.total_work(
               material['model_config'], [log[step] for step, _ in kept]),
           'steps': len(kept)}
    if path not in _LOGGED:
        _LOGGED.add(path)
        run['log']('lmlatent kernels: %d of the trace\'s %d runs kept '
                   '(steps %d..%d); seconds by kernel %s; by program %s'
                   % (len(kept), len(runs), kept[0][0], kept[-1][0],
                      {k: round(v, 4) for k, v in
                       out['kernel_seconds'].items()},
                      {k: round(v, 4) for k, v in by_program.items()}))
    return out


def read(run):
    traced = of_run(run)
    values = {}
    for kernel in work_mistral4.KERNELS:
        seconds = traced.get('kernel_seconds', {}).get(kernel, 0.0)
        if not seconds > 0:
            continue
        floor = work_lm.least_seconds(traced['work'][kernel], run['peaks'])
        run['log']('lmlatent kernels: %s took %.4f s over %d steps; least '
                   '%.4f s, bound by %s'
                   % (kernel, seconds, traced['steps'], floor['seconds'],
                      floor['bound']))
        values['lmlatentkernels.%s_roofline' % kernel] = \
            100.0 * floor['seconds'] / seconds
    return values
