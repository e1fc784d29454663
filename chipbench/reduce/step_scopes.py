"""Device time of the train step by part, from a capture and its legend.

The program wraps each part of ``jit_train_step`` in a ``jax.named_scope``
(``code2vec_tpu/scopes.py``: ``c2v_encode``, ``c2v_table_grad``,
``c2v_logits``, ``c2v_ce``, ``c2v_adam``). The profiler's device events
carry the compiled program's instruction names and not their scopes, so the
trainer writes, beside every capture, the text of each step program that ran
in it (``<trace dir>/programs/<module>.<shape key>.hlo.txt`` and ``.json``:
the legend). Here the two are joined: every ``XLA Ops`` event inside a run
of the step program counts **its own time less its children's**
(``lmhybridkernels.own_seconds``) under the part its instruction's
``op_name`` names.

- Autodiff wraps the names (``jvp(c2v_encode)``,
  ``transpose(jvp(c2v_encode))``), and scopes nest (``c2v_table_grad``
  inside the encoder's backward): the part is the **last** ``c2v_`` name
  anywhere in the ``op_name``, never a whole path component.
- ``c2v_logits`` and ``c2v_ce`` fuse into each other, so they are one part,
  ``logits_ce``.
- A collective (``reduce/trace.py::is_collective``) counts under no part:
  ``mesh.collective_ms_per_step`` has it.
- A fusion takes its root's part. A multi-output fusion's root is a
  tuple: where all of its named outputs are of one part, that part (the
  target table's Adam walk with the logits' backward product fused in is
  named ``dot_general`` after its anchor, and all three of its outputs are
  the walk's); where they disagree, the part of the fusion's own name,
  which is its anchor's (the logits' other backward product writes a cast
  and a row sum for the encoder's backward beside its result, and stays
  the logits'). Where its fused instructions name more than one part its
  seconds are also summed as ``mixed``, and the share of the step in such
  fusions goes on the log.
- The rest is ``unscoped``: the coverage of the names.

Seconds are per device and per run of the program (device 0 ... n-1,
averaged). Parts + unscoped + collective is the events' own time inside the
program's runs: the program's device time less the gaps between its
operations.

The step program is the one ``top_module`` names (what
``model.device_ms_per_step`` times). Two packed capacities are two programs
under one module name: where the legend holds several, each group of runs
(the module event's full name carries the program's id) takes the text that
knows most of its events' instructions.

**Nothing, never zeros**, where there is no capture, no legend, or a legend
that names no ``c2v_`` scope: the parent commit has neither scopes nor
legend, and a compile cache that a tree without scopes filled hands back an
executable whose text names none (JAX's cache key ignores metadata). One
line on the log says which.

The work of each part is ``chipbench/work_train_parts.py``'s, at the shapes
the legend's ``.json`` gives.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
from typing import Dict, List, NamedTuple, Optional

from chipbench import work as work_lib
from chipbench import work_train_parts
from chipbench.layer_metrics.lmhybridkernels import own_seconds
from chipbench.reduce import trace as trace_lib

PARTS = work_train_parts.PARTS
#: what tiles the step program's own device time
TILES = PARTS + ('unscoped', 'collective')
SCOPE_PART = {'c2v_encode': 'encode', 'c2v_table_grad': 'table_grad',
              'c2v_logits': 'logits_ce', 'c2v_ce': 'logits_ce',
              'c2v_adam': 'adam'}
SCOPE = re.compile(r'c2v_\w+')
PROGRAMS_DIR = 'programs'
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'\bcalls=%?([\w.\-]+)')
_FUSION = re.compile(r' fusion\(')
_TUPLE = re.compile(r' tuple\((.*?)\)')
_OPERAND = re.compile(r'%([\w.\-]+)')


def part_of(op_name: str) -> Optional[str]:
    """The part an ``op_name`` belongs to: by the last ``c2v_`` name in it,
    wherever autodiff's wrappers put it; None for no name and for a scope
    that is no part of the train step (``c2v_topk``)."""
    found = SCOPE.findall(op_name)
    return SCOPE_PART.get(found[-1]) if found else None


class Program(NamedTuple):
    module: str
    parts: Dict[str, Optional[str]]   # instruction's name -> part
    mixed: frozenset                  # fusions whose body names > 1 part
    named: int                        # instructions under some c2v_ scope
    about: dict                       # the legend's .json


def parse_program(text: str, about: Optional[dict] = None) -> Program:
    """One compiled program's text as {instruction: part}. An instruction
    has the part of its own ``op_name``; a fusion that of its fused
    computation's root: where the root is a tuple, its outputs' if they
    agree, else the fusion's own (its anchor's), else most outputs'; where
    no output is named its own, and failing that the one part its body
    names."""
    module = re.search(r'^HloModule\s+([^\s,]+)', text, re.MULTILINE)
    own: Dict[str, Optional[str]] = {}      # instruction -> its own part
    calls: Dict[str, str] = {}              # fusion -> fused computation
    roots: Dict[str, List[str]] = {}        # computation -> its outputs
    bodies: Dict[str, set] = {}             # computation -> parts it names
    fusions_in: Dict[str, List[str]] = {}   # computation -> its fusions
    named = 0
    computation = None
    for line in text.splitlines():
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
                bodies[computation] = set()
            continue
        is_root, name = instruction.groups()
        op_name = _OP_NAME.search(line)
        part = part_of(op_name.group(1)) if op_name else None
        named += bool(op_name and SCOPE.search(op_name.group(1)))
        own[name] = part
        if computation is not None:
            if part:
                bodies[computation].add(part)
            if is_root:
                outputs = _TUPLE.search(line)
                roots[computation] = _OPERAND.findall(
                    outputs.group(1)) if outputs else [name]
        if _FUSION.search(line):
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
                fusions_in.setdefault(computation, []).append(name)
    parts = dict(own)

    def resolve(name: str, seen: tuple = ()) -> Optional[str]:
        """A fusion's part, from its outputs', themselves resolved (a
        fused computation's root may be a fusion again)."""
        called = calls.get(name)
        if called is None or name in seen:
            return own.get(name)
        outputs = [part for part in (resolve(output, seen + (name,))
                                     for output in roots.get(called, ()))
                   if part]
        if len(set(outputs)) == 1 or (outputs and not own[name]):
            return max(outputs, key=outputs.count)
        # outputs of several parts: the fusion's own name; a root the
        # compiler left unnamed (a scatter): that, then the one part its
        # body names
        body = named_in(called)
        return own[name] or (body.pop() if len(body) == 1 else None)

    def named_in(computation: str, seen: tuple = ()) -> set:
        """The parts a computation's instructions name, nested fusions'
        bodies included."""
        found = set(bodies.get(computation, ()))
        for fusion in fusions_in.get(computation, ()):
            if calls[fusion] not in seen:
                found |= named_in(calls[fusion], seen + (computation,))
        return found

    mixed = set()
    for fusion, called in calls.items():
        parts[fusion] = resolve(fusion)
        if len(named_in(called)) > 1:
            mixed.add(fusion)
    return Program(module=module.group(1) if module else '', parts=parts,
                   mixed=frozenset(mixed), named=named, about=about or {})


def read_legend(trace_dir: str) -> List[Program]:
    """The programs whose text lies beside the capture under
    ``trace_dir``."""
    programs = []
    for path in sorted(glob.glob(os.path.join(trace_dir, PROGRAMS_DIR,
                                              '*.json'))):
        text_path = path[:-len('.json')] + '.hlo.txt'
        if not os.path.isfile(text_path):
            continue
        with open(path) as f:
            about = json.load(f)
        with open(text_path) as f:
            programs.append(parse_program(f.read(), about))
    return programs


def read_devices(path: str, module: str) -> Dict[int, dict]:
    """{device: {'ops': [(start, end, instruction)], 'runs': [(start, end,
    the module event's full name)]}} of one ``.xplane.pb``: the ``XLA
    Ops`` line sorted by start, the longer first, and the runs of
    ``module``."""
    from jax.profiler import ProfileData
    devices = {}
    for plane in ProfileData.from_file(path).planes:
        device = trace_lib.DEVICE_PLANE.match(plane.name)
        if not device:
            continue
        ops, runs = [], []
        for line in plane.lines:
            if line.name not in (trace_lib.OPS_LINE, trace_lib.MODULES_LINE):
                continue
            for event in line.events:
                start = event.start_ns * 1e-9
                end = start + event.duration_ns * 1e-9
                if line.name == trace_lib.OPS_LINE:
                    ops.append((start, end, trace_lib.op_name(event.name)))
                elif event.name.split('(', 1)[0] == module:
                    runs.append((start, end, event.name))
        if ops and runs:
            devices[int(device.group(1))] = {
                'ops': sorted(ops, key=lambda e: (e[0], -e[1])),
                'runs': sorted(runs)}
    return devices


def reduce_device(ops: List[tuple], runs: List[tuple],
                  programs: List[Program]) -> Dict[str, float]:
    """Seconds of one device inside ``runs`` of the step program, by part
    and under ``unscoped``, ``collective``, ``mixed`` (a part of the parts,
    not beside them)."""
    starts = [start for start, _, _ in runs]
    held: Dict[str, list] = {}          # run's full name -> its events
    for name, start, own in own_seconds(ops):
        run = bisect.bisect_right(starts, start) - 1
        if run >= 0 and start < runs[run][1]:
            held.setdefault(runs[run][2], []).append((name, own))
    seconds = dict.fromkeys(TILES + ('mixed',), 0.0)
    for events in held.values():
        names = {name for name, _ in events}
        program = max(programs, key=lambda p: len(names & p.parts.keys()))
        for name, own in events:
            if trace_lib.is_collective(name):
                seconds['collective'] += own
                continue
            seconds[program.parts.get(name) or 'unscoped'] += own
            if name in program.mixed:
                seconds['mixed'] += own
    return seconds


def reduce_capture(devices: Dict[int, dict],
                   programs: List[Program]) -> Dict[str, float]:
    """Seconds per run of the step program, averaged over the devices."""
    per_device = [
        {key: seconds / len(device['runs']) for key, seconds in
         reduce_device(device['ops'], device['runs'], programs).items()}
        for device in devices.values()]
    return {key: sum(d[key] for d in per_device) / len(per_device)
            for key in per_device[0]}


@functools.lru_cache(maxsize=2)
def _of_capture(path: str, trace_dir: str, module: str):
    """(seconds per step by part or None, the step's Program or None, why
    not) of one capture."""
    programs = [p for p in read_legend(trace_dir) if p.module == module]
    if not programs:
        return None, None, ('no text of %s beside the capture (%s/%s): the '
                            'program wrote no legend'
                            % (module, trace_dir, PROGRAMS_DIR))
    if not any(p.named for p in programs):
        return None, None, ('the text of %s names no c2v_ scope: a program '
                            'without scopes, or an executable from a '
                            'compile cache that a tree without scopes '
                            'filled' % module)
    devices = read_devices(path, module)
    if not devices:
        return None, None, 'no run of %s on a device in the capture' % module
    return reduce_capture(devices, programs), programs[0], None


_LOGGED = set()


def of_run(run: dict) -> dict:
    """{} or {'seconds': per step by part, 'work': per part, 'module'} of
    a traced run; the summary, or why there is none, goes on an earlier
    line, once."""
    module = trace_lib.top_module(run['trace']) if run['trace'] else None
    trace_dir = run['log'].__self__.trace_dir
    path = trace_lib.find_xplane(trace_dir)
    if module is None or path is None:
        return {}
    seconds, program, why_not = _of_capture(path, trace_dir, module[0])
    first = path not in _LOGGED
    _LOGGED.add(path)
    if seconds is None:
        if first:
            run['log']('step scopes: nothing read: %s' % why_not)
        return {}
    step = sum(seconds[k] for k in TILES)
    if first:
        run['log']('step scopes: %s, ms per step on a device: %s; '
                   'together %.3f; in fusions that mix parts %.3f (%.1f%% '
                   'of the step)'
                   % (module[0], ', '.join(
                       '%s %.3f' % (k, 1e3 * seconds[k])
                       for k in TILES),
                      1e3 * step, 1e3 * seconds['mixed'],
                      100.0 * seconds['mixed'] / step if step else 0.0))
    out = {'seconds': seconds, 'module': module[0], 'work': None}
    shapes = work_train_parts.shapes_of(program.about)
    obs = run['obs']
    if shapes is not None and 'examples_per_step_per_chip' in obs:
        examples = obs['examples_per_step_per_chip']
        out['work'] = work_train_parts.train_step_parts(
            shapes, examples, examples * obs['mean_contexts'],
            chips=run['cell'].chips)
    return out


def read_metric(run: dict, key: str) -> dict:
    """``{'kernels.<key>': value}`` or {}: what a
    ``layer_metrics/kernels.<key>.py`` returns. ``<part>_ms_per_step`` is
    the part's own seconds a step; ``<part>_roofline`` the least time the
    chip's peaks allow the part's work (``work_train_parts``) over them,
    in percent, the bound named on an earlier line."""
    traced = of_run(run)
    if not traced:
        return {}
    if key.endswith('_ms_per_step'):
        part = key[:-len('_ms_per_step')]
        return {'kernels.' + key: 1e3 * traced['seconds'][part]}
    part = key[:-len('_roofline')]
    seconds = traced['seconds'][part]
    if traced['work'] is None or not run['peaks'] or not seconds > 0:
        return {}
    floor = work_lib.least_seconds(traced['work'][part], run['peaks'])
    run['log']('kernels: %s: least time %.3f ms per step, bound by %s (%s); '
               'took %.3f ms'
               % (part, 1e3 * floor['seconds'], floor['bound'],
                  ', '.join('%s %.3f ms' % (k, 1e3 * v)
                            for k, v in floor['bounds'].items()),
                  1e3 * seconds))
    return {'kernels.' + key: 100.0 * floor['seconds'] / seconds}
