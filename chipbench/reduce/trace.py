"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. Keyed on the names
the trace itself prints — plane ``/device:TPU:<n>``, lines ``XLA Ops`` and
``XLA Modules``, HLO operation names, host span names — never on source
lines, so a refactor of the program does not break it.

What it gives, per device and for the trace as a whole:

- the traced window (first to last event of any plane, Python frames
  aside) and the union of the
  intervals in which an operation ran on the device: busy seconds, and from
  them the idle share;
- device time per program (the ``XLA Modules`` line): count and seconds;
- the device operations with most time, by the trace's own names;
- collective operations: their union, and the part of it during which no
  other operation ran on that device (exposed);
- every idle gap over a threshold, attributed to the host span that covers
  most of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
COLLECTIVE = re.compile(
    r'\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast)(-start|-done)?\b')
#: a gap shorter than this is the device's own pause between operations
GAP_THRESHOLD_S = 100e-6
#: host events of the Python tracer (one per function call) start with this
PYTHON_FRAME = '$'


class Event(NamedTuple):
    name: str
    start: float   # seconds
    end: float


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    files = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: [Event]}} with times in seconds."""
    from jax.profiler import ProfileData
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for event in line.events:
                start = event.start_ns * 1e-9
                events.append(Event(event.name, start,
                                    start + event.duration_ns * 1e-9))
    return planes


def union(intervals: Iterable[Tuple[float, float]]) -> np.ndarray:
    """(k, 2) array of disjoint sorted intervals covering the same points."""
    spans = sorted((a, b) for a, b in intervals if b > a)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return np.asarray(merged, np.float64).reshape(-1, 2)


def length(spans: np.ndarray) -> float:
    return float((spans[:, 1] - spans[:, 0]).sum())


def subtract(spans: np.ndarray, holes: np.ndarray) -> np.ndarray:
    """The part of ``spans`` (disjoint, sorted) that ``holes`` (disjoint,
    sorted) does not cover."""
    out = []
    for a, b in spans:
        at = a
        for c, d in holes:
            if d <= at:
                continue
            if c >= b:
                break
            if c > at:
                out.append((at, c))
            at = max(at, d)
            if at >= b:
                break
        if at < b:
            out.append((at, b))
    return np.asarray(out, np.float64).reshape(-1, 2)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8,128]{1,0} fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(' = ', 1)[0].strip()
    return head.lstrip('%') or event_name


_LAYOUT = re.compile(r'\{[^{}]*\}')
_RESULT = re.compile(r'^(?P<shapes>\(.*?\)|\S+) (?P<opcode>[\w-]+)\(')


def op_label(event_name: str, limit: int = 96) -> str:
    """The name a breakdown prints: the operation's name, its opcode and
    the shapes it produces, without layouts and operands — ``fusion.12
    fusion f32[8,128]``. A name in another form is kept as it is."""
    name, _, rest = event_name.partition(' = ')
    if not rest:
        return event_name[:limit]
    match = _RESULT.match(_LAYOUT.sub('', rest))
    if not match:
        return op_name(event_name)[:limit]
    return ('%s %s %s' % (op_name(event_name), match.group('opcode'),
                          match.group('shapes')))[:limit]


def is_collective(event_name: str) -> bool:
    return COLLECTIVE.search(op_name(event_name)) is not None or \
        COLLECTIVE.search(event_name.split('(', 1)[0]) is not None


def _host_spans(planes) -> List[Event]:
    spans = []
    for plane, lines in planes.items():
        if DEVICE_PLANE.match(plane) or not plane.startswith('/host:'):
            continue
        for events in lines.values():
            spans.extend(events)
    return spans


class _HostIndex:
    """Host spans long enough to cover most of a gap, as arrays."""

    def __init__(self, host: List[Event], shortest_s: float):
        kept = [e for e in host if e.end - e.start >= shortest_s]
        self.names = [e.name for e in kept]
        self.start = np.asarray([e.start for e in kept], np.float64)
        self.end = np.asarray([e.end for e in kept], np.float64)
        self.named = np.asarray(
            [not e.name.startswith(PYTHON_FRAME) for e in kept], bool)

    def covering(self, a: float, b: float) -> str:
        """The host span that covers most of the gap [a, b): a named span
        (``TraceAnnotation``) before a Python frame, and at equal cover the
        shorter, which is the innermost."""
        if not self.names:
            return 'unattributed'
        cover = np.minimum(b, self.end) - np.maximum(a, self.start)
        share = np.round(np.clip(cover / (b - a), 0.0, 1.0), 2)
        if not (share > 0).any():
            return 'unattributed'
        order = np.lexsort((self.end - self.start, -share,
                            ~(self.named & (share >= 0.5))))
        return self.names[int(order[0])]


def reduce_planes(planes, gap_threshold_s: float = GAP_THRESHOLD_S,
                  top: int = 10) -> dict:
    # Python frames are left out of the window's extent: the frame of
    # stop_trace itself lasts as long as the trace takes to collect
    every = [e for lines in planes.values() for events in lines.values()
             for e in events if not e.name.startswith(PYTHON_FRAME)]
    if not every:
        return {}
    window = (min(e.start for e in every), max(e.end for e in every))
    window_s = window[1] - window[0]
    host = _host_spans(planes)
    host_index = _HostIndex(host, gap_threshold_s / 2)
    devices = {}
    op_seconds: Dict[str, float] = {}
    gap_seconds: Dict[str, float] = {}
    for plane, lines in sorted(planes.items()):
        match = DEVICE_PLANE.match(plane)
        if not match:
            continue
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        busy = union((e.start, e.end) for e in ops)
        collective = union((e.start, e.end) for e in ops
                           if is_collective(e.name))
        compute = union((e.start, e.end) for e in ops
                        if not is_collective(e.name))
        modules: Dict[str, List[float]] = {}
        for e in lines.get(MODULES_LINE, []):
            entry = modules.setdefault(e.name.split('(', 1)[0], [0, 0.0])
            entry[0] += 1
            entry[1] += e.end - e.start
        for e in ops:
            name = op_label(e.name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (e.end - e.start)
        idle = subtract(np.asarray([window], np.float64), busy)
        for a, b in idle:
            if b - a >= gap_threshold_s:
                name = host_index.covering(a, b)
                gap_seconds[name] = gap_seconds.get(name, 0.0) + (b - a)
        devices[int(match.group(1))] = {
            'busy_s': length(busy),
            'idle_share': 1.0 - length(busy) / window_s,
            'collective_s': length(collective),
            'collective_exposed_s': length(subtract(collective, compute)),
            'modules': {name: {'count': int(c), 'seconds': s}
                        for name, (c, s) in modules.items()},
        }
    if not devices:
        return {}
    n = len(devices)

    def ranked(table: Dict[str, float]) -> List[list]:
        # seconds per device, so that four chips do not read as four times
        return [[name, seconds / n] for name, seconds in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    host_span_counts: Dict[str, int] = {}
    for e in host:
        if not e.name.startswith(PYTHON_FRAME):
            host_span_counts[e.name] = host_span_counts.get(e.name, 0) + 1
    return {
        'window_s': window_s,
        'busy_s': float(np.mean([d['busy_s'] for d in devices.values()])),
        'devices': devices,
        'device_ops': ranked(op_seconds),
        'idle_gaps': ranked(gap_seconds),
        'host_span_counts': host_span_counts,
    }


def reduce_trace(trace_dir: str, **kwargs) -> dict:
    """The reduction of the newest trace under ``trace_dir``; {} when there
    is none, or when no operation ran on a device in it."""
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    return reduce_planes(read_planes(path), **kwargs)


def top_module(reduced: dict) -> Optional[Tuple[str, int, float]]:
    """(name, runs, seconds) of the program that took most device time,
    summed over the devices and divided by their number."""
    totals: Dict[str, List[float]] = {}
    devices = reduced.get('devices', {})
    for device in devices.values():
        for name, entry in device['modules'].items():
            total = totals.setdefault(name, [0, 0.0])
            total[0] += entry['count']
            total[1] += entry['seconds']
    if not totals:
        return None
    name = max(totals, key=lambda k: totals[k][1])
    n = len(devices)
    return name, int(totals[name][0] // n), totals[name][1] / n
