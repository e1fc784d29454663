"""The program's own profiler events, with their ``stats`` and threads.

``reduce/trace.py`` keeps an event's name and times; the serving engine's
phase events (``serving/tokenize`` ... ``serving/deliver``,
``process/gc_pause``: OBSERVABILITY.md "Reading the phases in a profiler
capture") also carry ``stats`` (``queue_wait_ms``, ``handoff_ms``,
``batch`` ...) and their thread matters: the profiler names every line
``python3``, so a decode worker is known as the line that holds
``serving/fetch`` events. This module reads the host planes of a run's
``.xplane.pb`` with both, and reduces them to what the ``engine.*``
readers of ``layer_metrics/`` report.

The route to the run's trace: ``run`` carries no directory, but
``run['log']`` is the bound ``log`` of the run's ``Context``, whose
``trace_dir`` is where the runner's profiler session wrote. Readers call
``of_run(run)``; the reduction is made once a trace file.

A program without these events (every commit before they were added)
gives an empty reduction, and the readers report nothing.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from chipbench.reduce import trace as trace_lib

#: the events of one batch on a decode worker's line
POOL_EVENTS = ('serving/fetch', 'serving/decode', 'serving/deliver')
#: the collector's hook came into the program with these events: a capture
#: that holds them and no ``process/gc_pause`` had no full collection,
#: and its longest pause reads 0; one without them had no hook, and
#: reads nothing
HOOKED_BY = 'serving/deliver'
#: a request's phases, in order; their sum is set against its
#: ``since_enqueue_ms`` + the length of its ``serving/deliver``
REQUEST_PHASES = ('queue_wait', 'pack', 'h2d', 'dispatch', 'handoff',
                  'fetch', 'decode', 'behind_deliveries', 'deliver')


class HostEvent(NamedTuple):
    name: str
    start: float    # seconds on the profiler session's clock
    end: float
    line: int       # the thread: the line's position among the host lines
    stats: dict

    @property
    def ms(self) -> float:
        return 1e3 * (self.end - self.start)


def read_events(path: str, prefixes=('serving/', 'process/')) -> dict:
    """{'events': [HostEvent] named under ``prefixes``, 'window_s': first
    to last event of any plane, Python frames aside (as trace.py)}."""
    from jax.profiler import ProfileData
    events: List[HostEvent] = []
    first, last, line_no = float('inf'), float('-inf'), 0
    for plane in ProfileData.from_file(path).planes:
        host = plane.name.startswith('/host:')
        for line in plane.lines:
            line_no += 1
            for event in line.events:
                name = event.name
                if name.startswith(trace_lib.PYTHON_FRAME):
                    continue
                start = event.start_ns * 1e-9
                end = start + event.duration_ns * 1e-9
                first, last = min(first, start), max(last, end)
                if host and name.startswith(prefixes):
                    events.append(HostEvent(name, start, end, line_no,
                                            dict(event.stats)))
    return {'events': events, 'window_s': max(last - first, 0.0)}


def _median(values) -> Optional[float]:
    values = list(values)
    return float(np.median(values)) if values else None


def request_phases(events: List[HostEvent]) -> List[dict]:
    """One dict a delivered request of the slice whose batch's events all
    lie inside it: ``tier``, each of ``REQUEST_PHASES`` in ms, their
    ``sum_ms``, and ``enqueue_to_end_ms`` (``since_enqueue_ms`` + the
    deliver event's length), which the sum should tile."""
    batch: Dict[tuple, HostEvent] = {}
    for e in events:
        if 'batch' in e.stats and e.name != 'serving/deliver':
            batch[(e.name, e.stats['batch'])] = e
    out = []
    for e in events:
        if e.name != 'serving/deliver':
            continue
        parts = {name: batch.get(('serving/' + name, e.stats['batch']))
                 for name in ('pack', 'h2d', 'dispatch', 'fetch', 'decode')}
        if None in parts.values():
            continue    # the batch began before the slice did
        phases = {name: part.ms for name, part in parts.items()}
        phases['queue_wait'] = float(e.stats['queue_wait_ms'])
        phases['handoff'] = float(parts['fetch'].stats['handoff_ms'])
        phases['behind_deliveries'] = 1e3 * (e.start - parts['decode'].end)
        phases['deliver'] = e.ms
        out.append(dict(phases, tier=e.stats['tier'],
                        sum_ms=sum(phases[p] for p in REQUEST_PHASES),
                        enqueue_to_end_ms=float(e.stats['since_enqueue_ms'])
                        + e.ms))
    return out


def reduce_events(events: List[HostEvent], window_s: float) -> dict:
    """{} where the program wrote none of these events."""
    if not events:
        return {}
    by_name: Dict[str, List[HostEvent]] = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)

    def ms_of(name: str) -> List[float]:
        return [e.ms for e in by_name.get(name, ())]

    pool_lines = {e.line for e in by_name.get('serving/fetch', ())}
    pool_busy_s = sum(
        trace_lib.length(trace_lib.union(
            (e.start, e.end) for e in events
            if e.line == line and e.name in POOL_EVENTS))
        for line in pool_lines)
    requests = request_phases(events)
    tiers = {}
    for tier in sorted({r['tier'] for r in requests}):
        rows = [r for r in requests if r['tier'] == tier]
        tiers[tier] = dict(
            {name: _median(r[name] for r in rows)
             for name in REQUEST_PHASES + ('sum_ms', 'enqueue_to_end_ms')},
            requests=len(rows))
    return {
        'window_s': window_s,
        'counts': {name: len(found) for name, found in by_name.items()},
        'tokenize_ms_p50': _median(ms_of('serving/tokenize')),
        'queue_wait_ms_p50': _median(
            e.stats['queue_wait_ms'] for e in by_name.get(
                'serving/deliver', ())),
        'handoff_ms_p50': _median(
            e.stats['handoff_ms'] for e in by_name.get('serving/fetch', ())),
        'fetch_ms_p50': _median(ms_of('serving/fetch')),
        'index_search_ms_p50': _median(ms_of('serving/index_search')),
        'deliver_ms_p50': _median(ms_of('serving/deliver')),
        'gc_pause_ms_max': max(
            ms_of('process/gc_pause'),
            default=0.0 if HOOKED_BY in by_name else None),
        'gc_pauses_ms': sorted(ms_of('process/gc_pause')),
        'no_work_s': 1e-3 * sum(ms_of('serving/no_work')),
        'coalesce_s': 1e-3 * sum(ms_of('serving/coalesce')),
        'pool_lines': len(pool_lines),
        'pool_busy_s': pool_busy_s,
        'tiers': tiers,
    }


@functools.lru_cache(maxsize=2)
def reduce_file(path: str) -> dict:
    read = read_events(path)
    return reduce_events(read['events'], read['window_s'])


_LOGGED = set()


def of_run(run: dict) -> dict:
    """The reduction of the traced run's own profiler file ({} if it
    wrote none); its summary goes on an earlier line, once."""
    path = trace_lib.find_xplane(run['log'].__self__.trace_dir)
    if path is None:
        return {}
    reduced = reduce_file(path)
    if reduced and path not in _LOGGED:
        _LOGGED.add(path)
        run['log']('host spans: %s' % {
            k: v for k, v in reduced.items() if k != 'tiers'})
        for tier, phases in reduced['tiers'].items():
            run['log']('host spans: a %s request, medians in ms: %s'
                       % (tier, {k: round(v, 3) if isinstance(v, float)
                                 else v for k, v in phases.items()}))
    return reduced


def read_metric(run: dict, key: str) -> dict:
    """``{'engine.<key>': value}`` of the run's reduction, or {} where it
    has none: what a ``layer_metrics/engine.<key>.py`` returns."""
    value = of_run(run).get(key)
    return {} if value is None else {'engine.' + key: value}


def pool_busy_share(reduced: dict, workers: int) -> Optional[float]:
    """Percent of ``workers`` x the slice that decode workers spent in
    fetch, decode and deliver events."""
    if not reduced or not reduced['pool_lines'] or \
            not reduced['window_s'] > 0:
        return None
    return 100.0 * reduced['pool_busy_s'] / (workers * reduced['window_s'])
