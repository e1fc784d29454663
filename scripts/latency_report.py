"""Latency attribution from the serving span log (telemetry/tracing.py).

Reads the flat span records of ``spans.jsonl`` (or any
``flight_<event>.jsonl`` flight-recorder dump — header lines are
skipped; a flight path transparently merges its replica-namespaced
``flight_<event>_r<N>.jsonl`` siblings, the worker-process form, with
cross-file deduplication) and reports:

- **phase x bucket x tier x replica breakdown**: p50/p95/p99
  (nearest-rank) and count per span name, keyed by the trace's output
  tier, the batch bucket it dispatched on, and — for serving-mesh
  traffic — WHICH replica served it (the ``replica`` attribute the
  mesh dispatcher stamps on the pack span; '-' for single-engine
  traffic);
- **queue-wait vs device-time decomposition**: where end-to-end latency
  actually went (the micro-batcher's direct tuning signal:
  queue-dominated -> lower SERVING_MAX_DELAY_MS / raise buckets /
  add replicas; device-dominated -> the model is the bottleneck), as a
  FLEET view plus a per-replica x tier table — the "which replica is
  slow" question under a mesh is read straight off it;
- **terminal statuses**: how many traces ended ok / shed / expired /
  closed / error — shed storms and deadline expiries show up here;
- **top-K slowest traces** as full indented span trees, for the "why is
  p99 like that" question;
- with ``--fleet``: the cross-process view over STITCHED traces
  (OBSERVABILITY.md "Fleet observability") — true
  queue-vs-WIRE-vs-device decomposition per replica for worker-mode
  mesh traffic (the wire residual is the transport cost no
  single-process span can show), plus the count of delivered traces
  whose worker-side spans never stitched (``scripts/mesh_soak.py``
  asserts that count to zero).

``--perfetto out.json`` converts the spans to the Chrome trace-event
format, so serving traces open in the same Perfetto/chrome://tracing
tooling as the ``jax.profiler`` captures that
``benchmarks/analyze_trace.py`` decomposes.  ``--json`` emits one JSON
line per phase row for machine consumers (benchmarks/capture_all.sh
folds these into the capture trajectory).

Usage:
    python scripts/latency_report.py --spans <dir>/spans.jsonl \
        [--top 5] [--json] [--perfetto out.json]

Dependency-free (stdlib only), like the rest of the tracing layer.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

#: span names whose interval overlaps other phases by design (the
#: coalescing window contains its members' queue_wait); reported, but
#: excluded from phase-sum / decomposition arithmetic
OVERLAPPING = frozenset(('serving.coalesce',))

#: the disjoint per-request phase chain, in lifecycle order — these tile
#: the root span (small scheduler gaps aside), so their sums approximate
#: end-to-end latency (asserted in tests/test_tracing.py)
PHASE_CHAIN = (
    'serving.admission', 'serving.tokenize', 'serving.queue_wait',
    'serving.stall', 'serving.pack', 'serving.h2d', 'serving.dispatch',
    'serving.handoff', 'serving.device_execute', 'serving.decode',
    'serving.deliver',
)


#: flight-recorder dump filename, with the optional replica-instance
#: namespace a worker-mode mesh replica writes under
#: (flight_<event>_r<N>.jsonl — telemetry/tracing.py): the parent and
#: its workers share one telemetry dir, so a postmortem must read BOTH
#: forms
FLIGHT_RE = re.compile(
    r'^flight_(?P<event>.+?)(?:_(?P<inst>r\d+))?\.jsonl$')


def collect_span_paths(path: str) -> List[str]:
    """Expand one span-log path into every sibling that belongs to the
    same story: a ``flight_<event>.jsonl`` (or a replica-namespaced
    ``flight_<event>_r<N>.jsonl``) pulls in every other dump of that
    event in the directory.  A plain spans.jsonl stays itself."""
    match = FLIGHT_RE.match(os.path.basename(path))
    if match is None:
        return [path]
    dirname = os.path.dirname(path) or '.'
    event = match.group('event')
    paths = {path}
    try:
        siblings = sorted(os.listdir(dirname))
    except OSError:
        siblings = []
    for candidate in siblings:
        sibling = FLIGHT_RE.match(candidate)
        if sibling is not None and sibling.group('event') == event:
            paths.add(os.path.join(dirname, candidate))
    return sorted(paths)


def load_spans(path: str) -> List[dict]:
    """Flat span records from a spans.jsonl or flight_<event>.jsonl
    (flight header lines and garbage lines are skipped).  Flight paths
    transparently merge their replica-namespaced siblings; records
    appearing in several files (a trace in both the span log and a
    flight ring) are deduplicated."""
    records = []
    seen = set()
    for one_path in collect_span_paths(path):
        # only GLOBBED siblings may be absent (raced away); the
        # caller's own path stays strict — a typo'd path must fail,
        # not masquerade as an empty span log
        if one_path != path and not os.path.exists(one_path):
            continue
        with open(one_path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue
                if not (isinstance(rec, dict) and 'name' in rec
                        and 'trace' in rec):
                    continue
                key = (rec['trace'], rec.get('span'), rec['name'],
                       rec.get('t0'))
                if key in seen:
                    continue
                seen.add(key)
                records.append(rec)
    return records


def group_traces(records: List[dict]) -> Dict[str, dict]:
    """trace_id -> {'root': record|None, 'spans': [records]} (spans in
    file order; the root is the parentless span)."""
    traces: Dict[str, dict] = {}
    for rec in records:
        entry = traces.setdefault(rec['trace'],
                                  {'root': None, 'spans': []})
        entry['spans'].append(rec)
        if rec.get('parent') is None:
            entry['root'] = rec
    return traces


def percentile(sorted_ms: List[float], q: float) -> float:
    """Nearest-rank percentile over an ascending list (same convention
    as telemetry.core.Timer.snapshot)."""
    if not sorted_ms:
        return 0.0
    idx = min(len(sorted_ms) - 1, max(0, int(q * len(sorted_ms))))
    return sorted_ms[idx]


def trace_key(entry: dict) -> Tuple[str, str, str]:
    """(tier, bucket, replica) attribution for one trace: tier from the
    root attrs, bucket + replica from the pack span that dispatched it
    ('-' for traces that never reached a dispatch — shed/expired/closed
    — and '-' replica for single-engine traffic)."""
    root = entry['root'] or {}
    tier = str((root.get('attrs') or {}).get('tier', '-'))
    bucket = '-'
    replica = '-'
    for rec in entry['spans']:
        if rec['name'] == 'serving.pack':
            attrs = rec.get('attrs') or {}
            bucket = str(attrs.get('bucket', '-'))
            # the pack span also carries the EFFECTIVE tier (post-
            # degradation) and, on a mesh, the serving replica
            tier = str(attrs.get('tier', tier))
            replica = str(attrs.get('replica', '-'))
            break
    return tier, bucket, replica


def trace_scenario(entry: dict) -> str:
    """Scenario attribution for one trace: the workload label stamped
    into the root attrs at mesh admission and carried by the dispatch
    trace context (WORKLOADS.md); '-' for unlabeled traffic."""
    root = entry['root'] or {}
    scenario = (root.get('attrs') or {}).get('scenario')
    if scenario is None:
        for rec in entry['spans']:
            scenario = (rec.get('attrs') or {}).get('scenario')
            if scenario is not None:
                break
    return '-' if scenario is None else str(scenario)


def phase_rows(traces: Dict[str, dict]
               ) -> Dict[Tuple[str, str, str, str], List[float]]:
    """(phase, tier, bucket, replica) -> ascending durations (ms)."""
    rows: Dict[Tuple[str, str, str, str], List[float]] = {}
    for entry in traces.values():
        tier, bucket, replica = trace_key(entry)
        for rec in entry['spans']:
            rows.setdefault((rec['name'], tier, bucket, replica),
                            []).append(float(rec.get('dur_ms', 0.0)))
    for durs in rows.values():
        durs.sort()
    return rows


def _union_ms(spans: List[dict], name: str) -> float:
    """Total wall-clock covered by the named spans (ms): the union of
    their [t0, t1] intervals — an oversize request's chunks run their
    queue waits and device executes CONCURRENTLY, and summing the
    overlapping durations would over-count by the chunk fan-out."""
    intervals = sorted((float(r['t0']), float(r['t1']))
                       for r in spans if r['name'] == name)
    covered = 0.0
    end = None
    for t0, t1 in intervals:
        if end is None or t0 > end:
            covered += t1 - t0
            end = t1
        elif t1 > end:
            covered += t1 - end
            end = t1
    return covered * 1e3


def decomposition(traces: Dict[str, dict]) -> Dict[str, List[float]]:
    """Per delivered trace: end-to-end, queue-wait, and device-time
    (ms, ascending) — the queue-vs-device attribution."""
    out: Dict[str, List[float]] = {'end_to_end': [], 'queue_wait': [],
                                   'device': [], 'other': []}
    for entry in traces.values():
        root = entry['root']
        if root is None or root.get('status') not in (None, 'ok'):
            continue
        total = float(root.get('dur_ms', 0.0))
        queue = _union_ms(entry['spans'], 'serving.queue_wait')
        device = _union_ms(entry['spans'], 'serving.device_execute')
        out['end_to_end'].append(total)
        out['queue_wait'].append(queue)
        out['device'].append(device)
        out['other'].append(max(0.0, total - queue - device))
    for values in out.values():
        values.sort()
    return out


def replica_decomposition(traces: Dict[str, dict]
                          ) -> Dict[Tuple[str, str],
                                    Dict[str, List[float]]]:
    """(replica, tier) -> {end_to_end, queue_wait, device} (ms,
    ascending) over delivered traces — the per-replica column of the
    fleet decomposition (mesh traffic stamps the replica on the pack
    span; single-engine traffic lands under replica '-')."""
    out: Dict[Tuple[str, str], Dict[str, List[float]]] = {}
    for entry in traces.values():
        root = entry['root']
        if root is None or root.get('status') not in (None, 'ok'):
            continue
        tier, _bucket, replica = trace_key(entry)
        parts = out.setdefault((replica, tier),
                               {'end_to_end': [], 'queue_wait': [],
                                'device': []})
        parts['end_to_end'].append(float(root.get('dur_ms', 0.0)))
        parts['queue_wait'].append(
            _union_ms(entry['spans'], 'serving.queue_wait'))
        parts['device'].append(
            _union_ms(entry['spans'], 'serving.device_execute'))
    for parts in out.values():
        for values in parts.values():
            values.sort()
    return out


#: the fleet decomposition's wire residual subtracts the parent-side
#: phases that are NOT queue wait; everything left after queue + the
#: remote envelope is time on the wire (frame send, kernel buffers,
#: receiver scheduling)
_PARENT_PHASES = ('serving.admission', 'serving.tokenize')


def fleet_decomposition(traces: Dict[str, dict]
                        ) -> Dict[Tuple[str, str, str],
                                  Dict[str, List[float]]]:
    """(replica, tier, scenario) -> {end_to_end, queue_wait, wire,
    device, worker_host} (ms, ascending) over delivered traces — the
    ``--fleet`` view of STITCHED cross-process traces.  The scenario
    axis rides the spans the stitching already carries: the admission-
    time workload label lands in the root attrs and the dispatch trace
    context, so per-scenario fleet latency needs no new span names
    ('-' buckets unlabeled traffic).

    For worker-mode mesh traffic the parent only sees admission,
    tokenize, and queue wait; the grafted ``serving.remote`` envelope
    covers the worker's receipt-to-finish, ``serving.device_execute``
    nests inside it, and the residual between end-to-end and
    (parent phases + queue + remote) is true WIRE time — the
    cross-process transport cost no single-process span could show.
    Thread-mode traces land with wire 0 (there is no wire).

    Requests served from the memoization tier (a ``serving.memo_hit``
    marker span, SERVING.md "Memoization tier") are split out under
    replica ``memo``: their end-to-end IS the whole story — zero
    queue, zero wire, zero device — so the fleet table attributes the
    saved device work to the cache instead of diluting a replica's
    column with sub-ms rows."""
    out: Dict[Tuple[str, str, str], Dict[str, List[float]]] = {}
    for entry in traces.values():
        root = entry['root']
        if root is None or root.get('status') not in (None, 'ok'):
            continue
        tier, _bucket, replica = trace_key(entry)
        scenario = trace_scenario(entry)
        if any(rec['name'] == 'serving.memo_hit'
               for rec in entry['spans']):
            replica = 'memo'
        total = float(root.get('dur_ms', 0.0))
        queue = _union_ms(entry['spans'], 'serving.queue_wait')
        device = _union_ms(entry['spans'], 'serving.device_execute')
        remote = _union_ms(entry['spans'], 'serving.remote')
        if remote > 0:
            parent = sum(_union_ms(entry['spans'], name)
                         for name in _PARENT_PHASES)
            wire = max(0.0, total - queue - remote - parent)
            worker_host = max(0.0, remote - device)
        else:
            wire = 0.0
            worker_host = 0.0
        parts = out.setdefault(
            (replica, tier, scenario),
            {'end_to_end': [], 'queue_wait': [], 'wire': [],
             'device': [], 'worker_host': []})
        parts['end_to_end'].append(total)
        parts['queue_wait'].append(queue)
        parts['wire'].append(wire)
        parts['device'].append(device)
        parts['worker_host'].append(worker_host)
    for parts in out.values():
        for values in parts.values():
            values.sort()
    return out


def unstitched_traces(traces: Dict[str, dict]) -> List[str]:
    """Delivered traces with NO device-execute attribution — for
    worker-mode mesh traffic that means the worker-side spans never
    made it back over the wire (the stitching failure mode
    ``scripts/mesh_soak.py`` asserts to zero).  Thread-mode and
    single-engine traces record device_execute locally, so any
    delivered trace missing it is wire-truncated."""
    out = []
    for trace_id, entry in traces.items():
        root = entry['root']
        if root is None or root.get('status') not in (None, 'ok'):
            continue
        if root.get('name') != 'serving.request':
            continue  # engine-level singles (canary shadows) have no
            #           device leg by design
        if any(rec['name'] == 'serving.memo_hit'
               for rec in entry['spans']):
            continue  # served from the memoization tier: ZERO device
            #           work is the point, not a truncated wire
        if not any(rec['name'] == 'serving.device_execute'
                   for rec in entry['spans']):
            out.append(trace_id)
    return sorted(out)


def status_counts(traces: Dict[str, dict]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for entry in traces.values():
        root = entry['root']
        status = root.get('status', '?') if root else '?'
        counts[status] = counts.get(status, 0) + 1
    return counts


def format_tree(entry: dict) -> List[str]:
    """Indented span-tree lines for one trace (children under parents,
    by span id)."""
    spans = sorted(entry['spans'], key=lambda r: (r['t0'], r['span']))
    children: Dict[Optional[int], List[dict]] = {}
    for rec in spans:
        children.setdefault(rec.get('parent'), []).append(rec)
    lines: List[str] = []

    def walk(rec: dict, depth: int) -> None:
        attrs = rec.get('attrs') or {}
        extra = ' '.join('%s=%s' % (k, v) for k, v in sorted(
            attrs.items()) if k not in ('reason',))
        reason = attrs.get('reason') or rec.get('attrs', {}).get('reason')
        lines.append('  %s%-28s %9.2fms%s%s'
                     % ('  ' * depth, rec['name'],
                        float(rec.get('dur_ms', 0.0)),
                        ('  [' + extra + ']') if extra else '',
                        ('  reason: ' + str(reason)) if reason else ''))
        for child in children.get(rec['span'], ()):
            walk(child, depth + 1)

    for root in children.get(None, ()):
        walk(root, 0)
    return lines


def to_perfetto(traces: Dict[str, dict]) -> List[dict]:
    """Chrome trace-event ('X' complete events) conversion: one tid lane
    per trace, microsecond timestamps rebased to the earliest span."""
    t_min = min((rec['t0'] for entry in traces.values()
                 for rec in entry['spans']), default=0.0)
    events = []
    for lane, (trace_id, entry) in enumerate(sorted(traces.items()), 1):
        tier, bucket, replica = trace_key(entry)
        for rec in entry['spans']:
            attrs = dict(rec.get('attrs') or {})
            attrs['trace'] = trace_id
            if rec.get('status'):
                attrs['status'] = rec['status']
            events.append({
                'name': rec['name'],
                'cat': 'tier:%s,bucket:%s,replica:%s'
                       % (tier, bucket, replica),
                'ph': 'X',
                'ts': (rec['t0'] - t_min) * 1e6,
                'dur': max(0.0, (rec['t1'] - rec['t0']) * 1e6),
                'pid': 1,
                'tid': lane,
                'args': attrs,
            })
    return events


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description='p50/p95/p99 latency attribution from a serving '
                    'span log')
    parser.add_argument('--spans', required=True,
                        help='spans.jsonl or flight_<event>.jsonl path')
    parser.add_argument('--top', type=int, default=5,
                        help='slowest span trees to print (0 = none)')
    parser.add_argument('--fleet', action='store_true',
                        help='cross-process fleet view over STITCHED '
                             'traces: queue-vs-wire-vs-device '
                             'decomposition per replica, plus the '
                             'count of delivered traces whose worker-'
                             'side spans never stitched (wire-'
                             'truncated)')
    parser.add_argument('--json', action='store_true',
                        help='emit machine-readable JSON lines instead '
                             'of the table')
    parser.add_argument('--perfetto', default=None, metavar='OUT.json',
                        help='also write a Chrome-trace/Perfetto file')
    args = parser.parse_args(argv)

    if not os.path.exists(args.spans):
        print('no span log at %s' % args.spans, file=sys.stderr)
        return 1
    records = load_spans(args.spans)
    traces = group_traces(records)
    if not traces:
        print('no traces in %s' % args.spans, file=sys.stderr)
        return 1

    rows = phase_rows(traces)
    statuses = status_counts(traces)
    decomp = decomposition(traces)
    per_replica = replica_decomposition(traces)
    # the per-replica table earns its ink only when a mesh actually
    # stamped replica ids (single-engine logs land entirely under '-')
    meshy = any(replica != '-' for replica, _tier in per_replica)

    if args.json:
        print(json.dumps({'measure': 'trace_statuses', 'value': statuses,
                          'traces': len(traces)}))
        for (phase, tier, bucket, replica), durs in sorted(rows.items()):
            print(json.dumps({
                'measure': 'phase_latency_ms', 'phase': phase,
                'tier': tier, 'bucket': bucket, 'replica': replica,
                'count': len(durs),
                'p50': round(percentile(durs, 0.50), 3),
                'p95': round(percentile(durs, 0.95), 3),
                'p99': round(percentile(durs, 0.99), 3),
            }))
        for part, values in sorted(decomp.items()):
            if not values:
                continue
            print(json.dumps({
                'measure': 'latency_decomposition_ms', 'part': part,
                'count': len(values),
                'p50': round(percentile(values, 0.50), 3),
                'p99': round(percentile(values, 0.99), 3),
            }))
        for (replica, tier), parts in sorted(per_replica.items()):
            for part, values in sorted(parts.items()):
                print(json.dumps({
                    'measure': 'replica_decomposition_ms',
                    'replica': replica, 'tier': tier, 'part': part,
                    'count': len(values),
                    'p50': round(percentile(values, 0.50), 3),
                    'p99': round(percentile(values, 0.99), 3),
                }))
        if args.fleet:
            unstitched = unstitched_traces(traces)
            print(json.dumps({'measure': 'unstitched_traces',
                              'value': len(unstitched),
                              'traces': unstitched[:32]}))
            for (replica, tier, scenario), parts in sorted(
                    fleet_decomposition(traces).items()):
                for part in ('end_to_end', 'queue_wait', 'wire',
                             'device', 'worker_host'):
                    values = parts[part]
                    print(json.dumps({
                        'measure': 'fleet_decomposition_ms',
                        'replica': replica, 'tier': tier,
                        'scenario': scenario, 'part': part,
                        'count': len(values),
                        'p50': round(percentile(values, 0.50), 3),
                        'p99': round(percentile(values, 0.99), 3),
                    }))
    else:
        print('== %d trace(s) from %s' % (len(traces), args.spans))
        print('statuses: ' + ', '.join('%s=%d' % kv
                                       for kv in sorted(statuses.items())))
        print()
        print('%-26s %-10s %-7s %-7s %6s %9s %9s %9s'
              % ('phase', 'tier', 'bucket', 'replica', 'count',
                 'p50_ms', 'p95_ms', 'p99_ms'))
        for (phase, tier, bucket, replica), durs in sorted(rows.items()):
            print('%-26s %-10s %-7s %-7s %6d %9.2f %9.2f %9.2f'
                  % (phase, tier, bucket, replica, len(durs),
                     percentile(durs, 0.50), percentile(durs, 0.95),
                     percentile(durs, 0.99)))
        if decomp['end_to_end']:
            print()
            print('fleet decomposition over %d delivered trace(s):'
                  % len(decomp['end_to_end']))
            for part in ('end_to_end', 'queue_wait', 'device', 'other'):
                values = decomp[part]
                print('  %-12s p50 %9.2fms  p99 %9.2fms'
                      % (part, percentile(values, 0.50),
                         percentile(values, 0.99)))
        if meshy:
            print()
            print('per-replica decomposition (queue-wait vs device):')
            print('  %-7s %-10s %6s %9s %9s %9s %9s %9s %9s'
                  % ('replica', 'tier', 'count', 'queue_p50',
                     'queue_p99', 'dev_p50', 'dev_p99', 'e2e_p50',
                     'e2e_p99'))
            for (replica, tier), parts in sorted(per_replica.items()):
                print('  %-7s %-10s %6d %9.2f %9.2f %9.2f %9.2f '
                      '%9.2f %9.2f'
                      % (replica, tier, len(parts['end_to_end']),
                         percentile(parts['queue_wait'], 0.50),
                         percentile(parts['queue_wait'], 0.99),
                         percentile(parts['device'], 0.50),
                         percentile(parts['device'], 0.99),
                         percentile(parts['end_to_end'], 0.50),
                         percentile(parts['end_to_end'], 0.99)))
        if args.fleet:
            unstitched = unstitched_traces(traces)
            print()
            print('fleet view (stitched cross-process traces): %d '
                  'delivered trace(s) UNSTITCHED (no device-execute '
                  'attribution — worker spans lost on the wire)'
                  % len(unstitched))
            fleet = fleet_decomposition(traces)
            if fleet:
                print('  %-7s %-10s %-16s %6s %9s %9s %9s %9s %9s'
                      % ('replica', 'tier', 'scenario', 'count',
                         'queue_p99', 'wire_p99', 'dev_p99',
                         'whost_p99', 'e2e_p99'))
                for (replica, tier, scenario), parts in sorted(
                        fleet.items()):
                    print('  %-7s %-10s %-16s %6d %9.2f %9.2f %9.2f '
                          '%9.2f %9.2f'
                          % (replica, tier, scenario,
                             len(parts['end_to_end']),
                             percentile(parts['queue_wait'], 0.99),
                             percentile(parts['wire'], 0.99),
                             percentile(parts['device'], 0.99),
                             percentile(parts['worker_host'], 0.99),
                             percentile(parts['end_to_end'], 0.99)))
        if args.top > 0:
            slowest = sorted(
                (entry for entry in traces.values()
                 if entry['root'] is not None),
                key=lambda e: float(e['root'].get('dur_ms', 0.0)),
                reverse=True)[:args.top]
            for entry in slowest:
                root = entry['root']
                print()
                print('trace %s  status=%s  %0.2fms'
                      % (root['trace'], root.get('status', '?'),
                         float(root.get('dur_ms', 0.0))))
                for line in format_tree(entry):
                    print(line)

    if args.perfetto:
        events = to_perfetto(traces)
        with open(args.perfetto, 'w') as f:
            json.dump({'traceEvents': events,
                       'displayTimeUnit': 'ms'}, f)
        print('perfetto trace (%d events) -> %s'
              % (len(events), args.perfetto),
              file=sys.stderr)
    return 0


if __name__ == '__main__':
    sys.exit(main())
