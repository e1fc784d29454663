"""Summarize benchmarks/results/*.jsonl captures into one table.

``capture_all.sh`` appends stage-wrapped JSON lines ({"stage", "rc",
"secs", "data": {...}}); the interactive harnesses emit raw measure
lines. This collates
both shapes so the A/B verdicts (rbg dropout, fused CE,
bf16-mu, Pallas C=1024) can be read off — and defaults flipped on
evidence — without re-parsing JSONL by hand.

Run: python benchmarks/summarize_captures.py [--dir benchmarks/results]
"""
from __future__ import annotations

import argparse
import json
import os


def iter_records(path: str):
    with open(path) as f:
        for raw in f:
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            stage = rec.get('stage')
            data = rec.get('data') if isinstance(rec.get('data'), dict) \
                else (rec if 'stage' not in rec else None)
            # a stage wrapper with null data is a FAILED stage (run_stage
            # writes it when the stage produced no JSON) — surface it,
            # silence here would read as "stage not run yet"
            if data is None and stage is not None:
                yield stage, rec.get('rc'), {'measure': 'STAGE FAILED',
                                             'value': None,
                                             'secs': rec.get('secs')}
            elif data is not None:
                yield stage, rec.get('rc'), data


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--dir', default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'results'))
    args = parser.parse_args()

    names = sorted(n for n in os.listdir(args.dir) if n.endswith('.jsonl'))
    empty_rounds = 0
    for name in names:
        print(f'== {name}')
        measured = False
        for stage, rc, data in iter_records(os.path.join(args.dir, name)):
            label = (data.get('measure') or data.get('metric')
                     or data.get('probe') or next(iter(data), '?'))
            value = data.get('value')
            extras = {k: v for k, v in data.items()
                      if k in ('examples_per_sec', 'unit', 'vs_baseline',
                               'variant', 'devices', 'opt_sharding',
                               'speedup', 'verdict', 'distribution',
                               'step_ms', 'partition_overhead_vs_1dev',
                               'attempts', 'phase', 'tier', 'bucket',
                               'p50', 'p99',
                               # ragged-fusion A/B axes (ISSUE 10): the
                               # fused-vs-unfused step-time records key
                               # on these to be comparable across
                               # capture rounds; 'kind' disambiguates
                               # train vs train_bwd arms (ISSUE 12)
                               'fill', 'contexts', 'kind',
                               # the memory axis (ISSUE 9): per-stage
                               # peak HBM; None = stats-less backend,
                               # an explicit gap. 'temp_bytes' is the
                               # grad program's AOT temp allocation —
                               # the residual footprint the custom-VJP
                               # recompute backward cuts (ISSUE 12)
                               'peak_hbm_bytes', 'hbm_bytes_in_use',
                               'temp_bytes',
                               # serving-mesh load axes (ISSUE 13):
                               # p99-at-offered-load keyed by replica
                               # count, with shed rate, per-replica
                               # device fill, and the postwarm-compile
                               # check riding each arm record
                               'replicas', 'offered_rows_per_sec',
                               'p50_ms', 'p99_ms', 'shed_rate',
                               'per_replica_fill', 'dispatch_share',
                               'postwarm_compiles', 'host_cores',
                               # memoization-tier arms (ISSUE 16):
                               # cache-served vs live p99 keyed by the
                               # memo arm + Zipf shape, with the
                               # device-work-saved column the tier is
                               # judged on
                               # elastic-fleet transitions (ISSUE 18):
                               # scale-up/scale-down latency and the
                               # transition-vs-steady p99 from the
                               # stepped-load arm, plus the soak's
                               # elastic drill columns
                               'steady_p99_ms', 'up_p99_ms',
                               'down_p99_ms', 'scale_up_total',
                               'scale_down_total',
                               'reached_2_replicas',
                               'drained_to_1_replica',
                               'flap_freezes_total', 'retired_reason',
                               'rid',
                               'process_capacity_rows_per_sec_1r',
                               'memo', 'zipf_alpha', 'hit_rate',
                               'cache_p99_ms', 'live_p99_ms',
                               'semantic_hits', 'semantic_agreement',
                               'device_seconds_per_1k_requests',
                               # goodput plane (ISSUE 17): steady-state
                               # MFU / goodput fraction / badput shares
                               # of the real hot loop, the baseline a
                               # goodput regression flips against
                               'mfu', 'goodput_fraction',
                               'badput_compile_pct',
                               'badput_input_wait_pct',
                               'arithmetic_intensity',
                               'steps_per_window',
                               # quantized index tier (ISSUE 19):
                               # int8/pq arms keyed by 'kind' (above)
                               # — QPS rides 'value'; the bytes/vector
                               # and compression columns are the <=1/4-
                               # of-f16 acceptance, 'self_hit_at1' the
                               # insert arm's queryable-now check
                               'device_bytes_per_vector',
                               'f16_bytes_per_vector',
                               'compression_vs_f16', 'rerank',
                               'nprobe', 'rows', 'self_hit_at1',
                               'segments',
                               # scenario traffic plane (ISSUE 20):
                               # per-scenario x per-language replay
                               # quality, memo hit-rate, shed, and the
                               # retrieval-vs-softmax A/B columns
                               'scenario', 'language', 'exact_match',
                               'f1', 'memo_hit_rate', 'delivered',
                               'shed', 'blend_weight',
                               'softmax_exact', 'retrieval_exact',
                               'softmax_f1', 'retrieval_f1',
                               'availability_burn_share',
                               'p99_burn_share', 'admitted')}
            prefix = f'  [{stage}]' if stage else '  '
            flag = '' if not rc else f'  (rc={rc})'
            if label != 'STAGE FAILED':
                measured = True
            print(f'{prefix} {label}: {value} '
                  + ' '.join(f'{k}={v}' for k, v in extras.items()) + flag)
        if not measured:
            empty_rounds += 1
            print('  (no measurements this round — an explicit GAP in '
                  'the bench trajectory, not a skipped capture)')
    if empty_rounds:
        print(f'\n{empty_rounds}/{len(names)} round(s) produced no '
              'measurements (failed stages above).')
    print('\nDecision rule (PERF.md): a knob flips default only on a '
          '>=2% measured step-time win at the java14m config; ties keep '
          'reference-parity behavior.')


if __name__ == '__main__':
    main()
