"""Serving benchmark: naive per-request ``model.predict`` loop vs the
micro-batching engine (serving/engine.py) on a synthetic concurrent
request stream.

Measures, per variant, requests/sec + examples/sec throughput and
per-request latency p50/p99:

- ``naive``  — the reference REPL shape: one ``model.predict`` per
  request, sequential (warmed first, so it is not billed its compiles).
- ``engine`` — open-loop by default (the "heavy traffic" regime: the
  whole request stream is in flight at once and the dispatcher
  coalesces it into bucket-ladder batches); ``--closed-loop`` instead
  runs ``--clients`` concurrent client threads each waiting for its
  result before the next submit, which bounds in-flight requests and
  probes the latency end of the trade.

Prints one JSON line per metric:
  {"metric": "serving_requests_per_sec", "variant": ..., "value": ...}
  {"metric": "serving_latency_ms", "variant": ..., "p50": ..., "p99": ...}
  {"metric": "serving_speedup", "value": ...}

BENCH_SMOKE=1 shrinks shapes and request counts for a CPU smoke run
(rename-proofed: smoke metrics carry a ``smoke`` field). On-chip runs go
through benchmarks/capture_all.sh (stage ``serving``).

Usage: python benchmarks/bench_serving.py [--requests N] [--clients K]
       [--tokens T] [--max-delay-ms MS] [--tier topk|attention|full]
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402


def synthesize_dataset(prefix: str, rows: int, contexts: int,
                       n_tokens: int, n_paths: int, n_labels: int,
                       seed: int = 0):
    """Ragged java14m-shaped corpus + dict (bench_host_pipeline shape).
    Returns the raw lines — the request stream draws from them."""
    import pickle
    rng = random.Random(seed)
    tokens = [f'tok{i}' for i in range(n_tokens)]
    paths = [str(rng.getrandbits(31)) for _ in range(n_paths)]
    labels = [f'do|thing|{i}' for i in range(n_labels)]
    lines = []
    for _ in range(rows):
        n = rng.randint(max(1, contexts // 8), max(2, contexts // 2))
        ctxs = ' '.join(
            f'{rng.choice(tokens)},{rng.choice(paths)},{rng.choice(tokens)}'
            for _ in range(n))
        lines.append(f'{rng.choice(labels)} {ctxs}')
    with open(prefix + '.train.c2v', 'w') as f:
        f.write('\n'.join(lines) + '\n')
    with open(prefix + '.dict.c2v', 'wb') as f:
        pickle.dump({t: 10 for t in tokens}, f)
        pickle.dump({p: 10 for p in paths}, f)
        pickle.dump({label: 10 for label in labels}, f)
        pickle.dump(rows, f)
    return lines


def make_requests(lines, n_requests: int, max_lines: int, seed: int = 1):
    """Ragged 1..max_lines requests drawn from the corpus lines."""
    rng = random.Random(seed)
    return [[rng.choice(lines) for _ in range(rng.randint(1, max_lines))]
            for _ in range(n_requests)]


def percentiles(latencies_s):
    lat_ms = np.asarray(latencies_s) * 1e3
    return (float(np.percentile(lat_ms, 50)),
            float(np.percentile(lat_ms, 99)))


def run_naive(model, requests):
    model.predict(requests[0])  # warm (one bucket covers the stream)
    latencies = []
    t0 = time.perf_counter()
    for lines in requests:
        r0 = time.perf_counter()
        model.predict(lines)
        latencies.append(time.perf_counter() - r0)
    return time.perf_counter() - t0, latencies


def run_engine_open_loop(model, requests, tier: str, max_delay_ms: float,
                         **engine_kw):
    """Submit the whole stream up front; per-request latency is
    submit -> future-done (a done-callback stamps the clock)."""
    done_at = [0.0] * len(requests)
    # queue_bound=-1: the open-loop regime deliberately holds the WHOLE
    # stream in flight; the default (auto) admission bound would shed it
    with model.serving_engine(tiers=(tier,), max_delay_ms=max_delay_ms,
                              queue_bound=-1, **engine_kw) as engine:
        t0 = time.perf_counter()
        submit_at = []
        futures = []
        for idx, lines in enumerate(requests):
            submit_at.append(time.perf_counter())
            future = engine.submit(lines, tier=tier)
            future.add_done_callback(
                lambda _f, i=idx: done_at.__setitem__(
                    i, time.perf_counter()))
            futures.append(future)
        for future in futures:
            future.result(timeout=600)
        wall = time.perf_counter() - t0
        stats = engine.stats()
    latencies = [done_at[i] - submit_at[i] for i in range(len(requests))]
    return wall, latencies, stats


def run_engine_closed_loop(model, requests, tier: str, clients: int,
                           max_delay_ms: float, **engine_kw):
    latencies = [[] for _ in range(clients)]
    with model.serving_engine(tiers=(tier,), max_delay_ms=max_delay_ms,
                              **engine_kw) as engine:
        def client(idx):
            # closed-loop client: wait for each result before the next
            # submit, so `clients` bounds the in-flight requests
            for lines in requests[idx::clients]:
                r0 = time.perf_counter()
                engine.predict(lines, tier=tier, timeout=600)
                latencies[idx].append(time.perf_counter() - r0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        wall = time.perf_counter() - t0
        stats = engine.stats()
    return wall, [lat for per in latencies for lat in per], stats


def main() -> None:
    smoke = benchlib.smoke_requested()
    parser = argparse.ArgumentParser()
    parser.add_argument('--requests', type=int,
                        default=128 if smoke else 512)
    parser.add_argument('--clients', type=int, default=8)
    parser.add_argument('--max-request-lines', type=int,
                        default=4 if smoke else 8)
    parser.add_argument('--rows', type=int, default=200 if smoke else 2000)
    # smoke keeps contexts tiny so the CPU run stays in the regime the
    # engine targets (per-dispatch overhead >> per-row compute — on TPU
    # that is true at full java14m shapes; on CPU only at small ones)
    parser.add_argument('--contexts', type=int,
                        default=6 if smoke else 200)
    parser.add_argument('--tokens', type=int,
                        default=500 if smoke else 20000)
    parser.add_argument('--paths', type=int,
                        default=500 if smoke else 30000)
    parser.add_argument('--labels', type=int,
                        default=100 if smoke else 5000)
    parser.add_argument('--max-delay-ms', type=float, default=5.0)
    parser.add_argument('--tier', default='topk',
                        choices=['topk', 'attention', 'full'])
    # finer than the Config default ladder: open-loop streams land ragged
    # row totals, and fill rate (compute waste) is what the bench probes
    parser.add_argument('--buckets', default='8,32,128,512')
    parser.add_argument('--closed-loop', action='store_true',
                        help='bound in-flight requests to --clients '
                             'closed-loop client threads instead of the '
                             'open-loop full-stream default')
    parser.add_argument('--reps', type=int, default=3,
                        help='repetitions per variant; the best wall '
                             'time is reported (host-jitter control)')
    parser.add_argument('--trace-dir', default=None,
                        help='where the full-capture traced arm writes '
                             'its span log (default: a temp dir); point '
                             'it somewhere durable to keep the spans '
                             'for scripts/latency_report.py')
    args = parser.parse_args()

    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel

    workdir = tempfile.mkdtemp(prefix='c2v_servebench_')
    prefix = os.path.join(workdir, 'synth')
    lines = synthesize_dataset(prefix, args.rows, args.contexts,
                               args.tokens, args.paths, args.labels)
    config = Config(
        TRAIN_DATA_PATH_PREFIX=prefix, DL_FRAMEWORK='jax',
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        MAX_CONTEXTS=args.contexts, SERVING_BATCH_BUCKETS=args.buckets,
        SERVING_MAX_DELAY_MS=args.max_delay_ms)
    model = Code2VecModel(config)
    requests = make_requests(lines, args.requests, args.max_request_lines)
    n_lines = sum(len(r) for r in requests)

    def emit(record):
        if smoke:
            record['smoke'] = True
        print(json.dumps(record), flush=True)

    naive_s, naive_lat = min(
        (run_naive(model, requests) for _ in range(args.reps)),
        key=lambda pair: pair[0])
    p50, p99 = percentiles(naive_lat)
    emit({'metric': 'serving_requests_per_sec', 'variant': 'naive',
          'value': args.requests / naive_s})
    emit({'metric': 'serving_examples_per_sec', 'variant': 'naive',
          'value': n_lines / naive_s})
    emit({'metric': 'serving_latency_ms', 'variant': 'naive',
          'p50': p50, 'p99': p99})

    if args.closed_loop:
        runs = [run_engine_closed_loop(model, requests, args.tier,
                                       args.clients, args.max_delay_ms)
                for _ in range(args.reps)]
    else:
        runs = [run_engine_open_loop(model, requests, args.tier,
                                     args.max_delay_ms)
                for _ in range(args.reps)]
    engine_s, engine_lat, stats = min(runs, key=lambda rec: rec[0])
    p50, p99 = percentiles(engine_lat)
    emit({'metric': 'serving_requests_per_sec', 'variant': 'engine',
          'value': args.requests / engine_s, 'tier': args.tier,
          'mode': 'closed' if args.closed_loop else 'open',
          'batches': stats['batches_total'],
          'batch_fill_rate': stats['batch_fill_rate']})
    emit({'metric': 'serving_examples_per_sec', 'variant': 'engine',
          'value': n_lines / engine_s})
    emit({'metric': 'serving_latency_ms', 'variant': 'engine',
          'p50': p50, 'p99': p99})
    emit({'metric': 'serving_speedup', 'value': naive_s / engine_s})
    # per-stage peak HBM (ISSUE 9): measured after both arms, so the
    # peak covers naive AND engine serving on this backend
    emit({'metric': 'serving_peak_hbm_bytes',
          **benchlib.device_memory_record()})

    # ---- tracing overhead at the DEFAULT sample rate (ISSUE 8): the
    # engine arm above ran with the config default (tracer armed,
    # memory-only); an explicit rate-0 arm isolates the tracing cost
    runner = run_engine_closed_loop if args.closed_loop \
        else run_engine_open_loop
    extra = (args.clients,) if args.closed_loop else ()
    off_runs = [runner(model, requests, args.tier, *extra,
                       args.max_delay_ms, tracing_sample_rate=0.0)
                for _ in range(args.reps)]
    off_s = min(rec[0] for rec in off_runs)
    emit({'metric': 'serving_requests_per_sec', 'variant': 'engine_untraced',
          'value': args.requests / off_s})
    emit({'metric': 'serving_tracing_overhead_pct',
          'value': round((engine_s - off_s) / off_s * 100, 2),
          'note': 'engine wall at default TRACING_SAMPLE_RATE vs 0'})

    # ---- full-capture traced arm: span-log-derived latency attribution
    # (every request retained; scripts/latency_report.py reads the same
    # file offline)
    from code2vec_tpu.telemetry.tracing import Tracer
    scripts_dir = os.path.join(REPO, 'scripts')
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    import latency_report
    trace_dir = args.trace_dir or os.path.join(workdir, 'trace')
    stale = os.path.join(trace_dir, 'spans.jsonl')
    if os.path.exists(stale):
        # the tracer appends; a reused --trace-dir must not blend a
        # prior run's spans into this run's percentiles
        os.remove(stale)
    tracer = Tracer(trace_dir, sample_rate=1.0,
                    flight_traces=args.requests)
    traced_s, _lat, _stats = runner(model, requests, args.tier, *extra,
                                    args.max_delay_ms, tracer=tracer)
    spans_path = os.path.join(trace_dir, 'spans.jsonl')
    traces = latency_report.group_traces(
        latency_report.load_spans(spans_path))
    roots = sorted(
        float(entry['root'].get('dur_ms', 0.0))
        for entry in traces.values() if entry['root'] is not None)
    emit({'metric': 'serving_latency_ms', 'variant': 'engine_spans',
          'p50': latency_report.percentile(roots, 0.50),
          'p99': latency_report.percentile(roots, 0.99),
          'traces': len(roots), 'spans_path': spans_path})
    per_phase = {}
    for (phase, _tier, _bucket, _replica), durs in \
            latency_report.phase_rows(traces).items():
        per_phase.setdefault(phase, []).extend(durs)
    for phase, durs in sorted(per_phase.items()):
        durs.sort()
        emit({'metric': 'serving_phase_ms', 'phase': phase,
              'count': len(durs),
              'p50': round(latency_report.percentile(durs, 0.50), 3),
              'p99': round(latency_report.percentile(durs, 0.99), 3)})


if __name__ == '__main__':
    main()
