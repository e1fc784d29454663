"""Embedding-index benchmark: exact vs IVF throughput/recall curves
(ISSUE 5 acceptance).

Measures, on a synthetic clustered corpus (a Gaussian mixture — code
vectors cluster by semantics; that is the paper's premise):

- ``naive``  — the no-index baseline: a per-query NumPy host loop
  (full dot-product scan + argsort), the shape of the reference's
  embedding-similarity demos.
- ``exact``  — the device-resident warm tier (index/exact.py): batched
  queries through the pre-compiled bucket ladder. The post-warmup XLA
  compile count is measured via the telemetry jit listener and emitted
  (must be 0 — asserted in tests/test_bench_smoke.py).
- ``ivf``    — the approximate tier (index/ivf.py): recall@10 vs the
  exact tier and throughput, swept over nprobe.

Prints one JSON line per metric:
  {"metric": "index_exact_queries_per_sec", "value": ...}
  {"metric": "index_naive_queries_per_sec", "value": ...}
  {"metric": "index_exact_speedup_vs_numpy", "value": ...,
   "postwarm_compiles": 0}
  {"metric": "index_ivf_recall_at10", "value": ..., "nprobe": ...}
  {"metric": "index_ivf_curve", "points": [{"nprobe", "recall",
   "queries_per_sec"}, ...]}
  {"metric": "index_quant_recall_at10", "kind": "int8"|"pq", ...}
  {"metric": "index_quant_queries_per_sec", "kind": ...,
   "device_bytes_per_vector": ..., "compression_vs_f16": ...,
   "postwarm_compiles": 0}
  {"metric": "index_quant_insert_vectors_per_sec", "rows": ...,
   "self_hit_at1": ..., "segments": ...}

BENCH_SMOKE=1 shrinks the corpus for a CPU smoke run (metrics carry a
``smoke`` field). On-chip runs go through benchmarks/capture_all.sh
(stage ``index``).

Usage: python benchmarks/bench_index.py [--vectors N] [--dim D]
       [--queries Q] [--clusters C] [--dtype float32|float16]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402


def synthesize_corpus(n: int, dim: int, n_centers: int, seed: int = 0,
                      spread: float = 0.15) -> np.ndarray:
    """Gaussian-mixture corpus: unit-norm centers, intra-cluster noise
    of NORM ~``spread`` (per-coordinate σ = spread/sqrt(dim), so cluster
    tightness is dimension-independent — at σ=0.15 per coordinate a
    384-dim 'cluster' would have noise norm ~3 and be isotropic)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_centers, dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, n_centers, n)
    sigma = spread / np.sqrt(dim)
    return (centers[assign]
            + sigma * rng.normal(size=(n, dim))).astype(np.float32)


def naive_numpy_search(vectors_normed: np.ndarray, queries: np.ndarray,
                       k: int):
    """The no-index host loop: one full scan + argsort PER QUERY (the
    reference demo shape). Deliberately per-query — this is the baseline
    the index replaces, not a tuned BLAS batch."""
    out = []
    for q in queries:
        qn = q / max(np.linalg.norm(q), 1e-12)
        scores = vectors_normed @ qn
        top = np.argsort(-scores, kind='stable')[:k]
        out.append(top)
    return np.stack(out)


def main() -> None:
    smoke = benchlib.smoke_requested()
    parser = argparse.ArgumentParser()
    parser.add_argument('--vectors', type=int,
                        default=6000 if smoke else 50000)
    parser.add_argument('--dim', type=int, default=32 if smoke else 384)
    parser.add_argument('--queries', type=int,
                        default=64 if smoke else 256)
    parser.add_argument('--centers', type=int,
                        default=60 if smoke else 500)
    parser.add_argument('--k', type=int, default=10)
    parser.add_argument('--dtype', default='float32',
                        choices=['float32', 'float16'])
    parser.add_argument('--reps', type=int, default=3,
                        help='repetitions per variant; best wall time '
                             'reported (host-jitter control)')
    parser.add_argument('--arms', default='all',
                        choices=['all', 'base', 'quant'],
                        help="'base' = naive/exact/ivf (capture stage "
                             "`index`), 'quant' = int8/pq + insert "
                             "(stage `index_quant`; the exact tier "
                             "still builds as the recall baseline)")
    args = parser.parse_args()
    base_arms = args.arms in ('all', 'base')
    quant_arms = args.arms in ('all', 'quant')

    from code2vec_tpu.index import store as store_lib
    from code2vec_tpu.index.exact import ExactIndex
    from code2vec_tpu.index.ivf import IVFIndex, measure_recall
    from code2vec_tpu.telemetry import core
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener

    def emit(record):
        if smoke:
            record['smoke'] = True
        print(json.dumps(record), flush=True)

    vectors = synthesize_corpus(args.vectors, args.dim, args.centers)
    rng = np.random.default_rng(1)
    queries = (vectors[rng.choice(args.vectors, args.queries)]
               + (0.05 / np.sqrt(args.dim))
               * rng.normal(size=(args.queries, args.dim))
               ).astype(np.float32)

    workdir = tempfile.mkdtemp(prefix='c2v_idxbench_')
    store = store_lib.build(os.path.join(workdir, 'bench.vecindex'),
                            [vectors], dtype=args.dtype, metric='cosine')

    # ---- naive numpy host loop
    if base_arms:
        normed = store.all_rows().astype(np.float32)
        naive_s = min(benchlib.bench_timer_wall(
            lambda: naive_numpy_search(normed, queries, args.k))
            for _ in range(args.reps))
        emit({'metric': 'index_naive_queries_per_sec',
              'value': args.queries / naive_s})

    # ---- exact tier, warm; compile counter must stay flat after warmup
    core.reset()
    core.enable()
    try:
        install_compile_listener()
        compiles = core.registry().counter('jit/compiles_total')
        index = ExactIndex(store).warmup(args.k)
        index.search(queries, args.k)  # one full-shape warm pass
        warm_compiles = compiles.value
        exact_s = min(benchlib.bench_timer_wall(
            lambda: index.search(queries, args.k))
            for _ in range(args.reps))
        postwarm = compiles.value - warm_compiles
    finally:
        core.disable()
        core.reset()
    emit({'metric': 'index_exact_queries_per_sec',
          'value': args.queries / exact_s, 'dtype': args.dtype,
          'vectors': args.vectors})
    if base_arms:
        emit({'metric': 'index_exact_speedup_vs_numpy',
              'value': naive_s / exact_s, 'postwarm_compiles': postwarm})

    # ---- IVF: recall + throughput across nprobe
    if base_arms:
        ivf = IVFIndex.build(store, persist=False)
        points = []
        nprobe = 1
        while nprobe <= min(64, ivf.n_clusters):
            recall = measure_recall(ivf, index, queries, k=args.k,
                                    nprobe=nprobe)
            ivf.search(queries, args.k, nprobe=nprobe)  # warm this shape
            ivf_s = min(benchlib.bench_timer_wall(
                lambda: ivf.search(queries, args.k, nprobe=nprobe))
                for _ in range(args.reps))
            points.append({'nprobe': nprobe, 'recall': round(recall, 4),
                           'queries_per_sec': args.queries / ivf_s})
            nprobe *= 2
        default_recall = measure_recall(ivf, index, queries, k=args.k)
        emit({'metric': 'index_ivf_recall_at10', 'value': default_recall,
              'nprobe': ivf.nprobe, 'clusters': ivf.n_clusters,
              'vectors': args.vectors})
        emit({'metric': 'index_ivf_curve', 'points': points})

    # ---- quantized tier: f16 (above) vs int8 vs PQ — QPS, recall@10
    # vs exact, device bytes/vector, zero post-warmup compiles
    if quant_arms:
        from code2vec_tpu.index.quant import QuantizedIVFIndex
        f16_bpv = 2 * args.dim
        quant = None
        for kind in ('int8', 'pq'):
            core.reset()
            core.enable()
            try:
                install_compile_listener()
                compiles = core.registry().counter('jit/compiles_total')
                quant = QuantizedIVFIndex.build(store, kind=kind)
                quant.warmup(args.k)
                quant.search(queries, args.k)  # full-shape warm pass
                warm_compiles = compiles.value
                quant_s = min(benchlib.bench_timer_wall(
                    lambda: quant.search(queries, args.k))
                    for _ in range(args.reps))
                postwarm = compiles.value - warm_compiles
            finally:
                core.disable()
                core.reset()
            recall = measure_recall(quant, index, queries, k=args.k)
            emit({'metric': 'index_quant_recall_at10', 'kind': kind,
                  'value': recall, 'rerank': quant.rerank,
                  'vectors': args.vectors})
            emit({'metric': 'index_quant_queries_per_sec', 'kind': kind,
                  'value': args.queries / quant_s,
                  'postwarm_compiles': postwarm,
                  'device_bytes_per_vector': quant.bytes_per_vector,
                  'f16_bytes_per_vector': f16_bpv,
                  'compression_vs_f16': f16_bpv / quant.bytes_per_vector})

        # ---- live-insert arm (on the PQ index from the last loop
        # turn): encode + page + device refresh throughput, and the
        # inserted rows must be queryable immediately (no rebuild)
        insert_rows = 512 if smoke else 8192
        extra = synthesize_corpus(insert_rows, args.dim, args.centers,
                                  seed=7)
        t0 = time.perf_counter()
        row_ids = quant.insert(extra)
        insert_s = time.perf_counter() - t0
        probe = extra[:min(32, insert_rows)].astype(np.float32)
        _scores, got = quant.search(probe, 1)
        hit = float(np.mean([int(got[i, 0]) == int(row_ids[i])
                             for i in range(probe.shape[0])]))
        emit({'metric': 'index_quant_insert_vectors_per_sec',
              'kind': 'pq', 'value': insert_rows / insert_s,
              'rows': insert_rows, 'self_hit_at1': hit,
              'segments': quant.segment_count})

    # per-stage peak HBM (ISSUE 9): covers the exact store residency
    # AND the IVF cluster-sorted copy on this backend
    emit({'metric': 'index_peak_hbm_bytes',
          **benchlib.device_memory_record()})


if __name__ == '__main__':
    main()
