"""Quantized IVF tier tests (ISSUE 19): int8/PQ codecs, the warm
LUT-gather program (zero post-warmup compiles), exact re-rank parity,
the HBM budget gate on attach AND append, incremental inserts
(queryable without rebuild, versioned segment sidecars, reopen), and
the append-then-compact bit-for-rank property suite — empty segments,
duplicate vectors, and inserts that land mid-compaction included."""
import gc
import os
import threading

import numpy as np
import pytest

from code2vec_tpu.index import store as store_lib
from code2vec_tpu.index.exact import ExactIndex
from code2vec_tpu.index.ivf import measure_recall
from code2vec_tpu.index.quant import (QuantizedIVFIndex, encode_int8,
                                      resolve_pq_m, train_int8)
from code2vec_tpu.telemetry import core
from code2vec_tpu.telemetry import memory
from code2vec_tpu.telemetry.memory import MemoryBudgetExceeded

from test_index import clustered_corpus, reference_search


@pytest.fixture(autouse=True)
def fresh_state():
    memory.reset()
    core.reset()
    core.disable()
    yield
    memory.reset()
    core.reset()
    core.disable()


def small_store(tmp_path, n=800, dim=16, centers=12, seed=0,
                metric='cosine', labels=True, name='q.vecindex'):
    vecs = clustered_corpus(n, dim, centers=centers, seed=seed)
    return store_lib.build(
        str(tmp_path / name), [vecs], metric=metric,
        labels=(['m%d' % i for i in range(n)] if labels else None)), vecs


# ------------------------------------------------------------- codecs
def test_resolve_pq_m_divides_dim():
    assert resolve_pq_m(64) == 16
    assert resolve_pq_m(64, 32) == 32
    assert resolve_pq_m(30, 8) == 6     # clamped down to a divisor
    assert resolve_pq_m(7) == 1


def test_int8_codec_round_trip_error_bounded():
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(512, 24)).astype(np.float32)
    scale = train_int8(vecs)
    codes = encode_int8(vecs, scale)
    assert codes.dtype == np.int8
    recon = codes.astype(np.float32) * scale[None, :]
    # symmetric per-dim quantization: error under half a step
    assert np.abs(recon - vecs).max() <= (scale.max() / 2) + 1e-6


# --------------------------------------------- search parity + recall
@pytest.mark.parametrize('kind', ['int8', 'pq'])
def test_full_probe_full_rerank_matches_reference(tmp_path, kind):
    """With every list probed and re-rank covering the candidate set,
    the quantized tier is bit-for-rank the reference: quantization only
    ORDERS the candidate funnel, the exact re-rank decides."""
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind=kind, seed=0,
                                    rerank=10 ** 6)
    queries = vecs[::97][:12]
    values, ids = index.search(queries, 10, nprobe=index.n_clusters)
    ref_values, ref_ids = reference_search(vecs, queries, 10)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_allclose(values, ref_values, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize('kind', ['int8', 'pq'])
def test_rerank_recovers_recall_over_quantized_order(tmp_path, kind):
    store, vecs = small_store(tmp_path, n=2000, dim=32, centers=24)
    exact = ExactIndex(store)
    rng = np.random.default_rng(3)
    queries = (vecs[rng.choice(2000, 32)]
               + 0.01 * rng.normal(size=(32, 32))).astype(np.float32)
    index = QuantizedIVFIndex.build(store, kind=kind, seed=0, rerank=0)
    bare = measure_recall(index, exact, queries, k=10)
    index.rerank = 128
    reranked = measure_recall(index, exact, queries, k=10)
    assert reranked >= bare
    assert reranked >= 0.9, (bare, reranked)


def test_pq_device_bytes_per_vector_quarter_of_f16(tmp_path):
    store, _vecs = small_store(tmp_path, dim=16)
    index = QuantizedIVFIndex.build(store, kind='pq', seed=0)
    assert index.bytes_per_vector * 4 <= 2 * store.dim
    int8_index = QuantizedIVFIndex.build(store, kind='int8', seed=0)
    assert int8_index.bytes_per_vector * 2 <= 2 * store.dim


def test_zero_postwarm_compiles_across_query_buckets(tmp_path):
    from code2vec_tpu.telemetry.jit_tracker import \
        install_compile_listener
    store, vecs = small_store(tmp_path, n=600)
    index = QuantizedIVFIndex.build(store, kind='pq', seed=0)
    core.reset()
    core.enable()
    try:
        assert install_compile_listener()
        compiles = core.registry().counter('jit/compiles_total')
        # warm: full probe (capacity rung is query-independent there)
        # plus the default-nprobe traffic we will repeat
        for bucket in (1, 8, 64):
            index.search(vecs[:bucket], 10, nprobe=index.n_clusters)
            index.search(vecs[7:7 + bucket], 10)
        warm = compiles.value
        for bucket in (1, 8, 64):
            index.search(vecs[200:200 + bucket], 10,
                         nprobe=index.n_clusters)
            index.search(vecs[7:7 + bucket], 10)
        assert compiles.value - warm == 0, (
            '%d XLA compiles on the post-warmup query path'
            % (compiles.value - warm))
    finally:
        core.disable()


# ------------------------------------------------------- budget gates
def test_budget_refused_attach_is_typed_with_zero_allocation(tmp_path):
    store, _vecs = small_store(tmp_path)
    QuantizedIVFIndex.build(store, kind='int8', seed=0)  # sidecars
    gc.collect()
    memory.configure(budget_bytes=64, dump_dir=str(tmp_path))
    before = memory.backend_memory()['live_bytes']
    with pytest.raises(MemoryBudgetExceeded, match='index attach'):
        QuantizedIVFIndex(store_lib.VectorStore(store.path))
    gc.collect()
    assert memory.backend_memory()['live_bytes'] == before
    assert memory.ledger().bucket_bytes('index') == 0


def test_budget_refused_append_keeps_index_serving(tmp_path):
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind='int8', seed=0)
    memory.configure(
        budget_bytes=memory.ledger().attributed_bytes() + 8,
        dump_dir=str(tmp_path))
    with pytest.raises(MemoryBudgetExceeded, match='append segment'):
        index.insert(vecs[:4])
    memory.configure(budget_bytes=0)
    values, ids = index.search(vecs[:2], 5)
    assert (ids[:, 0] >= 0).all()


def test_ledger_keys_index_bucket_per_segment(tmp_path):
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind='pq', seed=0,
                                    segment_rows=8, compact_segments=0)
    index.insert(vecs[:20])     # 3 segments (8 + 8 + 4)
    snapshot = memory.ledger().snapshot(reconcile=False)
    keys = [entry['key'] for entry
            in snapshot['buckets']['index']['entries']]
    assert len([key for key in keys if ':seg0' in key]) == 3
    assert any(key.endswith(':base') for key in keys)


# ------------------------------------------------- inserts + segments
def test_insert_queryable_without_rebuild_and_labels(tmp_path):
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind='int8', seed=0)
    new = (vecs[37:40] + 0.001).astype(np.float32)
    ids = index.insert(new, labels=['n0', 'n1', 'n2'])
    assert ids.tolist() == [800, 801, 802]
    assert index.count == 803
    _values, got = index.search(new, 5)
    for j in range(3):
        assert ids[j] in got[j]
    assert index.labels[-3:].tolist() == ['n0', 'n1', 'n2']


def test_reopen_serves_uncompacted_segments(tmp_path):
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind='pq', seed=0)
    ids = index.insert(vecs[11:14] + 0.002)
    reopened = QuantizedIVFIndex(store_lib.VectorStore(store.path))
    assert reopened.segment_count == 1
    assert reopened.count == index.count
    values_a, ids_a = index.search(vecs[:8], 10)
    values_b, ids_b = reopened.search(vecs[:8], 10)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_allclose(values_a, values_b, rtol=1e-6)
    assert ids[0] in reopened.search(vecs[11:12] + 0.002, 5)[1][0]


def test_auto_compaction_triggers_on_segment_count(tmp_path):
    store, vecs = small_store(tmp_path)
    index = QuantizedIVFIndex.build(store, kind='int8', seed=0,
                                    segment_rows=4, compact_segments=2)
    index.insert(vecs[:4] + 0.001)
    index.insert(vecs[4:8] + 0.001)
    assert index.segment_count == 2 and index.compactions == 0
    index.insert(vecs[8:12] + 0.001)     # 3rd segment -> compact
    assert index.segment_count == 0
    assert index.compactions == 1
    assert index.store.count == 812
    assert index.version == 1


# ------------------------------------- compaction parity (property)
def _search_all(index, queries, k):
    """Full-probe, full-rerank search: candidate order is decided by
    the exact re-rank, so results are bit-for-rank reproducible."""
    index.rerank = 10 ** 6
    return index.search(queries, k, nprobe=index.n_clusters)


@pytest.mark.parametrize('kind', ['int8', 'pq'])
def test_append_then_compact_bit_for_rank_vs_fresh_build(
        tmp_path, kind):
    """ISSUE 19 satellite: append-segments-then-compaction must be
    bit-for-rank identical (under exact re-rank) to a fresh build over
    the same corpus — including empty segments and duplicate
    vectors."""
    base = clustered_corpus(600, 16, centers=10, seed=4)
    extra1 = clustered_corpus(40, 16, centers=10, seed=5)
    dupes = base[100:110].copy()           # exact duplicates
    extra2 = clustered_corpus(25, 16, centers=10, seed=6)
    store, _ = small_store(tmp_path, n=600, dim=16, centers=10, seed=4,
                           labels=False)
    index = QuantizedIVFIndex.build(store, kind=kind, seed=0,
                                    segment_rows=16, compact_segments=0)
    index.insert(extra1)
    index.insert(np.empty((0, 16), np.float32))   # empty segment
    index.insert(dupes)
    index.insert(extra2)
    queries = np.concatenate([base[::151][:4], extra1[:2], dupes[:2]])
    pre_values, pre_ids = _search_all(index, queries, 10)
    index.compact()
    post_values, post_ids = _search_all(index, queries, 10)
    np.testing.assert_array_equal(pre_ids, post_ids)
    np.testing.assert_allclose(pre_values, post_values, rtol=1e-6)
    # fresh build over the SAME corpus in the same row order
    full = np.concatenate([base, extra1, dupes, extra2])
    fresh_store = store_lib.build(str(tmp_path / 'fresh.vecindex'),
                                  [full], labels=None)
    fresh = QuantizedIVFIndex.build(fresh_store, kind=kind, seed=0)
    fresh_values, fresh_ids = _search_all(fresh, queries, 10)
    np.testing.assert_array_equal(post_ids, fresh_ids)
    np.testing.assert_allclose(post_values, fresh_values, rtol=1e-6)


def test_insert_landing_mid_compaction_is_not_lost(tmp_path):
    """Inserts racing a compaction serialize behind the index lock:
    the late batch lands as a fresh segment against the compacted base
    and stays queryable."""
    store, vecs = small_store(tmp_path, n=400)
    index = QuantizedIVFIndex.build(store, kind='int8', seed=0,
                                    compact_segments=0)
    index.insert(vecs[:6] + 0.001)
    racer_ids = []
    started = threading.Event()

    def racer():
        started.wait()
        racer_ids.append(index.insert(vecs[6:9] + 0.002))

    thread = threading.Thread(target=racer)
    thread.start()
    started.set()
    index.compact()
    thread.join()
    assert len(racer_ids) == 1
    _values, got = index.search(vecs[6:9] + 0.002, 5)
    for j, rid in enumerate(racer_ids[0]):
        assert rid in got[j]
    # every row accounted for: base 400 + first batch 6 + racer 3
    assert index.count == 409
    index.compact()
    assert index.store.count == 409
    _values2, got2 = index.search(vecs[6:9] + 0.002, 5)
    np.testing.assert_array_equal(got, got2)


# ------------------------------------------------------ 50k acceptance
@pytest.mark.parametrize('kind', ['int8', 'pq'])
def test_quant_recall_at_default_nprobe_50k(tmp_path, kind):
    """ISSUE 19 acceptance: recall@10 >= 0.95 vs exact at
    the default nprobe with the default re-rank on the 50k clustered
    corpus, at <= 1/2 (int8) / <= 1/4 (pq) the device bytes/vector of
    f16."""
    vecs = clustered_corpus(50000, 64, centers=500, seed=11)
    store = store_lib.build(str(tmp_path / 'big.vecindex'), [vecs])
    exact = ExactIndex(store)
    index = QuantizedIVFIndex.build(store, kind=kind, seed=0)
    rng = np.random.default_rng(12)
    queries = (vecs[rng.choice(50000, 128)]
               + 0.01 * rng.normal(size=(128, 64))).astype(np.float32)
    recall = measure_recall(index, exact, queries, k=10)
    assert recall >= 0.95, recall
    ceiling = 2 * store.dim // (2 if kind == 'int8' else 4)
    assert index.bytes_per_vector <= ceiling


# ----------------------------------------------------- store plumbing
def test_store_take_gathers_across_shards(tmp_path):
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(700, 8)).astype(np.float32)
    store = store_lib.build(str(tmp_path / 's.vecindex'), [vecs],
                            metric='dot', shard_rows=256)
    ids = np.array([0, 255, 256, 511, 512, 699, 3])
    np.testing.assert_allclose(store.take(ids), vecs[ids], rtol=1e-6)
    with pytest.raises(IndexError):
        store.take(np.array([700]))


def test_store_append_rows_extends_shards_and_labels(tmp_path):
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(300, 8)).astype(np.float32)
    store = store_lib.build(str(tmp_path / 's.vecindex'), [vecs],
                            metric='cosine', shard_rows=256,
                            labels=['m%d' % i for i in range(300)])
    extra = rng.normal(size=(10, 8)).astype(np.float32)
    start, end = store.append_rows(extra, labels=['x%d' % i
                                                  for i in range(10)])
    assert (start, end) == (300, 310)
    assert store.count == 310
    # appended rows normalized like build() (cosine store)
    np.testing.assert_allclose(
        store.take(np.arange(300, 310)),
        store_lib.normalize_rows(extra), rtol=1e-5)
    assert store.labels[-1] == 'x9'
    # a reopened view sees the grown store
    reopened = store_lib.VectorStore(store.path)
    assert reopened.count == 310
    assert reopened.labels[305] == 'x5'
    # unlabeled store refuses labels (would mis-align)
    bare = store_lib.build(str(tmp_path / 'b.vecindex'), [vecs],
                           metric='dot')
    with pytest.raises(ValueError, match='labels'):
        bare.append_rows(extra, labels=['z'] * 10)
