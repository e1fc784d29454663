"""Embedding-index correctness (ISSUE 5 tentpole): store round-trips,
exact k-NN bit-for-rank against a NumPy reference (mesh-sharded AND
streamed host-merge tiers, random and tie-heavy inputs, k > n_shard),
IVF recall, and the float16 store parity satellite."""
import os

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.index import store as store_lib
from code2vec_tpu.index.exact import ExactIndex, search_streamed
from code2vec_tpu.index.ivf import IVFIndex, measure_recall
from code2vec_tpu.parallel import mesh as mesh_lib


def reference_search(vectors, queries, k, metric='cosine'):
    """NumPy ground truth: float32 scores, ties by lowest index."""
    vectors = np.asarray(vectors, np.float32)
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    if metric == 'cosine':
        vectors = store_lib.normalize_rows(vectors)
        queries = store_lib.normalize_rows(queries)
    scores = (queries @ vectors.T).astype(np.float32)
    idx = np.argsort(-scores, axis=-1, kind='stable')[:, :k]
    return np.take_along_axis(scores, idx, axis=-1), idx


def clustered_corpus(n, dim, centers, seed=0, spread=0.15):
    """Gaussian mixture with noise NORM ~spread (per-coordinate σ
    scaled by 1/sqrt(dim)) — cluster tightness independent of dim."""
    rng = np.random.default_rng(seed)
    c = rng.normal(size=(centers, dim))
    c /= np.linalg.norm(c, axis=1, keepdims=True)
    assign = rng.integers(0, centers, n)
    return (c[assign]
            + (spread / np.sqrt(dim)) * rng.normal(size=(n, dim))
            ).astype(np.float32)


# ------------------------------------------------------------------ store
def test_store_round_trip_with_labels_and_shards(tmp_path):
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(700, 16)).astype(np.float32)
    labels = ['m%d' % i for i in range(700)]
    store = store_lib.build(str(tmp_path / 's.vecindex'),
                            [vecs[:300], vecs[300:]], metric='dot',
                            labels=labels, shard_rows=256)
    assert (store.count, store.dim) == (700, 16)
    assert store.shards == [256, 256, 188]
    assert not store.normalized
    np.testing.assert_array_equal(store.all_rows(), vecs)
    assert list(store.labels[:2]) == ['m0', 'm1']
    # reopen from disk
    reopened = store_lib.VectorStore(store.path)
    np.testing.assert_array_equal(reopened.all_rows(), vecs)
    assert reopened.label_of(699) == 'm699'


def test_store_cosine_normalizes_and_float16_halves_bytes(tmp_path):
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(64, 32)).astype(np.float32)
    vecs[7] = 0.0  # zero row must survive normalization as zero
    s32 = store_lib.build(str(tmp_path / 'f32.vecindex'), [vecs])
    s16 = store_lib.build(str(tmp_path / 'f16.vecindex'), [vecs],
                          dtype='float16')
    assert s32.normalized and s16.normalized
    norms = np.linalg.norm(np.asarray(s32.all_rows(), np.float32), axis=1)
    assert np.allclose(np.delete(norms, 7), 1.0, atol=1e-5)
    assert norms[7] == 0.0
    bytes32 = os.path.getsize(os.path.join(s32.path, 'shard_00000.bin'))
    bytes16 = os.path.getsize(os.path.join(s16.path, 'shard_00000.bin'))
    assert bytes16 * 2 == bytes32


def test_store_builders_from_text_and_word2vec(tmp_path):
    rng = np.random.default_rng(2)
    vecs = rng.normal(size=(20, 8)).astype(np.float32)
    vectors_path = tmp_path / 'corpus.c2v.vectors'
    with open(vectors_path, 'w') as f:
        for vec in vecs:
            f.write(' '.join(map(str, vec)) + '\n')
    st = store_lib.build_from_vectors_file(str(vectors_path),
                                           metric='dot')
    assert st.count == 20 and st.path == str(vectors_path) + '.vecindex'
    np.testing.assert_allclose(np.asarray(st.all_rows()), vecs,
                               rtol=1e-6)
    # word2vec text (--export_vocab_vectors / --save_word2v output)
    w2v_path = tmp_path / 'targets.txt'
    with open(w2v_path, 'w') as f:
        f.write('20 8\n')
        for i, vec in enumerate(vecs):
            f.write('word|%d ' % i + ' '.join(map(str, vec)) + '\n')
    sw = store_lib.build_from_word2vec(str(w2v_path), metric='dot')
    assert sw.count == 20
    assert sw.label_of(3) == 'word|3'
    np.testing.assert_allclose(np.asarray(sw.all_rows()), vecs,
                               rtol=1e-6)


def test_store_rejects_misaligned_labels(tmp_path):
    with pytest.raises(ValueError, match='label'):
        store_lib.build(str(tmp_path / 'bad.vecindex'),
                        [np.ones((4, 3), np.float32)], labels=['a', 'b'])


# ------------------------------------------------------------------ exact
@pytest.mark.parametrize('metric', ['cosine', 'dot'])
def test_exact_matches_numpy_bit_for_rank(tmp_path, metric):
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(500, 24)).astype(np.float32)
    queries = rng.normal(size=(13, 24)).astype(np.float32)
    store = store_lib.build(str(tmp_path / ('%s.vecindex' % metric)),
                            [vecs], metric=metric)
    _want_v, want_i = reference_search(vecs, queries, 10, metric)
    # device-resident, sharded over the 8-device test mesh's data axis
    mesh = mesh_lib.create_mesh(Config(MODEL_LOAD_PATH='unused'))
    got_v, got_i = ExactIndex(store, mesh=mesh).warmup(10).search(
        queries, 10)
    assert np.array_equal(got_i, want_i)
    # unsharded twin agrees too
    got_v1, got_i1 = ExactIndex(store).search(queries, 10)
    assert np.array_equal(got_i1, want_i)
    np.testing.assert_allclose(got_v, got_v1, atol=2e-6)


def test_exact_breaks_ties_by_lowest_index(tmp_path):
    # integer grid vectors: EXACT score ties across many rows
    rng = np.random.default_rng(4)
    vecs = rng.integers(0, 2, (96, 8)).astype(np.float32)
    store = store_lib.build(str(tmp_path / 'ties.vecindex'), [vecs],
                            metric='dot')
    queries = rng.integers(0, 2, (6, 8)).astype(np.float32)
    _v, want_i = reference_search(vecs, queries, 12, 'dot')
    _v, got_i = ExactIndex(store).search(queries, 12)
    assert np.array_equal(got_i, want_i)
    _v, streamed_i = search_streamed(store, queries, 12)
    assert np.array_equal(streamed_i, want_i)


def test_streamed_matches_device_including_k_above_shard(tmp_path):
    """The host-merge tier: shards of 40 rows with k=64 > n_shard —
    the −inf/−1 sentinel path — must stay bit-for-rank with the
    device-resident tier and the NumPy reference."""
    rng = np.random.default_rng(5)
    vecs = rng.normal(size=(150, 12)).astype(np.float32)
    store = store_lib.build(str(tmp_path / 'st.vecindex'), [vecs],
                            shard_rows=40)
    queries = rng.normal(size=(3, 12)).astype(np.float32)
    _want_v, want_i = reference_search(vecs, queries, 64)
    got_v, got_i = ExactIndex(store).search(queries, 64)
    streamed_v, streamed_i = search_streamed(store, queries, 64)
    assert np.array_equal(got_i, want_i)
    assert np.array_equal(streamed_i, want_i)
    np.testing.assert_allclose(streamed_v, got_v, atol=2e-6)


def test_exact_caps_k_at_store_size(tmp_path):
    vecs = np.eye(5, dtype=np.float32)
    store = store_lib.build(str(tmp_path / 'tiny.vecindex'), [vecs],
                            metric='dot')
    values, indices = ExactIndex(store).search(vecs[0], 50)
    assert indices.shape == (1, 5)
    assert indices[0, 0] == 0 and values[0, 0] == 1.0


# -------------------------------------------------------------------- ivf
def test_ivf_recall_and_full_probe_equivalence(tmp_path):
    vecs = clustered_corpus(3000, 24, centers=40, seed=6)
    store = store_lib.build(str(tmp_path / 'ivf.vecindex'), [vecs])
    exact = ExactIndex(store)
    ivf = IVFIndex.build(store)
    rng = np.random.default_rng(7)
    queries = (vecs[rng.choice(3000, 48)]
               + 0.01 * rng.normal(size=(48, 24))).astype(np.float32)
    recall = measure_recall(ivf, exact, queries, k=10)
    assert recall >= 0.9, recall
    # probing EVERY list degenerates to exact search
    assert measure_recall(ivf, exact, queries, k=10,
                          nprobe=ivf.n_clusters) == 1.0
    # sidecar reload answers identically
    reloaded = IVFIndex(store_lib.VectorStore(store.path))
    v1, i1 = ivf.search(queries[:5], 10)
    v2, i2 = reloaded.search(queries[:5], 10)
    assert np.array_equal(i1, i2)


def test_ivf_pads_with_sentinels_when_lists_run_dry(tmp_path):
    """k larger than the probed lists' candidates: the tail must be the
    −1/−inf sentinel pair, and real rows must never repeat."""
    vecs = clustered_corpus(120, 8, centers=12, seed=8)
    store = store_lib.build(str(tmp_path / 'dry.vecindex'), [vecs])
    ivf = IVFIndex.build(store)
    values, indices = ivf.search(vecs[:2], 60, nprobe=1)
    for row_i in indices:
        real = row_i[row_i >= 0]
        assert len(set(real.tolist())) == len(real)
        assert len(real) < 60  # one list cannot hold them all
    assert np.all(np.isneginf(values[indices < 0]))


def test_float16_store_recall_parity(tmp_path):
    """ISSUE 5 satellite: --vectors-dtype float16 halves the footprint;
    recall@10 vs the float32 exact ranking must be unchanged within
    tolerance."""
    vecs = clustered_corpus(2000, 32, centers=30, seed=9)
    s32 = store_lib.build(str(tmp_path / 'p32.vecindex'), [vecs])
    s16 = store_lib.build(str(tmp_path / 'p16.vecindex'), [vecs],
                          dtype='float16')
    rng = np.random.default_rng(10)
    queries = (vecs[rng.choice(2000, 64)]
               + 0.01 * rng.normal(size=(64, 32))).astype(np.float32)
    _v, idx32 = ExactIndex(s32).search(queries, 10)
    _v, idx16 = ExactIndex(s16).search(queries, 10)
    overlap = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10.0
        for a, b in zip(idx32, idx16)])
    assert overlap >= 0.97, overlap


def test_ivf_recall_at_default_nprobe_50k(tmp_path):
    """ISSUE 5 acceptance: recall@10 >= 0.95 at the default
    nprobe on a >= 50k-vector corpus."""
    vecs = clustered_corpus(50000, 64, centers=500, seed=11)
    store = store_lib.build(str(tmp_path / 'big.vecindex'), [vecs])
    exact = ExactIndex(store)
    ivf = IVFIndex.build(store)
    rng = np.random.default_rng(12)
    queries = (vecs[rng.choice(50000, 128)]
               + 0.01 * rng.normal(size=(128, 64))).astype(np.float32)
    recall = measure_recall(ivf, exact, queries, k=10)
    assert recall >= 0.95, recall


# -------------------------------------------------------- schema coverage
def test_metrics_lint_covers_index_package():
    """ISSUE 5 satellite: the schema lint must scan code2vec_tpu/index/
    — an uncataloged metric there has to fail tier-1."""
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, 'scripts'))
    import check_metrics_schema
    emissions = check_metrics_schema.find_emissions()
    index_sites = [name for rel, _line, name in emissions
                   if rel.startswith(os.path.join('code2vec_tpu',
                                                  'index'))]
    assert 'index/queries_total' in index_sites
    assert 'index/recall_at10' in index_sites
