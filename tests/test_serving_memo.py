"""Memoization-tier drills (serving/memo.py + the mesh admission wiring,
ISSUE 16): one shared request-identity definition (canonicalize_contexts)
across engine/mesh/memo key, exact-tier hits resolved AT SUBMIT with
memo-vs-live bit identity (including the oversize split/re-join path and
permuted context order), degraded-tier answers that cannot poison the
full-tier key, the rollover-invalidation drill (fleet swap -> every
pre-swap entry misses via ONE generation bump, not per-entry eviction;
a rolled-back canary leaves the cache warm), the epsilon-gated semantic
tier with its shadow-sampled top-1 agreement export, and LRU/ledger
byte accounting."""
import collections

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import canonicalize_contexts, parse_c2v_line
from code2vec_tpu.serving import memo as memo_lib
from code2vec_tpu.telemetry import memory as memory_lib
from tests.test_train_overfit import make_dataset

PREDICT_LINES = [
    'get|a toka0,pA,toka1 toka1,pB,toka2',
    'set|b tokb0,pA,tokb1',
    'run|c tokc0,pC,tokc1 tokc2,pA,tokc0 tokc1,pB,tokc2',
]

# same requests, context multisets permuted within each line (plus
# stray whitespace): identical canonical form, so identical memo keys
PERMUTED_LINES = [
    'get|a toka1,pB,toka2 toka0,pA,toka1',
    'set|b  tokb0,pA,tokb1',
    'run|c tokc1,pB,tokc2 tokc0,pC,tokc1 tokc2,pA,tokc0',
]


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('serving_memo'))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,16')
    return Code2VecModel(config)


def _assert_rows_identical(a_rows, b_rows):
    """Bit identity between two result lists (the memo acceptance bar:
    a cache-served answer is indistinguishable from the live one)."""
    assert len(a_rows) == len(b_rows)
    for a, b in zip(a_rows, b_rows):
        assert a.original_name == b.original_name
        assert a.topk_predicted_words == b.topk_predicted_words
        if a.topk_predicted_words_scores is None:
            assert b.topk_predicted_words_scores is None
        else:
            np.testing.assert_array_equal(a.topk_predicted_words_scores,
                                          b.topk_predicted_words_scores)
        assert a.attention_per_context == b.attention_per_context
        if a.code_vector is None:
            assert b.code_vector is None
        else:
            np.testing.assert_array_equal(a.code_vector, b.code_vector)


# --------------------------------------------------- canonical identity
def test_canonicalize_contexts_semantics():
    # sort each line's context multiset, label kept first; duplicates
    # are KEPT — a repeated context weights attention twice, so the
    # count is part of request identity
    assert canonicalize_contexts(['lab c,p,d a,p,b a,p,b']) == \
        ['lab a,p,b a,p,b c,p,d']
    # split matches parse_c2v_line (single-space separators): empty
    # slots from doubled spaces are dropped; blank lines survive
    # positionally
    assert canonicalize_contexts(['lab  x,y,z ', '', 'l2 a,b,c']) == \
        ['lab x,y,z', '', 'l2 a,b,c']
    # idempotent: canonical input is a fixed point
    lines = canonicalize_contexts(PERMUTED_LINES)
    assert canonicalize_contexts(lines) == lines
    # line ORDER is preserved — results are positional
    swapped = canonicalize_contexts([PREDICT_LINES[1], PREDICT_LINES[0]])
    assert swapped[0].startswith('set|b')


def test_canonicalize_truncates_in_extraction_order():
    """REVIEW fix: truncation to MAX_CONTEXTS happens in ORIGINAL
    extraction order, BEFORE the canonical sort — the context subset
    that survives is exactly the subset the evaluate-path reader
    (parse_c2v_line, which never canonicalizes) keeps."""
    line = 'lab c,p,3 a,p,1 b,p,2'
    # sort-first would keep {a,b}; extraction-order keeps {c,a}
    assert canonicalize_contexts([line], 2) == ['lab a,p,1 c,p,3']
    # an empty slot from a doubled space occupies a context slot in
    # parse_c2v_line, so it must occupy one during truncation here too
    gapped = 'lab a,p,1  b,p,2'
    assert canonicalize_contexts([gapped], 2) == ['lab a,p,1']
    # idempotent at fixed max_contexts
    once = canonicalize_contexts([line, gapped], 2)
    assert canonicalize_contexts(once, 2) == once
    # the canonical line tokenizes to the same label + valid-context
    # multiset as the raw line, at every truncation width
    wide = 'l ' + ' '.join('t%d,p,%d' % (i, i) for i in range(10))
    for raw in (line, gapped, wide):
        for m in (1, 2, 4, 8):
            canon = canonicalize_contexts([raw], m)[0]
            raw_row = parse_c2v_line(raw, m)
            canon_row = parse_c2v_line(canon, m)
            assert canon_row.label_str == raw_row.label_str

            def valid_ctxs(row):
                return sorted(t for t in zip(row.source_strs,
                                             row.path_strs,
                                             row.target_strs) if any(t))
            assert valid_ctxs(canon_row) == valid_ctxs(raw_row)


def test_request_key_scopes_tier_and_k_and_line_order():
    canon = canonicalize_contexts(PREDICT_LINES)
    permuted = canonicalize_contexts(PERMUTED_LINES)
    assert memo_lib.request_key(canon, 'topk') == \
        memo_lib.request_key(permuted, 'topk')
    assert memo_lib.request_key(canon, 'topk') != \
        memo_lib.request_key(canon, 'full')
    assert memo_lib.request_key(canon, 'neighbors', k=5) != \
        memo_lib.request_key(canon, 'neighbors', k=10)
    reordered = [canon[1], canon[0], canon[2]]
    assert memo_lib.request_key(canon, 'topk') != \
        memo_lib.request_key(reordered, 'topk')


# ------------------------------------------------------ MemoCache units
def test_memo_cache_lru_eviction_and_ledger_bytes():
    cache = memo_lib.MemoCache(4096)
    try:
        keys = [memo_lib.request_key(['l%d a,b,c' % i], 'topk')
                for i in range(8)]
        row = [{'scores': np.zeros(128, np.float64)}]  # ~1k + overhead
        for key in keys:
            assert cache.insert(key, row, cache.generation)
        stats = cache.stats()
        assert stats['evictions'] > 0
        assert stats['bytes'] <= cache.capacity_bytes
        # the LRU survivor set is the most-recent suffix
        assert cache.lookup(keys[0]) is None
        assert cache.lookup(keys[-1]) is not None
        # ledger: memo bucket carries the cache's host bytes
        assert memory_lib.ledger().bucket_bytes('memo') == stats['bytes']
        # a result larger than the whole budget is skipped
        huge = [{'scores': np.zeros(4096, np.float64)}]
        assert not cache.insert(keys[0], huge, cache.generation)
        # an insert carrying a stale generation is refused (a request
        # in flight across a rollover can never poison the new cache)
        old_gen = cache.generation
        cache.bump_generation(3)
        assert not cache.insert(keys[0], row, old_gen)
        assert cache.lookup(keys[-1]) is None  # swap invalidated all
        assert cache.stats()['params_step'] == 3
    finally:
        cache.close()
    assert memory_lib.ledger().bucket_bytes('memo') == 0


def test_memo_cache_generation_bump_is_not_eviction():
    cache = memo_lib.MemoCache(1 << 20)
    try:
        key = memo_lib.request_key(['l a,b,c'], 'topk')
        cache.insert(key, [{'s': np.zeros(8)}], cache.generation)
        before = cache.stats()
        assert before['entries'] == 1
        cache.bump_generation()
        after = cache.stats()
        assert after['generation'] == before['generation'] + 1
        assert after['entries'] == 0 and after['bytes'] == 0
        # the drill's distinguishing assertion: atomic version bump,
        # NOT a per-entry eviction walk
        assert after['evictions'] == before['evictions'] == 0
        assert cache.lookup(key) is None
    finally:
        cache.close()


def test_memo_stale_generation_eviction_reexports_gauges():
    """The defensive stale-generation eviction in lookup must re-export
    memo/bytes, memo/entries and the ledger bucket immediately — not
    leave them stale until the next insert."""
    cache = memo_lib.MemoCache(1 << 20)
    try:
        key = memo_lib.request_key(['l a,b,c'], 'topk')
        cache.insert(key, [{'s': np.zeros(64)}], cache.generation)
        assert cache.bytes_gauge.snapshot() > 0
        assert memory_lib.ledger().bucket_bytes('memo') > 0
        # forge the unreachable-in-practice state the branch defends
        # against: an entry whose generation mismatches the cache's
        cache._entries[key].generation += 1
        assert cache.lookup(key) is None
        assert cache.bytes_gauge.snapshot() == 0
        assert cache.entries_gauge.snapshot() == 0
        assert memory_lib.ledger().bucket_bytes('memo') == 0
        assert cache.stats()['entries'] == 0
    finally:
        cache.close()


def test_memo_hits_isolated_from_caller_mutation():
    """Neither the first (delivering) caller nor any hit-served caller
    can poison the cache by mutating what they were handed: inserts
    snapshot, hits get fresh copies (copy_results)."""
    from code2vec_tpu.index.service import NeighborResult
    cache = memo_lib.MemoCache(1 << 20)
    try:
        key = memo_lib.request_key(['l a,b,c'], 'neighbors', k=2)
        live = [NeighborResult(indices=np.array([2, 0]),
                               scores=np.array([0.9, 0.5], np.float32),
                               labels=['c', 'a'])]
        cache.insert(key, live, cache.generation)
        # the delivering caller mutates its rows AFTER delivery
        live[0].scores[:] = -1.0
        live[0].labels.append('poison')
        hit = cache.lookup(key)
        assert type(hit[0]) is NeighborResult  # NamedTuple type kept
        np.testing.assert_array_equal(
            hit[0].scores, np.array([0.9, 0.5], np.float32))
        assert hit[0].labels == ['c', 'a']
        # a hit-served caller mutates what IT got back
        hit[0].scores[:] = 7.0
        hit[0].labels.clear()
        again = cache.lookup(key)
        assert again[0] is not hit[0]
        np.testing.assert_array_equal(
            again[0].scores, np.array([0.9, 0.5], np.float32))
        assert again[0].labels == ['c', 'a']
    finally:
        cache.close()


def test_memo_semantic_serves_isolated_copies():
    from code2vec_tpu.index.service import neighbors_from_search
    cache = memo_lib.MemoCache(1 << 20, semantic_epsilon=0.05,
                               semantic_shadow_every=100)
    try:
        vec = np.array([1.0, 0.0, 0.0], np.float32)
        rows = neighbors_from_search(np.array([[0.9, 0.5]]),
                                     np.array([[2, 0]]), ['a', 'b', 'c'])
        cache.semantic_insert(vec[None, :], rows, 4, cache.generation)
        rows[0].scores[:] = -1.0  # delivering caller mutates after
        served, shadow = cache.semantic_lookup(vec, 4)
        assert not shadow
        np.testing.assert_array_almost_equal(served.scores, [0.9, 0.5])
        served.scores[:] = 5.0  # hit caller mutates its copy
        served2, _ = cache.semantic_lookup(vec, 4)
        assert served2 is not served
        np.testing.assert_array_almost_equal(served2.scores, [0.9, 0.5])
    finally:
        cache.close()


def test_memo_semantic_shadow_sampling_and_agreement():
    from code2vec_tpu.index.service import neighbors_from_search
    cache = memo_lib.MemoCache(1 << 20, semantic_epsilon=0.05,
                               semantic_shadow_every=2)
    try:
        vec = np.array([1.0, 0.0, 0.0, 0.0], np.float32)
        rows = neighbors_from_search(np.array([[0.9, 0.5]]),
                                     np.array([[2, 0]]),
                                     ['a', 'b', 'c'])
        assert cache.semantic_insert(vec[None, :], rows, 10,
                                     cache.generation) == 1
        near = vec * 1.001 + np.array([0.0, 1e-3, 0.0, 0.0], np.float32)
        hit = cache.semantic_lookup(near, 10)
        assert hit is not None and hit[1] is False  # served
        hit2 = cache.semantic_lookup(near, 10)
        assert hit2 is not None and hit2[1] is True  # shadow sample
        # beyond epsilon, or a different k: no candidate
        far = np.array([0.0, 1.0, 0.0, 0.0], np.float32)
        assert cache.semantic_lookup(far, 10) is None
        assert cache.semantic_lookup(near, 5) is None
        # shadow agreement export: 1 agree + 1 disagree -> rate 0.5
        cache.note_semantic_agreement(rows[0], rows[0])
        other = neighbors_from_search(np.array([[0.8, 0.1]]),
                                      np.array([[1, 0]]), ['a', 'b', 'c'])
        cache.note_semantic_agreement(rows[0], other[0])
        stats = cache.stats()['semantic']
        assert stats['samples'] == 2
        assert stats['agreement'] == pytest.approx(0.5)
        assert cache.agreement_gauge.snapshot() == pytest.approx(0.5)
    finally:
        cache.close()


def test_memo_semantic_off_by_default_stores_nothing():
    cache = memo_lib.MemoCache(1 << 20)  # epsilon 0 = tier OFF
    try:
        vec = np.ones((1, 4), np.float32)
        assert cache.semantic_insert(vec, [object()], 10,
                                     cache.generation) == 0
        assert cache.semantic_lookup(vec[0], 10) is None
        assert cache.stats()['semantic']['rows'] == 0
    finally:
        cache.close()


# ------------------------------------------------- mesh admission wiring
def test_mesh_exact_hit_at_submit_bit_identical_to_live(model):
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'attention'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        live = mesh.predict(PREDICT_LINES, tier='attention', timeout=60)
        # the duplicate — context order permuted — is served AT SUBMIT:
        # the future comes back already resolved, before tokenize,
        # before the queue, before the device
        handle = mesh.submit(PERMUTED_LINES, tier='attention')
        assert handle.done()
        cached = handle.result()
        _assert_rows_identical(cached, live)
        # ... and bit-identical to an independent live compute
        _assert_rows_identical(cached, model.predict(PREDICT_LINES))
        stats = mesh.stats()['memo']
        assert stats['hits'] == 1 and stats['entries'] >= 1
        assert stats['bytes'] > 0
    finally:
        mesh.close()


def test_mesh_memo_off_by_default(model):
    mesh = model.serving_mesh(replicas=1, tiers=('topk',),
                              max_delay_ms=0.0)
    try:
        assert mesh.stats()['memo'] is None
        mesh.predict(PREDICT_LINES, tier='topk', timeout=60)
        handle = mesh.submit(PREDICT_LINES, tier='topk')
        assert not handle.done() or handle.result()  # went live
        handle.result(timeout=60)
    finally:
        mesh.close()


def test_mesh_oversize_split_rejoin_memo_bit_identity(model):
    """A request wider than the top batch bucket (16) is split into
    chunks and re-joined; the memo insert fires on the CALLER-VISIBLE
    future after the join, so the cached answer covers all rows in
    order."""
    lines = [PREDICT_LINES[i % 3] for i in range(20)]
    permuted = [PERMUTED_LINES[i % 3] for i in range(20)]
    mesh = model.serving_mesh(replicas=1, tiers=('topk',),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        live = mesh.predict(lines, tier='topk', timeout=120)
        assert len(live) == 20
        handle = mesh.submit(permuted, tier='topk')
        assert handle.done()
        _assert_rows_identical(handle.result(), live)
        # independent live compute (model.predict serves the full tier:
        # compare the fields the topk tier produces). One 20-row program
        # against the mesh's 16+4 split: the softmax scores agree to
        # float32 rounding, not bitwise
        for cached, ref in zip(handle.result(), model.predict(lines)):
            assert cached.topk_predicted_words == ref.topk_predicted_words
            np.testing.assert_allclose(
                cached.topk_predicted_words_scores,
                ref.topk_predicted_words_scores, rtol=1e-6)
    finally:
        mesh.close()


def test_mesh_degraded_tier_cannot_poison_full_key(model, monkeypatch):
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'full'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        orig_admit = mesh._queue.admit

        def degrading_admit(n, tier, deadline_s):
            return orig_admit(n, 'topk' if tier == 'full' else tier,
                              deadline_s)

        monkeypatch.setattr(mesh._queue, 'admit', degrading_admit)
        degraded = mesh.predict(PREDICT_LINES, tier='full', timeout=60)
        assert all(not r.attention_per_context for r in degraded)
        monkeypatch.undo()
        # the degraded answer was keyed under its EFFECTIVE tier: the
        # full-tier ask misses and computes live, with attention
        handle = mesh.submit(PREDICT_LINES, tier='full')
        assert not handle.done()
        full = handle.result(timeout=60)
        assert all(r.attention_per_context for r in full)
        # ... while a topk ask is a legitimate hit on the degraded row
        topk_handle = mesh.submit(PREDICT_LINES, tier='topk')
        assert topk_handle.done()
        _assert_rows_identical(topk_handle.result(), degraded)
    finally:
        mesh.close()


# ------------------------------------------------ rollover invalidation
def test_rollover_invalidation_drill(model):
    """Fleet swap -> every pre-swap memo entry is a MISS via one atomic
    generation bump (evictions stay 0); a rolled-BACK canary leaves the
    cache warm."""
    import jax
    mesh = model.serving_mesh(replicas=2, tiers=('topk',),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        same = jax.tree_util.tree_map(lambda leaf: leaf, model.params)
        broken = jax.tree_util.tree_map(lambda leaf: -leaf, model.params)
        jax.block_until_ready(broken)
        mesh.predict(PREDICT_LINES, tier='topk', timeout=60)
        warm_hit = mesh.submit(PREDICT_LINES, tier='topk')
        assert warm_hit.done()
        gen_before = mesh.stats()['memo']['generation']

        # ---- canaried fleet swap: the CONCLUDE callback must bump
        handle = mesh.load_params(same, canary_batches=2,
                                  min_agreement=0.9)
        for _ in range(12):
            if handle.done():
                break
            mesh.predict(PREDICT_LINES, tier='topk', timeout=60)
        assert handle.result(timeout=60)['swapped'] is True
        stats = mesh.stats()['memo']
        assert stats['generation'] == gen_before + 1
        assert stats['entries'] == 0 and stats['bytes'] == 0
        assert stats['evictions'] == 0  # version bump, not eviction
        stale = mesh.submit(PREDICT_LINES, tier='topk')
        assert not stale.done()  # pre-swap entry can never serve
        stale.result(timeout=60)

        # ---- rolled-back canary: cache stays WARM
        rewarmed = mesh.submit(PREDICT_LINES, tier='topk')
        assert rewarmed.done()  # the post-swap compute re-cached it
        handle = mesh.load_params(broken, canary_batches=2,
                                  min_agreement=0.9)
        for _ in range(12):
            if handle.done():
                break
            mesh.predict([PREDICT_LINES[0]], tier='topk', timeout=60)
        assert handle.result(timeout=60)['swapped'] is False
        stats = mesh.stats()['memo']
        assert stats['generation'] == gen_before + 1  # unchanged
        still_warm = mesh.submit(PREDICT_LINES, tier='topk')
        assert still_warm.done()
    finally:
        mesh.close()


# ------------------------------------------------------- semantic tier
class _FakeIndex:
    """Deterministic stand-in for index/service.py's loaded index."""

    def __init__(self, dim, n=8, seed=0):
        rng = np.random.default_rng(seed)
        store = rng.normal(size=(n, dim)).astype(np.float32)
        self._store = store / np.linalg.norm(store, axis=1,
                                             keepdims=True)
        self.labels = ['lab%d' % i for i in range(n)]

    def search(self, vectors, k):
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        sims = vectors @ self._store.T
        idx = np.argsort(-sims, axis=1)[:, :k]
        return np.take_along_axis(sims, idx, axis=1), idx


def test_mesh_neighbors_exact_and_semantic_tiers(model):
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20,
                              memo_semantic_epsilon=0.05)
    try:
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        mesh.attach_index(_FakeIndex(dim=vec.shape[0]))
        # line-path exact tier: keyed per k
        first = mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        again = mesh.submit_neighbors(list(PERMUTED_LINES), k=4)
        assert again.done()
        assert [r.labels for r in again.result()] == \
            [r.labels for r in first]
        # keyed per k: the k=6 ask is NOT served from the k=4 entry
        # (it may still complete synchronously — its inner vectors-tier
        # submit is itself a legitimate memo hit)
        hits_before = mesh.stats()['memo']['hits']
        other_k = mesh.submit_neighbors(PREDICT_LINES, k=6).result(60)
        assert len(other_k[0].labels) == 6
        assert mesh.stats()['memo']['hits'] == hits_before + 1  # vectors
        # ndarray-path semantic tier: a near-identical single-row query
        # is served from the cached neighbor result; every 8th
        # candidate hit shadow-samples top-1 agreement instead
        live = mesh.submit_neighbors(vec, k=4).result(60)
        serves = 0
        for i in range(10):
            near = vec * np.float32(1.0 + 1e-5 * (i + 1))
            out = mesh.submit_neighbors(near, k=4).result(60)
            assert out[0].labels == live[0].labels
        stats = mesh.stats()['memo']
        assert stats['semantic']['serves'] >= 8
        assert stats['semantic']['samples'] >= 1  # shadow ran live
        assert stats['semantic']['agreement'] == pytest.approx(1.0)
        assert stats['semantic_hits'] >= 1
    finally:
        mesh.close()


class _SloStub:
    """Records SloMonitor observations (serving/slo.py interface)."""

    def __init__(self):
        self.good = 0
        self.bad = 0

    def observe_good(self, latency_s=None, scenario=None):
        self.good += 1

    def observe_bad(self, reason='failed', scenario=None):
        self.bad += 1

    def stats(self):
        return {'good': self.good, 'bad': self.bad}


def test_mesh_neighbors_memo_stands_down_during_canary(model):
    """REVIEW fix: while a canary rollover is in flight, BOTH
    submit_neighbors memo tiers (exact nkey + semantic) must run live,
    like submit() — cache-served duplicates would starve the shadow
    scorer.  Also: cache-served neighbors requests must stay in the
    SLO good-rate denominator."""
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20,
                              memo_semantic_epsilon=0.05)
    try:
        slo = _SloStub()
        mesh._slo = slo
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        mesh.attach_index(_FakeIndex(dim=vec.shape[0]))
        # warm both tiers
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        mesh.submit_neighbors(vec, k=4).result(60)
        # duplicates are hits while no rollover is in flight — and each
        # cache-served request is observed into the SLO good stream
        good_before = slo.good
        warm = mesh.submit_neighbors(PREDICT_LINES, k=4)
        assert warm.done()
        assert slo.good == good_before + 1
        near = vec * np.float32(1.00001)
        sem = mesh.submit_neighbors(near, k=4)
        assert sem.done()
        assert slo.good == good_before + 2
        serves_before = mesh.stats()['memo']['semantic']['serves']
        hits_before = mesh.stats()['memo']['hits']
        # arm a fake in-flight rollover: both tiers stand down
        mesh._rollover = {'replica': None, 'handle': None}
        try:
            rolled = mesh.submit_neighbors(PREDICT_LINES, k=4)
            assert not rolled.done()  # ran live, not cache-served
            rolled.result(60)
            sem_rolled = mesh.submit_neighbors(near, k=4)
            sem_rolled.result(60)
            stats = mesh.stats()['memo']
            assert stats['hits'] == hits_before  # exact tier stood down
            assert stats['semantic']['serves'] == serves_before
        finally:
            mesh._rollover = None
        # rollover concluded: duplicates serve from cache again
        assert mesh.submit_neighbors(PREDICT_LINES, k=4).done()
    finally:
        mesh.close()


# ------------------------------------------ index-generation keying
def test_memo_index_generation_two_axes():
    """ISSUE 19 bugfix: memo generations key on (params step, index
    version).  An index swap bumps ONLY the index axis — neighbor
    entries (pinned to an index generation) invalidate atomically while
    predict entries (index-independent) keep serving; a params bump
    still clears everything."""
    cache = memo_lib.MemoCache(1 << 20)
    try:
        pkey = memo_lib.request_key(['l a,b,c'], 'topk')
        nkey = memo_lib.request_key(['l a,b,c'], 'neighbors', k=4)
        row = [{'s': np.zeros(8)}]
        assert cache.insert(pkey, row, cache.generation)
        assert cache.insert(nkey, row, cache.generation,
                            index_generation=cache.index_generation)
        before = cache.stats()
        assert before['entries'] == 2
        cache.bump_index_generation()
        after = cache.stats()
        assert after['index_generation'] == \
            before['index_generation'] + 1
        assert after['generation'] == before['generation']
        assert cache.lookup(nkey) is None       # index-dependent: gone
        assert cache.lookup(pkey) is not None   # index-independent: warm
        assert after['entries'] == 1
        assert after['evictions'] == 0  # version bump, not eviction
        # byte accounting stays consistent through the selective drop
        assert memory_lib.ledger().bucket_bytes('memo') == \
            cache.stats()['bytes'] > 0
        # an insert carrying a stale index generation is refused (a
        # neighbor request in flight across an index swap can never
        # poison the new cache)
        assert not cache.insert(
            nkey, row, cache.generation,
            index_generation=after['index_generation'] - 1)
        assert cache.insert(nkey, row, cache.generation,
                            index_generation=cache.index_generation)
        # the params axis still clears BOTH kinds of entry
        cache.bump_generation()
        assert cache.lookup(pkey) is None
        assert cache.lookup(nkey) is None
    finally:
        cache.close()


def test_memo_index_bump_drops_semantic_and_refuses_stale():
    """The semantic tier answers from cached index results, so an index
    swap drops it wholesale; a stale-index-generation semantic insert
    is refused."""
    from code2vec_tpu.index.service import neighbors_from_search
    cache = memo_lib.MemoCache(1 << 20, semantic_epsilon=0.05,
                               semantic_shadow_every=100)
    try:
        vec = np.array([1.0, 0.0, 0.0, 0.0], np.float32)
        rows = neighbors_from_search(np.array([[0.9, 0.5]]),
                                     np.array([[2, 0]]),
                                     ['a', 'b', 'c'])
        assert cache.semantic_insert(
            vec[None, :], rows, 4, cache.generation,
            index_generation=cache.index_generation) == 1
        assert cache.semantic_lookup(vec, 4) is not None
        cache.bump_index_generation()
        assert cache.semantic_lookup(vec, 4) is None
        assert cache.semantic_insert(
            vec[None, :], rows, 4, cache.generation,
            index_generation=cache.index_generation - 1) == 0
        assert cache.semantic_lookup(vec, 4) is None
    finally:
        cache.close()


# ------------------------------------------------ index rollover drills
class _WorstIndex(_FakeIndex):
    """Deterministically DISAGREEING candidate: returns the worst-k
    rows, disjoint from _FakeIndex's top-k when k <= n/2."""

    def search(self, vectors, k):
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        sims = vectors @ self._store.T
        idx = np.argsort(sims, axis=1)[:, :k]
        return np.take_along_axis(sims, idx, axis=1), idx


class _BoomIndex:
    def search(self, vectors, k):
        raise RuntimeError('candidate index cannot answer')


class _CountingIndex:
    """Search-call counter: a cache-served neighbors answer never
    touches the index, a live one always does.  (.done() alone cannot
    distinguish them — the chain resolves synchronously whenever the
    inner vectors-tier submit is itself a legitimate memo hit.)"""

    def __init__(self, inner):
        self._inner = inner
        self.searches = 0

    def search(self, vectors, k):
        self.searches += 1
        return self._inner.search(vectors, k)

    @property
    def labels(self):
        return self._inner.labels


def test_mesh_index_rollover_swap_invalidates_neighbors_not_predict(
        model):
    """Agreeing candidate swaps in: index version + memo index
    generation bump, every cached neighbor result misses, predict
    entries survive (the model didn't change)."""
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        live = _CountingIndex(_FakeIndex(dim=vec.shape[0]))
        mesh.attach_index(live)
        # warm one neighbor entry and one predict entry
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        searches = live.searches
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert live.searches == searches  # duplicate served from cache
        mesh.predict(PREDICT_LINES, tier='topk', timeout=60)
        assert mesh.submit(PREDICT_LINES, tier='topk').done()
        stats = mesh.stats()
        version_before = stats['index_version']
        igen_before = stats['memo']['index_generation']
        gen_before = stats['memo']['generation']
        # same seed -> identical store -> agreement 1.0
        cand = _CountingIndex(_FakeIndex(dim=vec.shape[0]))
        handle = mesh.rollover_index(cand, shadow_queries=1,
                                     min_agreement=0.9)
        # drive the shadow with a DIFFERENT query than the probe key:
        # a driver admitted right after the conclusion would re-insert
        # its own key under the new generation, which must not turn
        # the staleness probe below into a legitimate hit
        for _ in range(12):
            if handle.done():
                break
            mesh.submit_neighbors([PREDICT_LINES[0]], k=4).result(60)
        report = handle.result(timeout=60)
        assert report['swapped'] is True
        assert report['agreement'] == pytest.approx(1.0)
        assert report['index_version'] == version_before + 1
        stats = mesh.stats()
        assert stats['index_version'] == version_before + 1
        assert stats['index_rollover_total'] >= 1
        assert stats['memo']['index_generation'] == igen_before + 1
        assert stats['memo']['generation'] == gen_before  # untouched
        # the pre-swap neighbor entry can never serve again: the
        # duplicate must run LIVE against the new index
        searches = cand.searches
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert cand.searches > searches
        # ... while the predict entry survives the swap
        assert mesh.submit(PREDICT_LINES, tier='topk').done()
    finally:
        mesh.close()


def test_mesh_index_rollover_rollback_keeps_memo_warm(model):
    """Disagreeing candidate rolls back: the serving index, its
    version, and every cached neighbor result stay live — the
    candidate never serves a request."""
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        live = _CountingIndex(_FakeIndex(dim=vec.shape[0]))
        mesh.attach_index(live)
        first = mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        stats = mesh.stats()
        version_before = stats['index_version']
        igen_before = stats['memo']['index_generation']
        handle = mesh.rollover_index(_WorstIndex(dim=vec.shape[0]),
                                     shadow_queries=1,
                                     min_agreement=0.9)
        for _ in range(12):
            if handle.done():
                break
            mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        report = handle.result(timeout=60)
        assert report['swapped'] is False
        assert report['agreement'] == pytest.approx(0.0)
        stats = mesh.stats()
        assert stats['index_version'] == version_before
        assert stats['index_rollover_rollbacks_total'] >= 1
        assert stats['memo']['index_generation'] == igen_before
        # rollback left the neighbor memo warm: the duplicate is
        # answered without a live index search
        searches = live.searches
        warm = mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert live.searches == searches
        assert [r.labels for r in warm] == [r.labels for r in first]
    finally:
        mesh.close()


def test_mesh_index_rollover_candidate_error_and_validation(model):
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        # no index attached yet: nothing to roll over
        with pytest.raises(RuntimeError, match='no index attached'):
            mesh.rollover_index(_FakeIndex(dim=4))
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        live = _FakeIndex(dim=vec.shape[0])
        mesh.attach_index(live)
        with pytest.raises(ValueError, match='shadow_queries'):
            mesh.rollover_index(_FakeIndex(dim=vec.shape[0]),
                                shadow_queries=0)
        with pytest.raises(ValueError, match='candidate index'):
            mesh.rollover_index(object())
        # a candidate that cannot answer the shadow queries must never
        # swap in: the handle raises, the old index keeps serving
        first = mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        handle = mesh.rollover_index(_BoomIndex(), shadow_queries=1)
        deadline = 60
        while not handle.done() and deadline:
            mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
            deadline -= 1
        with pytest.raises(RuntimeError, match='cannot answer'):
            handle.result(timeout=60)
        stats = mesh.stats()
        assert stats['index_version'] == 0
        assert stats['index_rollover_rollbacks_total'] >= 1
        again = mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert [r.labels for r in again] == [r.labels for r in first]
    finally:
        mesh.close()


def test_mesh_neighbors_memo_stands_down_during_index_rollover(model):
    """While an index rollover is armed, submit_neighbors duplicates
    run LIVE (both the exact nkey and semantic tiers) — cache-served
    answers would starve the shadow scorer, exactly like the params
    canary stand-down."""
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20,
                              memo_semantic_epsilon=0.05)
    try:
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        live = _CountingIndex(_FakeIndex(dim=vec.shape[0]))
        mesh.attach_index(live)
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        mesh.submit_neighbors(vec, k=4).result(60)
        searches = live.searches
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert live.searches == searches  # warm: served from cache
        serves_before = mesh.stats()['memo']['semantic']['serves']
        # arm a minimal in-flight rollover state ('concluding' makes
        # the shadow scorer a no-op, so it never concludes under us)
        mesh._index_rollover = {'concluding': True}
        try:
            mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
            assert live.searches == searches + 1  # exact tier ran live
            near = vec * np.float32(1.00001)
            mesh.submit_neighbors(near, k=4).result(60)
            assert live.searches == searches + 2  # semantic ran live
            stats = mesh.stats()['memo']
            assert stats['semantic']['serves'] == serves_before
        finally:
            mesh._index_rollover = None
        # concluded: duplicates serve from cache again
        searches = live.searches
        mesh.submit_neighbors(PREDICT_LINES, k=4).result(60)
        assert live.searches == searches
    finally:
        mesh.close()


def test_mesh_semantic_tier_defaults_off(model):
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              max_delay_ms=0.0,
                              memo_cache_bytes=32 << 20)
    try:
        vec = mesh.predict([PREDICT_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        mesh.attach_index(_FakeIndex(dim=vec.shape[0]))
        mesh.submit_neighbors(vec, k=4).result(60)
        mesh.submit_neighbors(vec * np.float32(1.00001),
                              k=4).result(60)
        stats = mesh.stats()['memo']
        assert stats['semantic']['epsilon'] == 0.0
        assert stats['semantic']['rows'] == 0
        assert stats['semantic']['serves'] == 0
    finally:
        mesh.close()
