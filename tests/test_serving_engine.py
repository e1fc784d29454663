"""Serving engine (serving/engine.py + serving/bulk.py): bucket
selection, when a coalescing batch closes (a free decode slot, the
deadline), exact parity with ``model.predict``, output tiers, oversize
splitting, and the corpus-scale bulk paths."""
import time

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.serving import engine as engine_lib
from tests.serving_slots import decode_slots_held
from tests.test_train_overfit import make_dataset

# the four labels/token families of make_dataset's corpus
PREDICT_LINES = [
    'get|a toka0,pA,toka1 toka1,pB,toka2',
    'set|b tokb0,pA,tokb1',
    'run|c tokc0,pC,tokc1 tokc2,pA,tokc0 tokc1,pB,tokc2',
]


# ------------------------------------------------------------ pure units
def test_batch_ladder_rounds_to_data_axis():
    assert engine_lib.batch_ladder([8, 64], 8) == (8, 64)
    # rounded up to the axis, deduplicated, sorted
    assert engine_lib.batch_ladder([1, 8, 10, 60], 8) == (8, 16, 64)
    with pytest.raises(ValueError):
        engine_lib.batch_ladder([0], 8)


def test_pick_bucket_smallest_cover():
    ladder = (8, 16, 64)
    assert engine_lib.pick_bucket(1, ladder) == 8
    assert engine_lib.pick_bucket(8, ladder) == 8
    assert engine_lib.pick_bucket(9, ladder) == 16
    assert engine_lib.pick_bucket(64, ladder) == 64
    assert engine_lib.pick_bucket(65, ladder) is None


def test_capacity_ladder_covers_and_grows_geometrically():
    assert packed_lib.capacity_ladder(6) == (64,)
    assert packed_lib.capacity_ladder(64) == (64,)
    assert packed_lib.capacity_ladder(65) == (64, 65)
    assert packed_lib.capacity_ladder(1600) == (64, 256, 1024, 1600)
    ladder = packed_lib.capacity_ladder(25600)
    assert ladder[-1] == 25600
    assert all(a < b for a, b in zip(ladder, ladder[1:]))
    with pytest.raises(ValueError):
        packed_lib.capacity_ladder(0)


def test_capacity_rungs_are_exact_pack_targets():
    """pack_ragged with capacity_minimum=<rung> must land EXACTLY on the
    rung for any total <= rung — that is what makes every dispatched
    wire shape one of the pre-compiled ladder shapes."""
    rng = np.random.default_rng(0)
    for rung in packed_lib.capacity_ladder(1600):
        count = np.array([3, 0, 5, 1], np.int32)
        ctx_rows = rng.integers(
            1, 100, (int(count.sum()), 3)).astype(np.int32)
        ctx = packed_lib.pack_ragged(ctx_rows, count, 0, 0,
                                     capacity_minimum=rung)
        assert ctx.shape == (1, rung, 3)


def test_shard_totals():
    count = np.array([1, 2, 3, 4], np.int32)
    np.testing.assert_array_equal(
        packed_lib.shard_totals(count, 2), [3, 7])
    with pytest.raises(ValueError):
        packed_lib.shard_totals(count, 3)


# -------------------------------------------------------------- fixtures
@pytest.fixture(scope='module')
def model(tmp_path_factory):
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('serving'))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,16')
    return Code2VecModel(config)


# --------------------------------------------------------------- engine
def test_engine_matches_model_predict_exactly(model):
    direct = model.predict(PREDICT_LINES)
    with model.serving_engine(tiers=('attention',),
                              max_delay_ms=0.0) as engine:
        served = engine.predict(PREDICT_LINES, tier='attention',
                                timeout=60)
    assert len(served) == len(direct) == len(PREDICT_LINES)
    for s, d in zip(served, direct):
        assert s.original_name == d.original_name
        assert s.topk_predicted_words == d.topk_predicted_words
        np.testing.assert_array_equal(s.topk_predicted_words_scores,
                                      d.topk_predicted_words_scores)
        assert s.attention_per_context == d.attention_per_context
        assert s.code_vector is None and d.code_vector is None


def test_idle_engine_answers_a_lone_request_without_the_delay(model):
    """A free decode slot closes the batch: the delay is the longest a
    request may be held, not what an idle engine holds it for."""
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=500.0) as engine:
        engine.predict([PREDICT_LINES[0]], tier='topk', timeout=60)
        t0 = time.perf_counter()
        (result,) = engine.predict([PREDICT_LINES[1]], tier='topk',
                                   timeout=60)
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
    assert stats['batches_total'] == 2
    assert stats['early_close_total'] == 2
    assert elapsed < 0.25, 'a lone request took %.3fs of a 0.5s delay' \
        % elapsed
    assert result.topk_predicted_words == \
        model.predict([PREDICT_LINES[1]])[0].topk_predicted_words


def test_deadline_coalescing_batches_concurrent_requests(model):
    """Requests submitted while every decode slot is taken ride ONE
    dispatched micro-batch when a slot frees, and each future gets
    exactly its own rows back."""
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=60_000.0) as engine:
        with decode_slots_held(engine, PREDICT_LINES[0]) as held:
            futures = [engine.submit([line], tier='topk')
                       for line in PREDICT_LINES]
            time.sleep(0.05)  # the dispatcher had its chance
            assert engine.stats()['batches_total'] == held.batches
            held.release()
            results = [f.result(timeout=60) for f in futures]
        stats = engine.stats()
    assert stats['batches_total'] == held.batches + 1
    # closed by the freed slot, a minute before its deadline
    assert stats['early_close_total'] == held.early + 1
    assert stats['requests_total'] == held.batches + len(PREDICT_LINES)
    assert stats['last_dispatch']['requests'] == len(PREDICT_LINES)
    assert stats['last_dispatch']['rows'] == len(PREDICT_LINES)
    direct = model.predict(PREDICT_LINES)
    for (res,), d in zip(results, direct):
        assert res.original_name == d.original_name
        assert res.topk_predicted_words == d.topk_predicted_words


def test_batch_closes_at_the_deadline_while_every_slot_is_taken(model):
    """The deadline still ends the wait: with the slots held past it
    the gathered requests go out as one batch, not closed early."""
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=50.0) as engine:
        with decode_slots_held(engine, PREDICT_LINES[0]) as held:
            futures = [engine.submit([line], tier='topk')
                       for line in PREDICT_LINES[:2]]
            deadline = time.perf_counter() + 30
            while engine.stats()['batches_total'] == held.batches:
                assert time.perf_counter() < deadline, \
                    'no dispatch at the deadline'
                time.sleep(0.005)
            stats = engine.stats()  # the slots are still held
        for future in futures:
            assert future.result(timeout=60)
    assert stats['batches_total'] == held.batches + 1
    assert stats['early_close_total'] == held.early
    assert stats['last_dispatch']['requests'] == 2


def test_a_released_slot_wakes_the_dispatcher(model):
    """No lost wake-up: with one decode worker every next request finds
    the slot still taken by the batch that answered the last one, and
    waits for its release, not for the ten-second deadline."""
    with model.serving_engine(tiers=('topk',), max_delay_ms=10_000.0,
                              decode_workers=1) as engine:
        t0 = time.perf_counter()
        for i in range(20):
            engine.predict([PREDICT_LINES[i % 3]], tier='topk',
                           timeout=60)
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
    assert stats['batches_total'] == stats['early_close_total'] == 20
    assert elapsed < 10.0, '20 lone requests took %.1fs' % elapsed


def test_a_failed_decode_frees_its_slot(model, monkeypatch):
    with model.serving_engine(tiers=('topk',), max_delay_ms=10_000.0,
                              decode_workers=1) as engine:
        def broken(*args, **kwargs):
            raise RuntimeError('decode broke')
        monkeypatch.setattr(engine_lib, 'decode_results', broken)
        with pytest.raises(RuntimeError, match='decode broke'):
            engine.predict([PREDICT_LINES[0]], tier='topk', timeout=60)
        monkeypatch.undo()
        t0 = time.perf_counter()
        (result,) = engine.predict([PREDICT_LINES[1]], tier='topk',
                                   timeout=60)
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
    assert result.topk_predicted_words
    assert stats['early_close_total'] == 2
    assert elapsed < 5.0, 'the failed batch kept its slot: %.1fs' % elapsed


def test_bucket_selection_smallest_cover(model):
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=0.0) as engine:
        engine.predict([PREDICT_LINES[0]], tier='topk', timeout=60)
        first = dict(engine.stats()['last_dispatch'])
        nine = [PREDICT_LINES[i % 3] for i in range(9)]
        engine.predict(nine, tier='topk', timeout=60)
        second = dict(engine.stats()['last_dispatch'])
    assert first == {'bucket': 8, 'rows': 1, 'capacity': 64,
                     'requests': 1}
    assert second['bucket'] == 16 and second['rows'] == 9
    assert engine.stats()['batch_fill_rate'] == pytest.approx(9 / 16)


def test_topk_tier_is_attention_and_vector_free(model):
    direct = model.predict(PREDICT_LINES)
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=0.0) as engine:
        served = engine.predict(PREDICT_LINES, tier='topk', timeout=60)
    for s, d in zip(served, direct):
        assert s.topk_predicted_words == d.topk_predicted_words
        np.testing.assert_array_equal(s.topk_predicted_words_scores,
                                      d.topk_predicted_words_scores)
        assert s.attention_per_context == {}
        assert s.code_vector is None


def test_oversize_request_splits_across_buckets(model):
    lines = [PREDICT_LINES[i % 3] for i in range(20)]
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=0.0) as engine:
        served = engine.predict(lines, tier='topk', timeout=60)
        stats = engine.stats()
    assert len(served) == 20
    assert stats['batches_total'] == 2  # 16-row chunk + 4-row chunk
    # row results are independent of batch membership (per-row softmax)
    direct = model.predict(lines)
    for s, d in zip(served, direct):
        assert s.original_name == d.original_name
        assert s.topk_predicted_words == d.topk_predicted_words
        np.testing.assert_allclose(s.topk_predicted_words_scores,
                                   d.topk_predicted_words_scores,
                                   rtol=1e-5, atol=1e-7)


def test_cancelled_request_does_not_poison_batchmates(model):
    """A caller cancelling its future (these futures are never marked
    running, so cancel() always succeeds) must not break delivery to
    the other requests coalesced into the same micro-batch."""
    with model.serving_engine(tiers=('topk',),
                              max_delay_ms=60_000.0) as engine:
        with decode_slots_held(engine, PREDICT_LINES[2]) as held:
            doomed = engine.submit([PREDICT_LINES[0]], tier='topk')
            survivor = engine.submit([PREDICT_LINES[1]], tier='topk')
            assert doomed.cancel()
            held.release()
            results = survivor.result(timeout=60)
        stats = engine.stats()
    assert stats['batches_total'] == held.batches + 1  # same micro-batch
    assert stats['last_dispatch']['requests'] == 2
    assert results[0].topk_predicted_words == \
        model.predict([PREDICT_LINES[1]])[0].topk_predicted_words


def test_engine_empty_submit_and_close_semantics(model):
    engine = model.serving_engine(tiers=('topk',), warmup=False,
                                  max_delay_ms=0.0)
    assert engine.submit([], tier='topk').result(timeout=5) == []
    with pytest.raises(ValueError):
        engine.submit(PREDICT_LINES, tier='vectors')  # not warmed
    engine.close()
    engine.close()  # idempotent
    with pytest.raises(RuntimeError):
        engine.submit(PREDICT_LINES, tier='topk')


# ----------------------------------------------------------------- bulk
def test_bulk_export_code_vectors(model, tmp_path):
    corpus = tmp_path / 'corpus.c2v'
    lines = [PREDICT_LINES[i % 3] for i in range(10)]
    corpus.write_text('\n'.join(lines) + '\n')
    from code2vec_tpu.serving import bulk
    total, out_path = bulk.export_code_vectors(model, str(corpus))
    assert total == 10
    rows = [np.array(line.split(), dtype=float)
            for line in open(out_path).read().splitlines()]
    assert len(rows) == 10
    dim = model.config.CODE_VECTOR_SIZE
    assert all(r.shape == (dim,) for r in rows)
    # parity with the engine's vectors tier (batch shapes differ, so
    # allclose, not bit equality)
    with model.serving_engine(tiers=('vectors',),
                              max_delay_ms=0.0) as engine:
        served = engine.predict(lines, tier='vectors', timeout=60)
    for file_vec, res in zip(rows, served):
        np.testing.assert_allclose(file_vec, res.code_vector,
                                   rtol=1e-4, atol=1e-6)


def test_bulk_predict_streams_in_order(model):
    lines = [PREDICT_LINES[i % 3] for i in range(11)]
    from code2vec_tpu.serving import bulk
    results = list(bulk.bulk_predict(model, iter(lines), tier='topk',
                                     batch_size=8))
    assert len(results) == 11
    direct = model.predict(lines)
    for r, d in zip(results, direct):
        assert r.original_name == d.original_name
        assert r.topk_predicted_words == d.topk_predicted_words
        assert r.attention_per_context == {}


# ------------------------------- tokenizing and the contexts' strings
def _slotwise_attention(model, lines, tier):
    """``attention_per_context`` the way the batch used to carry it: the
    per-slot strings of ``parse_c2v_line`` beside the program's
    weights."""
    from code2vec_tpu.data.reader import (canonicalize_contexts,
                                          parse_c2v_line)
    contexts = model.config.MAX_CONTEXTS
    reader = model._get_predict_reader()
    batch = reader.pad_batch_to(reader.process_input_rows(lines), 8)
    out = model.trainer.predict_step(model.params, batch, tier=tier)
    attention = np.asarray(out['attention'])
    want = []
    for r, line in enumerate(canonicalize_contexts(lines, contexts)):
        row = parse_c2v_line(line, contexts)
        want.append({
            (s, p, t): float(w) for s, p, t, w in zip(
                row.source_strs, row.path_strs, row.target_strs,
                attention[r]) if s or p or t})
    return want


@pytest.fixture
def native_on(model, monkeypatch):
    """The module's model (built for the fallback) with the native
    tokenizer switched on for the readers a test builds."""
    from code2vec_tpu.data import native
    if not native.is_available():
        pytest.skip('native toolchain unavailable')
    monkeypatch.setattr(model.config, 'READER_USE_NATIVE', True)
    return model


AWKWARD_LINES = PREDICT_LINES + [
    'get|a toka1,pB,toka2  toka0,pA,toka1 toka0,pA,toka1',  # twice, a gap
    'nolabel',
    'set|b tokb0,pA tokb1 ,,',
    'run|c ' + ' '.join('tokc%d,pC,tokc%d' % (i % 3, (i + 1) % 3)
                        for i in range(9)),                 # over-long
]


@pytest.mark.parametrize('tier', ['attention', 'full'])
@pytest.mark.parametrize('tokenizer', ['fallback', 'native'])
def test_attention_from_strings_made_at_decode(model, monkeypatch, tier,
                                               tokenizer):
    from code2vec_tpu.data import native
    if tokenizer == 'native' and not native.is_available():
        pytest.skip('native toolchain unavailable')
    monkeypatch.setattr(model.config, 'READER_USE_NATIVE',
                        tokenizer == 'native')
    want = _slotwise_attention(model, AWKWARD_LINES, tier)
    with model.serving_engine(tiers=(tier,), max_delay_ms=0.0) as engine:
        assert engine.reader.native == (tokenizer == 'native')
        served = engine.predict(AWKWARD_LINES, tier=tier, timeout=60)
    assert [r.attention_per_context for r in served] == want
    assert any(want) and not want[AWKWARD_LINES.index('nolabel')]
    assert [r.original_name for r in served] == \
        [line.split(' ', 1)[0] for line in AWKWARD_LINES]


@pytest.mark.parametrize('tier', ['topk', 'vectors'])
def test_no_context_strings_for_a_tier_without_attention(
        native_on, monkeypatch, tier):
    def never(line):
        raise AssertionError('strings made for a %s row' % tier)
    monkeypatch.setattr(engine_lib, 'context_triples', never)
    with native_on.serving_engine(tiers=(tier,),
                                  max_delay_ms=0.0) as engine:
        served = engine.predict(AWKWARD_LINES, tier=tier, timeout=60)
    assert len(served) == len(AWKWARD_LINES)
    assert all(r.attention_per_context == {} for r in served)


@pytest.mark.parametrize('tokenizer', ['fallback', 'native'])
def test_oversize_split_keeps_lines_on_their_rows(model, monkeypatch,
                                                  tokenizer):
    """20 distinct rows over buckets 8,16: the chunks' lines, labels and
    ids stay aligned, so every row's attention names its own contexts."""
    from code2vec_tpu.data import native
    from code2vec_tpu.data.reader import (canonicalize_contexts,
                                          context_triples)
    if tokenizer == 'native' and not native.is_available():
        pytest.skip('native toolchain unavailable')
    monkeypatch.setattr(model.config, 'READER_USE_NATIVE',
                        tokenizer == 'native')
    lines = ['m%d|x %s' % (i, ' '.join(
        'tok%s%d,p%s,tok%s%d' % ('abc'[(i + j) % 3], j % 3, 'ABC'[j % 3],
                                 'abc'[i % 3], (i + j) % 3)
        for j in range(i % 6 + 1))) for i in range(20)]
    with model.serving_engine(tiers=('attention',),
                              max_delay_ms=0.0) as engine:
        served = engine.predict(lines, tier='attention', timeout=60)
        stats = engine.stats()
    assert stats['batches_total'] == 2  # 16-row chunk + 4-row chunk
    direct = model.predict(lines)
    canonical = canonicalize_contexts(lines, model.config.MAX_CONTEXTS)
    for i, (s, d) in enumerate(zip(served, direct)):
        assert s.original_name == d.original_name == 'm%d|x' % i
        assert set(s.attention_per_context) == \
            set(context_triples(canonical[i]))
        assert s.topk_predicted_words == d.topk_predicted_words
        assert s.attention_per_context.keys() == \
            d.attention_per_context.keys()
        for key, weight in s.attention_per_context.items():
            assert weight == pytest.approx(d.attention_per_context[key],
                                           rel=1e-5, abs=1e-7)


def test_engine_counts_rows_tokenized_natively(native_on):
    with native_on.serving_engine(tiers=('topk',),
                                  max_delay_ms=0.0) as engine:
        # loaded with the reader, in set-up: never on a request
        assert engine.reader.native
        direct = native_on.predict(PREDICT_LINES)
        served = engine.predict(PREDICT_LINES, timeout=60)
        engine.predict(PREDICT_LINES[:1], timeout=60)
        stats = engine.stats()
    assert stats['tokenize_native_rows_total'] == 4
    assert stats['tokenize_fallback_rows_total'] == 0
    for s, d in zip(served, direct):
        assert s.topk_predicted_words == d.topk_predicted_words


def test_engine_serves_through_the_fallback_without_the_library(
        model, monkeypatch):
    """A host with no toolchain: ``READER_USE_NATIVE`` is on, the library
    is not there, and the engine serves the same answers through the
    Python tokenizer and says so."""
    from code2vec_tpu.data import native
    direct = model.predict(AWKWARD_LINES)      # the module's fallback
    monkeypatch.setattr(model.config, 'READER_USE_NATIVE', True)
    monkeypatch.setattr(native, 'is_available', lambda: False)
    with model.serving_engine(tiers=('attention',),
                              max_delay_ms=0.0) as engine:
        assert not engine.reader.native
        served = engine.predict(AWKWARD_LINES, tier='attention',
                                timeout=60)
        stats = engine.stats()
    assert stats['tokenize_fallback_rows_total'] == len(AWKWARD_LINES)
    assert stats['tokenize_native_rows_total'] == 0
    for s, d in zip(served, direct):
        assert s.original_name == d.original_name
        assert s.topk_predicted_words == d.topk_predicted_words
        assert s.attention_per_context == d.attention_per_context
