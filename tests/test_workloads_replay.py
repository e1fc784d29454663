"""Scenario traffic plane, mesh half (ISSUE 20, WORKLOADS.md): the
recorded-then-replayed round trip through a live ServingMesh, replay
determinism (bit-identical admitted set AND bit-identical results), a
mixed Java+C# stream with ZERO post-warmup compiles, retrieval-blend
weight=0 bit-parity against the plain softmax path, and the typed
no-index fallback.  Most drills use tiny in-code profiles; the last
replays the full synthetic corpus (tests/test_bench_smoke.py budgets
this file's tier-1 wall time)."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu.config import Config  # noqa: E402
from code2vec_tpu.telemetry import core as tele_core  # noqa: E402
from code2vec_tpu.telemetry.jit_tracker import \
    install_compile_listener  # noqa: E402
from code2vec_tpu.workloads import blend as blend_lib  # noqa: E402
from code2vec_tpu.workloads import profile as profile_lib  # noqa: E402
from code2vec_tpu.workloads import replay as replay_lib  # noqa: E402
from tests.test_serving_memo import _FakeIndex  # noqa: E402
from tests.test_train_overfit import make_dataset  # noqa: E402

JAVA_LINES = [
    'get|a toka0,pA,toka1 toka1,pB,toka2',
    'run|c tokc0,pC,tokc1 tokc2,pA,tokc0',
]
CSHARP_LINES = [
    'set|b tokb0,pA,tokb1',
    'read|d tokd0,pB,tokd1 tokd1,pC,tokd2',
]


def _mixed_records():
    """A tiny in-code mixed Java+C# profile covering all three entry
    points (predict / blend), with labels from the line heads."""
    records = []
    t = 0.0
    for line in JAVA_LINES:
        records.append({'t': t, 'scenario': 'java_naming',
                        'language': 'java', 'lines': [line],
                        'label': line.split(' ', 1)[0]})
        t += 0.001
    for line in CSHARP_LINES:
        records.append({'t': t, 'scenario': 'csharp_naming',
                        'language': 'csharp', 'lines': [line],
                        'label': line.split(' ', 1)[0]})
        t += 0.001
    for line, language in ((JAVA_LINES[0], 'java'),
                           (CSHARP_LINES[0], 'csharp')):
        records.append({'t': t, 'scenario': 'retrieval_naming',
                        'language': language, 'lines': [line],
                        'label': line.split(' ', 1)[0],
                        'weight': 0.5, 'k': 4})
        t += 0.001
    return records


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('workloads_replay'))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8',
        # SLO targets ON so the replay report carries per-scenario
        # error-budget burn attribution (generous: burn math, not
        # alert flakes, is under test)
        SERVING_SLO_AVAILABILITY=0.5, SERVING_SLO_P99_MS=60_000.0)
    return Code2VecModel(config)


@pytest.fixture(scope='module')
def mesh(model):
    """One warmed mesh with an attached index, shared by the tier-1
    drills (mesh warmup is the expensive part; the memo serving
    bit-identical answers across tests is the tier's contract)."""
    tele_core.reset()
    tele_core.enable()
    assert install_compile_listener()
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              memo_cache_bytes=8 << 20)
    try:
        vec = mesh.predict([JAVA_LINES[0]], tier='vectors',
                           timeout=60)[0].code_vector
        mesh.attach_index(_FakeIndex(dim=vec.shape[0]))
        yield mesh
    finally:
        mesh.close()
        tele_core.disable()
        tele_core.reset()


# ------------------------------------------------ typed no-index path
def test_blend_fallback_without_index(model):
    """No attached index degrades TYPED (source='softmax_fallback' +
    counter), never raises — a profile with retrieval_naming records
    replays against an index-less mesh and still answers."""
    tele_core.reset()
    tele_core.enable()
    mesh = model.serving_mesh(replicas=1, tiers=('topk',),
                              memo_cache_bytes=4 << 20)
    try:
        rows = mesh.submit_blended(JAVA_LINES).result(60)
        assert [r.source for r in rows] == \
            [blend_lib.SOURCE_FALLBACK] * len(JAVA_LINES)
        snap = tele_core.registry().snapshot()
        assert snap.get('mesh/blend_fallback_total', 0) >= 1
        # the fallback rows still rank: softmax words/scores untouched
        for row in rows:
            np.testing.assert_array_equal(
                row.predicted_scores, row.base.topk_predicted_words_scores)
        with pytest.raises(ValueError):
            mesh.submit_blended(JAVA_LINES, weight=1.5)
    finally:
        mesh.close()
        tele_core.disable()
        tele_core.reset()


# ------------------------------------------------- weight=0 bit-parity
def test_blend_weight_zero_is_bit_identical_to_softmax(mesh):
    """The A/B baseline contract: weight=0 short-circuits to the plain
    submit path and wraps the UNTOUCHED result — bit-identical scores,
    source='softmax' (index attached, so NOT the fallback)."""
    plain = mesh.submit(CSHARP_LINES).result(60)
    wrapped = mesh.submit_blended(CSHARP_LINES, weight=0.0).result(60)
    assert len(wrapped) == len(plain)
    for blend_row, base_row in zip(wrapped, plain):
        assert blend_row.source == blend_lib.SOURCE_SOFTMAX
        assert blend_row.predicted_words == \
            list(base_row.topk_predicted_words)
        np.testing.assert_array_equal(
            blend_row.predicted_scores,
            base_row.topk_predicted_words_scores)
    # a real blend on the same mesh re-ranks with neighbor votes and
    # says so
    blended = mesh.submit_blended(CSHARP_LINES, weight=0.5,
                                  k=4).result(60)
    assert all(r.source == blend_lib.SOURCE_BLEND for r in blended)
    assert all(r.neighbors is not None for r in blended)


# ------------------------------------- recorded-then-replayed round trip
def test_record_then_replay_round_trip(mesh, tmp_path):
    """Live traffic -> admission tap -> durable profile -> replay of
    that profile against the same mesh, joined to a per-scenario x
    per-language report."""
    recorder = profile_lib.ProfileRecorder()
    mesh.record_traffic(recorder)
    try:
        futures = [
            mesh.submit([JAVA_LINES[0]], scenario='java_naming',
                        language='java'),
            mesh.submit([CSHARP_LINES[0]], scenario='csharp_naming',
                        language='csharp'),
            mesh.submit_blended([JAVA_LINES[1]], weight=0.5, k=4,
                                scenario='retrieval_naming',
                                language='java'),
            mesh.submit([CSHARP_LINES[1]]),  # unlabeled -> fallback name
        ]
        for future in futures:
            future.result(60)
    finally:
        mesh.record_traffic(None)
    records = recorder.records()
    assert len(records) == 4
    # ONE tap record per caller-visible request: the blend's inner
    # submit + submit_neighbors legs must not re-record
    assert [r['scenario'] for r in records] == \
        ['java_naming', 'csharp_naming', 'retrieval_naming',
         'softmax_naming']
    assert records[2]['weight'] == 0.5 and records[2]['k'] == 4
    # labels recovered from the context-line heads at admission
    assert records[0]['label'] == 'get|a'
    path = str(tmp_path / 'recorded.jsonl')
    assert recorder.save(path) == 4
    header, loaded = profile_lib.read_profile(path)
    assert header['source'] == 'recorded'
    report = replay_lib.replay(mesh, loaded, pace=False)
    assert report['admitted'] == 4
    cells = report['scenarios']
    assert cells['java_naming']['java']['delivered'] == 1
    assert cells['csharp_naming']['csharp']['delivered'] == 1
    assert cells['retrieval_naming']['java']['delivered'] == 1
    assert cells['softmax_naming']['-']['delivered'] == 1
    # every labeled record scored against its recorded label
    for name in ('java_naming', 'csharp_naming', 'retrieval_naming'):
        cell = next(iter(cells[name].values()))
        assert cell['scored'] == 1
        assert 0.0 <= cell['f1'] <= 1.0
    # identical requests were served once live already: the replay is
    # memo traffic, visible in the per-scenario hit rate
    assert cells['java_naming']['java']['memo_hit_rate'] == 1.0
    # per-scenario SLO burn attribution rides the report
    assert 'java_naming' in report['slo']['scenarios']
    assert report['slo']['scenarios']['java_naming']['good'] >= 1


# ----------------------------------------------------- determinism drill
def test_replay_determinism_bit_identical_results(mesh):
    """Same profile + same seed => the identical admitted set (plan
    fingerprint) AND bit-identical per-request results — the memo
    tier's cache-serve bit-identity extended to whole replays."""
    records = _mixed_records()
    plan_a = replay_lib.plan_replay(records, rate_scale=4.0, seed=11)
    plan_b = replay_lib.plan_replay(records, rate_scale=4.0, seed=11)
    assert replay_lib.admitted_fingerprint(plan_a) == \
        replay_lib.admitted_fingerprint(plan_b)

    def run_words_scores():
        out = []
        for _t, record in plan_a:
            if record['scenario'] == 'retrieval_naming':
                rows = mesh.submit_blended(
                    record['lines'], weight=record['weight'],
                    k=record['k'], scenario='retrieval_naming',
                    language=record.get('language')).result(60)
                out.append((list(rows[0].predicted_words),
                            np.asarray(rows[0].predicted_scores)))
            else:
                rows = mesh.submit(
                    record['lines'], scenario=record['scenario'],
                    language=record.get('language')).result(60)
                out.append((list(rows[0].topk_predicted_words),
                            np.asarray(
                                rows[0].topk_predicted_words_scores)))
        return out

    first = run_words_scores()
    second = run_words_scores()
    for (words_a, scores_a), (words_b, scores_b) in zip(first, second):
        assert words_a == words_b
        np.testing.assert_array_equal(scores_a, scores_b)
    # the aggregated reports agree on every deterministic field
    rep_a = replay_lib.replay(mesh, records, rate_scale=4.0, seed=11,
                              pace=False)
    rep_b = replay_lib.replay(mesh, records, rate_scale=4.0, seed=11,
                              pace=False)
    assert rep_a['fingerprint'] == rep_b['fingerprint']
    for name, langs in rep_a['scenarios'].items():
        for language, cell in langs.items():
            other = rep_b['scenarios'][name][language]
            for key in ('requests', 'delivered', 'shed', 'scored',
                        'exact_match', 'f1'):
                assert cell[key] == other[key], (name, language, key)


# ------------------------------------- mixed stream, zero new compiles
def test_mixed_stream_zero_postwarm_compiles(mesh):
    """Java and C# records ride the SAME compiled buckets (path
    contexts are language-agnostic at serve time): a mixed-scenario
    steady state triggers zero post-warmup compiles (acceptance)."""
    compiles = tele_core.registry().counter('jit/compiles_total')
    # warm every entry path the mixed profile uses (shared mesh is
    # already warm from earlier drills; this makes the test order-
    # independent rather than relying on it)
    mesh.submit([JAVA_LINES[0]]).result(60)
    mesh.submit_blended([JAVA_LINES[0]], weight=0.5, k=4).result(60)
    warm = compiles.value
    report = replay_lib.replay(mesh, _mixed_records(), pace=False)
    assert compiles.value - warm == 0
    assert report['admitted'] == 6
    # both languages answered in the same steady state
    assert report['scenarios']['java_naming']['java']['delivered'] == 2
    assert report['scenarios']['csharp_naming']['csharp'][
        'delivered'] == 2
    assert report['scenarios']['retrieval_naming']['java'][
        'delivered'] == 1
    assert report['scenarios']['retrieval_naming']['csharp'][
        'delivered'] == 1


# ------------------------------------------------------------ full drill
@pytest.mark.skipif(
    not os.path.exists(os.path.join(REPO, 'extractor', 'build',
                                    'c2v-extract')),
    reason='native extractor not built')
def test_full_synthetic_replay_drill(model, tmp_path):
    """The full pipeline at real (paced) rates: synthetic mixed-corpus
    profile -> durable file -> paced replay with rate scaling against
    a fresh mesh, reporting quality, hit-rate, shed, p99, and SLO
    burn per scenario x language."""
    records = profile_lib.build_synthetic_profile(
        model.config, str(tmp_path / 'corpus'),
        classes_per_language=2, seed=5, rate_rps=40.0)
    assert {r['language'] for r in records} == {'java', 'csharp'}
    path = str(tmp_path / 'synthetic.jsonl')
    profile_lib.write_profile(path, records,
                              meta={'source': 'synthetic'})
    _header, loaded = profile_lib.read_profile(path)
    tele_core.reset()
    tele_core.enable()
    mesh = model.serving_mesh(replicas=1, tiers=('topk', 'vectors'),
                              memo_cache_bytes=8 << 20)
    try:
        vec = mesh.predict([loaded[0]['lines'][0]], tier='vectors',
                           timeout=60)[0].code_vector
        mesh.attach_index(_FakeIndex(dim=vec.shape[0]))
        report = replay_lib.replay(mesh, loaded, rate_scale=8.0,
                                   seed=5, pace=True, timeout_s=120.0)
        assert report['admitted'] == len(loaded)
        for name in ('java_naming', 'csharp_naming'):
            cell = next(iter(report['scenarios'][name].values()))
            assert cell['delivered'] + cell['shed'] + cell['errors'] \
                == cell['requests']
            assert cell['p99_ms'] >= cell['p50_ms'] >= 0.0
        assert report['slo']['good_total'] > 0
        # paced replays of the same profile share one fingerprint
        again = replay_lib.plan_replay(loaded, rate_scale=8.0, seed=5)
        assert replay_lib.admitted_fingerprint(again) == \
            report['fingerprint']
    finally:
        mesh.close()
        tele_core.disable()
        tele_core.reset()
