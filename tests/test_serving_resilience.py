"""Serving resilience drills (serving/engine.py + serving/errors.py,
ISSUE 7): deterministic overload (bounded queue + injected
``slow_dispatch`` -> shed/expiry/degrade with typed errors), canaried
zero-downtime checkpoint rollover with ZERO post-warmup XLA compiles,
fail-fast vs drain close semantics, and the submit/close/attach_index
stress test. The extractor-bridge drills live in
tests/test_extractor_resilience.py; the fault-window grammar they all
ride is unit-tested here too."""
import threading
import time

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.resilience import faults
from code2vec_tpu.serving.errors import (DeadlineExceeded, EngineClosed,
                                         EngineOverloaded, ServingError)
from tests.serving_slots import decode_slots_held
from tests.test_train_overfit import make_dataset

PREDICT_LINES = [
    'get|a toka0,pA,toka1 toka1,pB,toka2',
    'set|b tokb0,pA,tokb1',
    'run|c tokc0,pC,tokc1 tokc2,pA,tokc0 tokc1,pB,tokc2',
]


@pytest.fixture(autouse=True)
def clear_fault_plan():
    """The plan is process-global by design: every test starts and ends
    disarmed."""
    faults.configure('')
    yield
    faults.configure('')


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('serving_res'))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,16')
    return Code2VecModel(config)


def _wait_until(predicate, timeout=10.0, what='condition'):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if predicate():
            return
        time.sleep(0.001)
    raise AssertionError('timed out waiting for %s' % what)


def _stall_dispatcher(engine, line):
    """Submit one plug request and wait until the dispatcher POPPED it —
    at which point it is inside the injected ``slow_dispatch`` stall and
    everything submitted next queues behind the stall deterministically.
    Returns the plug future."""
    plug = engine.submit([line], tier='topk')
    # queue depth drops to 0 at pop time, before the stall sleep
    _wait_until(lambda: engine.queue_depth.snapshot() == 0,
                what='dispatcher to pop the plug batch')
    return plug


# ----------------------------------------------------- fault-window grammar
def test_fault_window_parse():
    assert faults.parse_spec('extractor_crash@call=0..2') == {
        'extractor_crash': (0, 2)}
    assert faults.parse_spec(
        'slow_dispatch@req=1..1,nan_loss@step=7') == {
            'slow_dispatch': (1, 1), 'nan_loss': 7}
    with pytest.raises(ValueError):
        faults.parse_spec('slow_dispatch@req=3..1')   # hi < lo
    with pytest.raises(ValueError):
        faults.parse_spec('slow_dispatch@req=-1..2')  # negative lo
    with pytest.raises(ValueError):
        faults.parse_spec('no_such_point@call=0..1')  # unknown point


def test_fault_window_fires_every_count_inside_then_disarms():
    faults.configure('slow_dispatch@req=1..2')
    fired = [faults.maybe_fire('slow_dispatch') for _ in range(5)]
    assert fired == [False, True, True, False, False]


def test_fault_single_shot_still_single_shot():
    faults.configure('slow_dispatch@req=1')
    fired = [faults.maybe_fire('slow_dispatch') for _ in range(4)]
    assert fired == [False, True, False, False]


# ---------------------------------------------------------- admission drills
def test_reject_all_drill_sheds_typed(model):
    with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                              queue_bound=64) as engine:
        faults.configure('reject_all@req=0..1')
        for _ in range(2):
            with pytest.raises(EngineOverloaded):
                engine.submit(PREDICT_LINES[:1], tier='topk')
        # window passed: traffic flows again
        results = engine.predict(PREDICT_LINES[:1], tier='topk',
                                 timeout=60)
        assert results[0].topk_predicted_words
        assert engine.stats()['shed_total'] == 2


def test_drain_estimate_sheds_undeliverable_deadline(model):
    with model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                              queue_bound=64) as engine:
        engine.predict(PREDICT_LINES[:1], tier='topk', timeout=60)
        # pin the observed service rate at 1 row/s: any multi-row
        # deadlined request is then hopeless at admission
        with engine._lock:
            engine._service_rows_per_s = 1.0
        with pytest.raises(EngineOverloaded, match='drain estimate'):
            engine.submit(PREDICT_LINES, tier='topk', deadline_ms=100.0)
        # no deadline -> no drain check: the same submission is admitted
        assert len(engine.predict(PREDICT_LINES, tier='topk',
                                  timeout=60)) == 3


def test_service_rate_aggregates_parallel_completions(model):
    """Regression: with SERVING_DECODE_WORKERS > 1, near-simultaneous
    batch completions span microseconds — a per-completion-gap rate
    would explode by orders of magnitude and admit deadlines the queue
    cannot meet. The estimator aggregates over a sliding window and
    keeps the (low-biased) sojourn seed until the window spans a
    measurable interval."""
    import types
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  warmup=False)
    try:
        req = types.SimpleNamespace(t_enqueue=time.perf_counter() - 1.0)
        engine._note_service(100, [req])  # seeds ~100 rows/s (sojourn)
        for _ in range(8):                # a burst microseconds apart
            engine._note_service(100, [req])
        rate = engine._service_rows_per_s
        assert rate < 1000, 'burst inflated the service rate: %r' % rate
        # once the window spans real time it reports honest throughput
        time.sleep(0.06)
        engine._note_service(100, [req])
        assert engine._service_rows_per_s > rate
    finally:
        engine.close()


def test_oversize_request_admitted_alone_then_bounds_queue(model):
    """The admission bound rejects pile-up, not request size: a single
    request larger than the whole bound keeps submit's oversize-
    splitting contract on an idle queue, and while it drains everything
    behind it is shed."""
    lines = PREDICT_LINES * 2  # 6 rows > bound
    bound = 4
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  queue_bound=bound)
    try:
        faults.configure('slow_dispatch@req=0..63')
        plug = _stall_dispatcher(engine, PREDICT_LINES[0])
        # queue is empty (plug already popped): the oversize is admitted
        oversize = engine.submit(lines, tier='topk')
        # ... and now ITS size bounds the queue: pile-up behind it sheds
        with pytest.raises(EngineOverloaded):
            engine.submit(PREDICT_LINES[:1], tier='topk')
        faults.configure('')
        results = oversize.result(timeout=60)
        assert [r.original_name for r in results] == \
            [model.predict([line])[0].original_name for line in lines]
        plug.result(timeout=60)
        assert engine.stats()['shed_total'] == 1
    finally:
        faults.configure('')
        engine.close()


def test_overload_drill_sheds_expires_and_results_bit_identical(model):
    """The ISSUE 7 acceptance drill: bounded queue + injected
    ``slow_dispatch``; an open-loop burst sheds at admission and expires
    deadlined queued work with typed errors, queue depth never exceeds
    the bound, and every ADMITTED request's results are bit-identical to
    the unloaded path."""
    line = PREDICT_LINES[0]
    unloaded = model.predict([line])[0]
    bound = 8
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  queue_bound=bound)
    try:
        faults.configure('slow_dispatch@req=0..63')
        plug = _stall_dispatcher(engine, line)
        # 4 deadlined requests queue behind the >=250ms stall with a
        # 60ms SLO: they MUST expire at pop time, never dispatch
        doomed = [engine.submit([line], tier='topk', deadline_ms=60.0)
                  for _ in range(4)]
        # open-loop burst of undeadlined singles: 4 more fill the bound
        # (4 deadlined rows are already queued), the rest shed
        admitted, shed = [], 0
        for _ in range(10):
            try:
                admitted.append(engine.submit([line], tier='topk'))
            except EngineOverloaded:
                shed += 1
        assert shed == 6 and len(admitted) == 4
        peak = engine.stats()['queue_peak_rows']
        assert peak <= bound, 'queue overshot the bound: %d' % peak
        for future in doomed:
            assert isinstance(future.exception(timeout=60),
                              DeadlineExceeded)
        for future in admitted + [plug]:
            (result,) = future.result(timeout=60)
            assert result.original_name == unloaded.original_name
            assert result.topk_predicted_words == \
                unloaded.topk_predicted_words
            np.testing.assert_array_equal(
                result.topk_predicted_words_scores,
                unloaded.topk_predicted_words_scores)
        stats = engine.stats()
        assert stats['shed_total'] == 6
        assert stats['expired_total'] == 4
    finally:
        faults.configure('')
        engine.close()


def test_degradation_ladder_downgrades_full_under_sustained_load(model):
    """Past 75% queue fill the ladder serves 'full' as 'topk' (typed in
    _DEGRADE_LADDER), and drops back once the queue drains."""
    line = PREDICT_LINES[0]
    engine = model.serving_engine(
        tiers=('topk', 'attention', 'full'), max_delay_ms=0.0,
        queue_bound=8)
    try:
        faults.configure('slow_dispatch@req=0..63')
        plug = _stall_dispatcher(engine, line)
        backlog = [engine.submit([line], tier='topk') for _ in range(6)]
        # 6 queued + 1 reserved = 7/8 fill >= 0.75: overload level 2
        degraded = engine.submit([line], tier='full')
        assert engine.stats()['overload_level'] == 2
        assert engine.stats()['degraded_total'] == 1
        (result,) = degraded.result(timeout=60)
        # served as bare topk: no attention decode, no code vector
        assert result.attention_per_context == {}
        assert result.code_vector is None
        for future in backlog + [plug]:
            future.result(timeout=60)
    finally:
        faults.configure('')
        engine.close()
    # a fresh unloaded engine serves 'full' at full fidelity again
    with model.serving_engine(tiers=('topk', 'full'),
                              max_delay_ms=0.0) as calm:
        (result,) = calm.predict([line], tier='full', timeout=60)
        assert result.attention_per_context != {}
        assert result.code_vector is not None


# ------------------------------------------------------------ close semantics
def test_default_close_fails_queued_futures_typed(model):
    line = PREDICT_LINES[0]
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0)
    faults.configure('slow_dispatch@req=0..63')
    plug = _stall_dispatcher(engine, line)
    queued = [engine.submit([line], tier='topk') for _ in range(3)]
    engine.close()
    # the in-flight batch still delivers; the queued ones fail typed
    assert plug.result(timeout=60)[0].topk_predicted_words
    for future in queued:
        assert isinstance(future.exception(timeout=10), EngineClosed)
    with pytest.raises(EngineClosed):
        engine.submit([line], tier='topk')
    assert not engine._dispatcher.is_alive()


def test_close_drain_serves_everything_admitted(model):
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=10_000.0)
    with decode_slots_held(engine, PREDICT_LINES[0]) as held:
        # every decode slot taken: parked in the coalescing window,
        # nothing of these dispatched yet
        futures = [engine.submit([line], tier='topk')
                   for line in PREDICT_LINES]
        time.sleep(0.05)
        assert engine.stats()['batches_total'] == held.batches
        closer = threading.Thread(target=engine.close,
                                  kwargs={'drain': True})
        closer.start()
        # the drain ends the wait with the slots still held, ten
        # seconds before the deadline
        _wait_until(
            lambda: engine.stats()['batches_total'] > held.batches,
            what='the draining close to dispatch the parked requests')
    closer.join(timeout=60)
    assert not closer.is_alive()
    for future, line in zip(futures, PREDICT_LINES):
        (result,) = future.result(timeout=60)
        assert result.topk_predicted_words == \
            model.predict([line])[0].topk_predicted_words
    assert not engine._dispatcher.is_alive()


def test_concurrent_submit_close_attach_index_stress(model):
    """Satellite: racing submit()/close()/attach_index() must resolve
    EVERY returned future (result or typed ServingError) and leak no
    dispatcher thread."""

    class _FakeIndex:
        labels = np.array(['m'], dtype=object)

        def search(self, vectors, k):
            n = vectors.shape[0]
            return (np.zeros((n, k), np.float32),
                    np.zeros((n, k), np.int64))

    engine = model.serving_engine(tiers=('topk', 'vectors'),
                                  max_delay_ms=1.0)
    futures = []
    futures_lock = threading.Lock()
    begun = threading.Barrier(6)  # 4 submitters + attacher + main

    def submitter(i):
        begun.wait()
        while True:
            try:
                future = engine.submit(
                    [PREDICT_LINES[i % len(PREDICT_LINES)]], tier='topk')
            except EngineClosed:
                return
            except EngineOverloaded:
                continue
            with futures_lock:
                futures.append(future)

    def attacher():
        begun.wait()
        for _ in range(50):
            engine.attach_index(_FakeIndex())
            time.sleep(0.001)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(4)] + [threading.Thread(target=attacher)]
    for thread in threads:
        thread.start()
    begun.wait()
    time.sleep(0.25)  # let traffic flow
    engine.close()
    for thread in threads:
        thread.join(timeout=60)
        assert not thread.is_alive()
    assert futures, 'stress produced no traffic'
    unresolved = [f for f in futures if not f.done()]
    assert not unresolved, '%d futures left unresolved' % len(unresolved)
    for future in futures:
        exc = future.exception()
        assert exc is None or isinstance(exc, ServingError), repr(exc)
    assert not engine._dispatcher.is_alive()
    assert not any(t.name.startswith('serving-dispatch')
                   for t in threading.enumerate())


# ------------------------------------------------------------------ rollover
def test_rollover_canary_swap_rollback_and_zero_compiles(model):
    """Acceptance: a LIVE load_params rollover (canary pass -> swap, and
    canary fail -> rollback) adds ZERO XLA compiles after warmup — the
    shadow dispatches reuse the warm ladder."""
    import jax
    from code2vec_tpu.telemetry import core
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener
    lines = PREDICT_LINES
    core.reset()
    core.enable()
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0)
    try:
        assert install_compile_listener()
        compiles = core.registry().counter('jit/compiles_total')
        # candidates are built BEFORE the compile snapshot: the -leaf op
        # itself compiles a (tiny) program that is not rollover machinery
        same = jax.tree_util.tree_map(lambda leaf: leaf, model.params)
        broken = jax.tree_util.tree_map(lambda leaf: -leaf, model.params)
        import jax as _jax
        _jax.block_until_ready(broken)
        before = engine.predict(lines, tier='topk', timeout=60)
        warm_compiles = compiles.value

        # ---- canary PASS: identical params agree 100% -> swap
        handle = engine.load_params(same, canary_batches=2,
                                    min_agreement=0.9)
        for _ in range(3):  # live traffic feeds the canary
            engine.predict(lines, tier='topk', timeout=60)
        report = handle.result(timeout=60)
        assert report['swapped'] is True
        assert report['agreement'] == pytest.approx(1.0)
        assert report['rows'] >= 2 * len(lines)
        assert engine.params is same

        # ---- canary FAIL: negated params disagree -> rollback
        handle = engine.load_params(broken, canary_batches=2,
                                    min_agreement=0.9)
        for _ in range(3):
            engine.predict(lines, tier='topk', timeout=60)
        report = handle.result(timeout=60)
        assert report['swapped'] is False
        assert report['agreement'] < 0.9
        assert engine.params is same  # rollback kept the serving set
        stats = engine.stats()
        assert stats['rollover_total'] == 1
        assert stats['rollover_rollbacks_total'] == 1

        # ---- the whole double rollover compiled NOTHING new
        assert compiles.value - warm_compiles == 0, (
            '%d XLA compiles during live rollover'
            % (compiles.value - warm_compiles))
        after = engine.predict(lines, tier='topk', timeout=60)
        for a, b in zip(before, after):
            assert a.topk_predicted_words == b.topk_predicted_words
            np.testing.assert_array_equal(a.topk_predicted_words_scores,
                                          b.topk_predicted_words_scores)
    finally:
        engine.close()
        core.disable()
        core.reset()


def test_canary_rejected_on_vectors_only_engine(model):
    """A vectors-only engine produces no top-1 predictions to canary
    against: an armed canary would never conclude and wedge every later
    rollover, so load_params must reject it loudly (canary_batches=0
    still swaps)."""
    import jax
    engine = model.serving_engine(tiers=('vectors',), max_delay_ms=0.0,
                                  warmup=False)
    try:
        same = jax.tree_util.tree_map(lambda leaf: leaf, model.params)
        with pytest.raises(RuntimeError, match='vectors-only'):
            engine.load_params(same, canary_batches=2)
        report = engine.load_params(same, canary_batches=0).result(10)
        assert report['swapped'] is True
    finally:
        engine.close()


def test_rollover_api_guards(model):
    import jax
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0,
                                  warmup=False)
    same = jax.tree_util.tree_map(lambda leaf: leaf, model.params)
    # no param source on this engine config? the module fixture has no
    # save/load path only when neither is set — here TRAIN prefix only,
    # so step refs must fail loudly while pytrees work
    if engine._param_source is None:
        with pytest.raises(RuntimeError, match='param source'):
            engine.load_params(7)
        with pytest.raises(RuntimeError, match='param source'):
            engine.follow_checkpoints(poll_secs=1.0)
    armed = engine.load_params(same, canary_batches=5)
    with pytest.raises(RuntimeError, match='already in flight'):
        engine.load_params(same, canary_batches=5)
    engine.close()
    # close() fails the armed canary typed, and post-close loads reject
    assert isinstance(armed.exception(timeout=10), EngineClosed)
    with pytest.raises(EngineClosed):
        engine.load_params(same, canary_batches=0)


def test_param_source_step_rollover_and_follow(tmp_path_factory):
    """End-to-end param source: retained steps resolve by number, the
    newest-step poll sees new saves, and --serve-follow-checkpoints
    rolls them in live (canary disabled for determinism)."""
    import jax.numpy as jnp
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('rollsrc'))
    save_path = str(tmp_path_factory.mktemp('rollsrc_model') / 'model')
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), MODEL_SAVE_PATH=save_path,
        DL_FRAMEWORK='jax', COMPUTE_DTYPE='float32', MAX_CONTEXTS=6,
        TRAIN_BATCH_SIZE=16, TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1,
        SHUFFLE_BUFFER_SIZE=64, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8', SERVING_CANARY_BATCHES=0)
    model = Code2VecModel(config)
    model.save(state=model.state, epoch=0, wait=True)  # step 0
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0)
    try:
        source = engine._param_source
        assert source is not None
        assert source.newest_step() == 0
        report = engine.load_params(0).result(timeout=60)
        assert report['swapped'] is True and report['step'] == 0
        assert engine.stats()['params_step'] == 0
        with pytest.raises(ValueError, match='step 7'):
            engine.load_params(7).result(timeout=60)
        # a newer save appears; the follow poller rolls it in live
        newer = model.state._replace(step=jnp.asarray(9, jnp.int32))
        model.save(state=newer, epoch=0, wait=True)
        assert source.newest_step() == 9
        engine.follow_checkpoints(poll_secs=0.05)
        _wait_until(lambda: engine.stats()['params_step'] == 9,
                    timeout=30.0, what='follow-checkpoints rollover')
    finally:
        engine.close()
        model.close_stores()


def test_follow_single_poller_and_transient_load_retry(tmp_path_factory):
    """Regressions: concurrent follow_checkpoints() calls must start
    exactly ONE poller thread (the check-and-assign is locked; close()
    only joins the stored one), and a step whose restore fails
    transiently — a poll racing an in-progress checkpoint write, a
    filesystem blip — must stay eligible for the next poll instead of
    being marked attempted and skipped forever."""
    import jax.numpy as jnp
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('followretry'))
    save_path = str(tmp_path_factory.mktemp('followretry_model') / 'model')
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), MODEL_SAVE_PATH=save_path,
        DL_FRAMEWORK='jax', COMPUTE_DTYPE='float32', MAX_CONTEXTS=6,
        TRAIN_BATCH_SIZE=16, TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1,
        SHUFFLE_BUFFER_SIZE=64, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8', SERVING_CANARY_BATCHES=0)
    model = Code2VecModel(config)
    model.save(state=model.state, epoch=0, wait=True)  # step 0
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0)
    try:
        source = engine._param_source
        real_load = source.load
        blips = {'left': 2}

        def flaky_load(ref):
            if blips['left'] > 0:
                blips['left'] -= 1
                raise IOError('transient restore blip')
            return real_load(ref)

        source.load = flaky_load
        newer = model.state._replace(step=jnp.asarray(9, jnp.int32))
        model.save(state=newer, epoch=0, wait=True)
        barrier = threading.Barrier(8)

        def race():
            barrier.wait()
            engine.follow_checkpoints(poll_secs=0.05)

        workers = [threading.Thread(target=race) for _ in range(8)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        pollers = [t for t in threading.enumerate()
                   if t.name == 'serving-follow' and t.is_alive()]
        assert len(pollers) == 1, \
            'duplicate follow pollers: %r' % pollers
        # the first two polls hit the blip; step 9 must still roll in
        _wait_until(lambda: engine.stats()['params_step'] == 9,
                    timeout=30.0, what='retry after transient load blip')
        assert blips['left'] == 0
    finally:
        engine.close()
        model.close_stores()
    # close() joined the (single) registered poller
    assert not any(t.name == 'serving-follow' and t.is_alive()
                   for t in threading.enumerate())
    with pytest.raises(EngineClosed):
        engine.follow_checkpoints(poll_secs=1.0)


def test_canary_timeout_rolls_back_on_vectors_only_traffic(model):
    """A canary armed on a MIXED-tier engine passes the vectors-only
    guard, but pure vectors traffic (submit_neighbors) never scores a
    top-1 comparison: without the timeout the rollover would never
    decide and every later load_params would raise 'already in
    flight' forever."""
    import jax
    engine = model.serving_engine(tiers=('vectors', 'topk'),
                                  max_delay_ms=0.0, warmup=False)
    try:
        same = jax.tree_util.tree_map(lambda leaf: leaf, model.params)
        handle = engine.load_params(same, canary_batches=2)
        engine.canary_timeout_s = 0.05
        time.sleep(0.1)
        # vectors dispatches shadow-score nothing, but DO check the age
        engine.predict(PREDICT_LINES, tier='vectors', timeout=60)
        report = handle.result(timeout=10)
        assert report['swapped'] is False
        assert 'timed out' in report['reason']
        assert engine.rollover_rollbacks_total.value == 1
        # the wedge is gone: a fresh rollover proceeds
        assert engine.load_params(
            same, canary_batches=0).result(10)['swapped'] is True
    finally:
        engine.close()


def test_follow_baseline_skips_already_serving_step(tmp_path_factory):
    """The follow poller starts baselined at the restored step: its
    first poll must NOT pay a restore + canary to re-roll the params
    the engine is already serving, while genuinely newer steps still
    roll in."""
    import jax.numpy as jnp
    from code2vec_tpu.model_api import Code2VecModel
    prefix = make_dataset(tmp_path_factory.mktemp('followbase'))
    save_path = str(tmp_path_factory.mktemp('followbase_model') / 'model')
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), MODEL_SAVE_PATH=save_path,
        DL_FRAMEWORK='jax', COMPUTE_DTYPE='float32', MAX_CONTEXTS=6,
        TRAIN_BATCH_SIZE=16, TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1,
        SHUFFLE_BUFFER_SIZE=64, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8', SERVING_CANARY_BATCHES=0)
    model = Code2VecModel(config)
    model.save(state=model.state, epoch=0, wait=True)  # step 0
    engine = model.serving_engine(tiers=('topk',), max_delay_ms=0.0)
    try:
        assert engine.stats()['params_step'] == 0  # wired baseline
        engine.follow_checkpoints(poll_secs=0.05)
        time.sleep(0.5)  # several polls over the already-serving step
        assert engine.rollover_total.value == 0, \
            'first poll re-rolled the already-serving step'
        newer = model.state._replace(step=jnp.asarray(3, jnp.int32))
        model.save(state=newer, epoch=0, wait=True)
        _wait_until(lambda: engine.stats()['params_step'] == 3,
                    timeout=30.0, what='follow rollover of newer step')
        assert engine.rollover_total.value == 1
    finally:
        engine.close()
        model.close_stores()
