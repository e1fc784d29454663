"""Training goodput plane tests (ISSUE 17): badput-ledger interval
classification, MFU math against hand-computed FLOPs, the step-time
anomaly watchdog's fire/cooldown contract and its TraceController
auto-capture, the jax-free goodput_report CLI, and the zero-overhead
guarantee with telemetry off."""
import json
import os
import sys
import time
import types

import pytest

from code2vec_tpu.telemetry import core
from code2vec_tpu.telemetry import goodput
from code2vec_tpu.telemetry.trace import TraceController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_registry():
    """Registry + active-ledger reset between tests: both are
    process-global by design, so every test starts and ends clean."""
    core.reset()
    core.enable()
    goodput.deactivate()
    yield
    goodput.deactivate()
    core.reset()
    core.disable()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


def make_ledger(tmp_path=None, **kwargs):
    path = str(tmp_path / 'intervals.jsonl') if tmp_path else None
    clock = FakeClock()
    return goodput.GoodputLedger(path, clock=clock, **kwargs), clock


def read_records(path):
    return [json.loads(line) for line in
            open(path).read().splitlines()]


# ------------------------------------------------- ledger classification
def test_ledger_classifies_every_second(tmp_path):
    """The accounting contract: productive + typed badput == wall, with
    warmup, input-wait excess, compile, eval, and checkpoint each landing
    in their own bucket."""
    led, clock = make_ledger(tmp_path)
    led.run_start(step=0)

    # iteration 1: 0.3s input wait (threshold excess is badput), 0.1s
    # compile inside the step, 1.0s total -> clean remainder is warmup
    led.note_input_wait(0.3)
    led.on_compile(0.1)
    clock.advance(1.0)
    clean, had_compile = led.step_done(1, 1.0)
    assert had_compile
    expected_wait = 0.3 - goodput.INPUT_WAIT_THRESHOLD_S
    assert clean == pytest.approx(1.0 - expected_wait - 0.1)

    # iteration 2: clean 0.5s step -> productive
    led.note_input_wait(0.001)  # under threshold: not badput
    clock.advance(0.5)
    clean, had_compile = led.step_done(2, 0.5)
    assert not had_compile
    assert clean == pytest.approx(0.5)

    # epoch end, outside any iteration: eval then checkpoint intervals
    with led.interval(goodput.KIND_EVAL):
        clock.advance(2.0)
    with led.interval(goodput.KIND_CHECKPOINT):
        clock.advance(0.25)
    led.run_end(step=2)

    snap = led.snapshot()
    bad = snap['badput_s']
    assert bad['input_wait'] == pytest.approx(expected_wait)
    assert bad['compile'] == pytest.approx(0.1)
    assert bad['warmup'] == pytest.approx(1.0 - expected_wait - 0.1)
    assert bad['eval'] == pytest.approx(2.0)
    assert bad['checkpoint'] == pytest.approx(0.25)
    assert snap['productive_s'] == pytest.approx(0.5)
    # honesty check: buckets + productive == ledger wall
    assert snap['productive_s'] + sum(bad.values()) \
        == pytest.approx(snap['wall_s'])
    kinds = [r['kind'] for r in read_records(led._path)]
    assert kinds == ['run_start', 'interval', 'interval', 'run_end']


def test_nested_interval_marks_absorb_into_outermost(tmp_path):
    """model_api's eval funnel runs inside the trainer's eval-callback
    wrap: the wall seconds must count once, under the OUTER kind."""
    led, clock = make_ledger(tmp_path)
    with led.interval(goodput.KIND_EVAL):
        clock.advance(1.0)
        with led.interval(goodput.KIND_CHECKPOINT):
            clock.advance(0.5)
        clock.advance(0.5)
    snap = led.snapshot()
    assert snap['badput_s']['eval'] == pytest.approx(2.0)
    assert snap['badput_s']['checkpoint'] == 0.0
    intervals = [r for r in read_records(led._path)
                 if r['kind'] == 'interval']
    assert len(intervals) == 1 and intervals[0]['type'] == 'eval'


def test_compile_inside_interval_absorbed_not_double_billed():
    """An eval program compiling inside an eval mark: the interval
    already accrues that wall; billing compile too would push the badput
    sum past wall time."""
    led, clock = make_ledger()
    with led.interval(goodput.KIND_EVAL):
        clock.advance(1.0)
        led.on_compile(0.8)
    bad = led.snapshot()['badput_s']
    assert bad['compile'] == 0.0
    assert bad['eval'] == pytest.approx(1.0)


def test_mark_replay_bills_retrained_steps_as_rewind_replay():
    led, clock = make_ledger()
    led.note_input_wait(0.0)
    clock.advance(0.1)
    led.step_done(1, 0.1)  # warmup
    led.mark_replay(2)
    for step in (2, 3):
        led.note_input_wait(0.0)
        clock.advance(0.2)
        led.step_done(step, 0.2)
    led.note_input_wait(0.0)
    clock.advance(0.3)
    led.step_done(4, 0.3)
    snap = led.snapshot()
    assert snap['badput_s']['rewind_replay'] == pytest.approx(0.4)
    assert snap['productive_s'] == pytest.approx(0.3)


def test_run_end_idempotent_per_span(tmp_path):
    """The preempt exit writes run_end with its reason; the fit-finally
    shutdown must not write a second."""
    led, _clock = make_ledger(tmp_path)
    led.run_start()
    led.run_end(step=5, reason='preempt')
    led.run_end(step=5)  # shutdown's duplicate: dropped
    ends = [r for r in read_records(led._path) if r['kind'] == 'run_end']
    assert len(ends) == 1 and ends[0]['reason'] == 'preempt'
    # a new span re-opens
    led.run_start(step=5)
    led.run_end(step=9)
    ends = [r for r in read_records(led._path) if r['kind'] == 'run_end']
    assert len(ends) == 2


def test_harvest_window_rebases_open_interval():
    """A long eval spanning a flush boundary: the elapsed portion bills
    to the closing window, the rest to the next — never double."""
    led, clock = make_ledger()
    led.run_start()
    ctx = led.interval(goodput.KIND_EVAL)
    ctx.__enter__()
    clock.advance(3.0)
    window = led.harvest_window()
    assert window['badput/eval'] == pytest.approx(3.0)
    clock.advance(2.0)
    ctx.__exit__(None, None, None)
    window = led.harvest_window()
    assert window['badput/eval'] == pytest.approx(2.0)
    assert led.snapshot()['badput_s']['eval'] == pytest.approx(5.0)


# --------------------------------------------------------- MFU / roofline
def test_mfu_math():
    # 1e12 flops in 2s against 4 devices of 1e12 peak -> 1/8
    assert goodput.mfu(1e12, 2.0, 1e12, 4) == pytest.approx(0.125)
    assert goodput.mfu(0.0, 1.0, 1e12, 1) == 0.0


def test_resolve_peak_flops_precedence(monkeypatch):
    monkeypatch.delenv(goodput.ENV_DEVICE_PEAK_FLOPS, raising=False)
    # explicit config wins over everything
    assert goodput.resolve_peak_flops(7e12, 'TPU v4') == 7e12
    # env var next
    monkeypatch.setenv(goodput.ENV_DEVICE_PEAK_FLOPS, '9e12')
    assert goodput.resolve_peak_flops(-1.0, 'TPU v4') == 9e12
    monkeypatch.delenv(goodput.ENV_DEVICE_PEAK_FLOPS)
    # then the device-kind table (prefix match)
    assert goodput.resolve_peak_flops(-1.0, 'TPU v4 (chip)') \
        == goodput.KNOWN_DEVICE_PEAK_FLOPS['TPU v4']
    assert goodput.resolve_peak_flops(-1.0, 'TPU v5 lite podslice') \
        == goodput.KNOWN_DEVICE_PEAK_FLOPS['TPU v5 lite']
    # the string the v5e reports (PERF.md "Bring-up", PR 21 chip run)
    assert goodput.resolve_peak_flops(-1.0, 'TPU v5 lite') == 197e12
    # an unknown kind with no knob set is an error, never a default
    with pytest.raises(ValueError, match='FPGA x1'):
        goodput.resolve_peak_flops(-1.0, 'FPGA x1')


def test_program_cost_matches_hand_computed_flops():
    """Lowered.cost_analysis on a plain matmul must report the textbook
    2*M*K*N flops — the foundation the MFU numerator rests on."""
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.training.trainer import Trainer

    m, k, n = 8, 16, 32
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    cost = Trainer._program_cost(jax.jit(jnp.dot), a, b)
    assert cost is not None
    assert cost['flops'] == pytest.approx(2 * m * k * n)
    assert cost['bytes_accessed'] > 0


def test_ledger_window_flops_follow_dispatch_shape():
    led, clock = make_ledger()
    led.set_step_cost('packed:64', 100.0, 50.0)
    led.set_step_cost('packed:128', 300.0, 100.0)
    for step, shape in ((1, 'packed:64'), (2, 'packed:128'),
                        (3, 'packed:128')):
        led.note_input_wait(0.0)
        clock.advance(0.1)
        led.step_done(step, 0.1, shape)
    window = led.harvest_window()
    assert window['flops'] == pytest.approx(100.0 + 300.0 + 300.0)
    assert window['steps'] == 3
    assert led.arithmetic_intensity() == pytest.approx(3.0)


# --------------------------------------------------- anomaly watchdog
def _feed_baseline(dog, shape='s', n=20, step_s=0.01, start_step=0):
    for i in range(n):
        assert not dog.observe(shape, step_s, start_step + i)
    return start_step + n


def test_watchdog_fires_once_then_cooldown(tmp_path):
    clock = FakeClock()
    captures = []
    dog = goodput.StepAnomalyWatchdog(
        6.0, cooldown_s=600.0, dump_dir=str(tmp_path),
        on_capture=captures.append, clock=clock)
    step = _feed_baseline(dog)
    # a sustained regression: fires only after `sustain` consecutive
    # outliers, and auto-captures on the first fire
    assert not dog.observe('s', 0.1, step)
    assert not dog.observe('s', 0.1, step + 1)
    assert dog.observe('s', 0.1, step + 2)
    assert captures == [step + 2]
    assert core.registry().counter('goodput/anomalies_total').value == 1
    assert core.registry().counter('goodput/autocaptures_total').value == 1

    # flight dump: fire record + recent window samples
    dump = tmp_path / 'flight_step_anomaly.jsonl'
    records = read_records(dump)
    assert records[0]['kind'] == 'anomaly'
    assert records[0]['autocapture'] is True
    assert records[0]['step'] == step + 2
    assert len(records) > dog.min_samples

    # second anomaly inside the cooldown: counted + dumped, NO capture
    clock.advance(10.0)
    for i in range(3):
        fired = dog.observe('s', 0.1, step + 3 + i)
    assert fired
    assert core.registry().counter('goodput/anomalies_total').value == 2
    assert core.registry().counter('goodput/autocaptures_total').value == 1
    assert captures == [step + 2]
    assert read_records(dump)[0]['autocapture'] is False

    # past the cooldown: the next fire captures again
    clock.advance(600.0)
    for i in range(3):
        fired = dog.observe('s', 0.1, step + 6 + i)
    assert fired
    assert len(captures) == 2


def test_watchdog_interleaved_normal_steps_reset_streak():
    dog = goodput.StepAnomalyWatchdog(6.0, cooldown_s=600.0,
                                      clock=FakeClock())
    step = _feed_baseline(dog)
    assert not dog.observe('s', 0.1, step)
    assert not dog.observe('s', 0.1, step + 1)
    assert not dog.observe('s', 0.01, step + 2)  # streak broken
    assert not dog.observe('s', 0.1, step + 3)
    assert not dog.observe('s', 0.1, step + 4)
    assert core.registry().counter('goodput/anomalies_total').value == 0


def test_watchdog_sigma_zero_disables():
    dog = goodput.StepAnomalyWatchdog(0.0, cooldown_s=600.0,
                                      clock=FakeClock())
    assert not dog.enabled
    for i in range(40):
        assert not dog.observe('s', 10.0, i)


def test_watchdog_baselines_per_shape():
    """A bigger bucket's slower steps are its own normal, not an anomaly
    against the smaller bucket's baseline."""
    dog = goodput.StepAnomalyWatchdog(6.0, cooldown_s=600.0,
                                      clock=FakeClock())
    step = _feed_baseline(dog, shape='packed:64', step_s=0.01)
    # first sightings of a slower shape: baseline still filling
    for i in range(10):
        assert not dog.observe('packed:128', 0.05, step + i)


def test_autocapture_arms_trace_controller_exactly_once(
        tmp_path, monkeypatch):
    """The full anomaly -> profiler-capture path: the watchdog's
    on_capture arms the TraceController at the anomalous step; the next
    maybe_update starts exactly one capture, and the cooldown prevents a
    second."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, 'start_trace',
                        lambda d: calls.append(('start', d)))
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: calls.append(('stop', None)))
    ctl = TraceController(str(tmp_path), trace_at_step=-1, num_steps=2)
    clock = FakeClock()
    dog = goodput.StepAnomalyWatchdog(6.0, cooldown_s=600.0,
                                      on_capture=ctl.request, clock=clock)
    step = _feed_baseline(dog)
    fired_at = None
    for i in range(3):
        if dog.observe('s', 0.1, step + i):
            fired_at = step + i
        ctl.maybe_update(step + i)
    assert fired_at is not None
    # the fire armed the controller at the anomalous step; the trainer's
    # next maybe_update (same batch counter) starts the capture
    for i in range(3, 8):
        dog.observe('s', 0.1, step + i)
        ctl.maybe_update(step + i)
    starts = [c for c in calls if c[0] == 'start']
    assert len(starts) == 1
    assert starts[0][1].endswith('step%d' % fired_at)
    assert [c[0] for c in calls][:2] == ['start', 'stop']


# ------------------------------------------- throughput rate attribution
def test_examples_per_sec_excludes_eval_and_checkpoint_wall(tmp_path):
    """Satellite regression: a slow eval inside the flush window must
    not dilute train/examples_per_sec (the gauge measures train steps,
    not eval wall)."""
    from code2vec_tpu.telemetry.stepwatch import StepTelemetry
    cfg = types.SimpleNamespace(TELEMETRY_DIR=str(tmp_path),
                                TELEMETRY_FLUSH_EVERY_STEPS=100,
                                TELEMETRY_CONSOLE_EVERY_SECS=3600.0)
    st = StepTelemetry(cfg)
    try:
        st.resume()
        st.count_batch(1000, 5000)
        # a fake 8s eval recorded by the ledger's rate-excluded marking
        st.goodput._clock = FakeClock(0.0)
        with st.goodput.interval(goodput.KIND_EVAL):
            st.goodput._clock.advance(8.0)
        # pretend the window spans 10 wall seconds
        st._window_t0 = time.monotonic() - 10.0
        st.flush_now(100)
        rate = st.registry.gauge('train/examples_per_sec').value
        # 1000 examples over (10 - 8) train seconds, not over 10
        assert rate == pytest.approx(1000 / 2.0, rel=0.05)
    finally:
        st.shutdown(100)


# ------------------------------------------------------- report CLI
def _scripts_import(name):
    scripts_dir = os.path.join(REPO, 'scripts')
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    return __import__(name)


def _write_ledger(path, spans):
    with open(path, 'w') as f:
        for record in spans:
            f.write(json.dumps(record) + '\n')


def test_goodput_report_render_json_and_merge(tmp_path, capsys):
    goodput_report = _scripts_import('goodput_report')
    base = {'compile': 3.0, 'input_wait': 0.5, 'checkpoint': 1.0,
            'eval': 2.0, 'rewind': 0.5, 'rewind_replay': 1.0,
            'preempt': 0.2, 'warmup': 0.8}
    _write_ledger(tmp_path / 'intervals.jsonl', [
        {'kind': 'run_start', 'wall': 100.0, 'step': 0},
        {'kind': 'window', 'wall': 110.0, 'step': 50, 'elapsed_s': 10.0,
         'productive_s': 6.0, 'steps': 50, 'flops': 5e12, 'mfu': 0.41,
         'badput_s': {'compile': 3.0}},
        {'kind': 'anomaly', 'wall': 115.0, 'step': 70, 'shape':
         'packed:64', 'step_ms': 120.0, 'median_ms': 10.0,
         'mad_scale_ms': 1.0, 'sigma': 110.0, 'autocapture': True},
        {'kind': 'run_end', 'wall': 120.0, 'step': 90, 'reason':
         'preempt', 'wall_s': 20.0, 'productive_s': 10.0, 'steps': 90,
         'badput_s': base},
        # restart after a 30s scheduler gap; second span crashes (no
        # run_end) and is reconstructed from its windows
        {'kind': 'run_start', 'wall': 150.0, 'step': 90},
        {'kind': 'window', 'wall': 160.0, 'step': 140, 'elapsed_s': 10.0,
         'productive_s': 9.0, 'steps': 50, 'flops': 6e12, 'mfu': 0.5,
         'badput_s': {'input_wait': 0.5}},
    ])
    assert goodput_report.main([str(tmp_path / 'intervals.jsonl')]) == 0
    out = capsys.readouterr().out
    assert 'rewind_replay' in out and 'restart_gap' in out
    assert 'unattributed' in out
    assert 'MFU timeline' in out
    assert 'step-time anomalies (1)' in out
    assert 'profiler capture auto-triggered' in out
    assert 'no run_end record' in out

    assert goodput_report.main([str(tmp_path), '--json']) == 0
    payload = json.loads(capsys.readouterr().out)
    # wall = span1 20 + gap 30 + span2 (windows) 10
    assert payload['wall_s'] == pytest.approx(60.0)
    assert payload['productive_s'] == pytest.approx(19.0)
    assert payload['badput_s']['restart_gap'] == pytest.approx(30.0)
    # honesty row: buckets + productive sum to wall
    total = payload['productive_s'] + sum(payload['badput_s'].values())
    assert total == pytest.approx(payload['wall_s'])

    # multi-process merge: a directory renders every proc's ledger
    _write_ledger(tmp_path / 'intervals.proc1.jsonl', [
        {'kind': 'run_start', 'wall': 100.0, 'step': 0},
        {'kind': 'run_end', 'wall': 120.0, 'step': 90, 'reason': 'done',
         'wall_s': 20.0, 'productive_s': 15.0, 'steps': 90,
         'badput_s': {'compile': 5.0}},
    ])
    assert goodput_report.main([str(tmp_path), '--json']) == 0
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    procs = {json.loads(line)['proc'] for line in lines}
    assert procs == {'proc0', 'proc1'}


def test_goodput_report_missing_dir_fails_typed(tmp_path):
    goodput_report = _scripts_import('goodput_report')
    with pytest.raises(FileNotFoundError):
        goodput_report.main([str(tmp_path)])


def test_flip_verdict_ignores_goodput_columns(tmp_path):
    """A capture round carrying the new goodput measures must not
    confuse the flip ledger: untracked measures are ignored, tracked
    verdicts still settle."""
    flip_verdict = _scripts_import('flip_verdict')
    results = tmp_path / 'results'
    results.mkdir()
    with open(results / 'capture.jsonl', 'w') as f:
        for rec in ({'measure': 'mfu', 'value': 0.42},
                    {'measure': 'goodput_fraction', 'value': 0.93},
                    {'measure': 'badput_compile_pct', 'value': 1.2},
                    {'stage': 'goodput', 'rc': 0,
                     'data': {'measure': 'arithmetic_intensity',
                              'value': 161.0}}):
            f.write(json.dumps(rec) + '\n')
    rc = flip_verdict.main(['--dir', str(results), '--root',
                            str(tmp_path), '--json'])
    # 3 = "all tracked verdicts pending" (this round carried none of
    # them) — the point is a clean exit, not a settle
    assert rc in (0, 3)


# --------------------------------------------------- zero-overhead guard
def test_goodput_inactive_without_telemetry(tmp_path):
    """Telemetry off => no active ledger: every module-level mark site
    reduces to one attribute read and a no-op."""
    assert goodput.active() is None
    goodput.on_compile(1.0)  # no-op, no error
    with goodput.interval(goodput.KIND_EVAL):
        pass
    assert goodput.active() is None
    # and the trainer-side gate: a telemetry-less trainer holds None, so
    # the hot loop never touches goodput objects (same is-None contract
    # as the rest of the telemetry integration)
    assert not os.listdir(str(tmp_path))  # nothing written anywhere


def test_stepwatch_shutdown_deactivates_global_ledger(tmp_path):
    from code2vec_tpu.telemetry.stepwatch import StepTelemetry
    cfg = types.SimpleNamespace(TELEMETRY_DIR=str(tmp_path))
    st = StepTelemetry(cfg)
    st.resume()
    assert goodput.active() is st.goodput
    st.shutdown(0)
    assert goodput.active() is None
    assert not core.enabled()


# ------------------------------------------------- acceptance (slow, e2e)
def _drill_config(tmp_path, **overrides):
    from code2vec_tpu.config import Config
    from tests.test_train_overfit import make_dataset
    prefix = make_dataset(tmp_path)
    defaults = dict(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, SAVE_EVERY_EPOCHS=1000,
        SHUFFLE_BUFFER_SIZE=64, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        TEST_DATA_PATH=str(tmp_path / 'tiny.val.c2v'),
        MODEL_SAVE_PATH=str(tmp_path / 'models' / 'saved_model'),
        TELEMETRY=True, TELEMETRY_DIR=str(tmp_path / 'tele'),
        TELEMETRY_CONSOLE_EVERY_SECS=3600.0)
    defaults.update(overrides)
    return Config(**defaults)


def _read_tags(path):
    by_tag = {}
    for line in open(path).read().splitlines():
        record = json.loads(line)
        by_tag.setdefault(record['tag'], []).append(record)
    return by_tag


def test_goodput_acceptance_rewind_run_reconstructs(tmp_path):
    """ISSUE 17 acceptance: a CPU fit with eval + checkpoints + one
    injected divergence rewind -> the report reconstructs the run
    (buckets sum to wall within 2%, the rewind attributed) and
    train/mfu stays finite with zero post-warmup compiles."""
    import math

    from code2vec_tpu.model_api import Code2VecModel
    config = _drill_config(
        tmp_path, NUM_TRAIN_EPOCHS=8, LEARNING_RATE=0.01,
        SAVE_EVERY_N_STEPS=2, NUM_BATCHES_TO_LOG_PROGRESS=2,
        TELEMETRY_FLUSH_EVERY_STEPS=4, FAULT_INJECT='nan_loss@step=5')
    Code2VecModel(config).train()

    goodput_report = _scripts_import('goodput_report')
    spans = goodput_report.split_spans(goodput_report.load_records(
        str(tmp_path / 'tele' / 'intervals.jsonl')))
    summary = goodput_report.summarize(spans)
    wall = summary['wall_s']
    assert summary['badput_s']['unattributed'] / wall < 0.02
    assert summary['badput_s']['rewind'] > 0
    assert summary['badput_s']['rewind_replay'] > 0
    assert 0 < summary['goodput_fraction'] < 1

    by_tag = _read_tags(tmp_path / 'tele' / 'metrics.jsonl')
    mfus = [r['value'] for r in by_tag['train/mfu']]
    assert mfus and all(math.isfinite(m) and m > 0 for m in mfus)
    # zero post-warmup compiles: the counter is flat over the last
    # half of the run (the rewind restores params, same shapes)
    compiles = [r['value'] for r in by_tag['jit/compiles_total']]
    assert compiles[-1] == compiles[len(compiles) // 2]


@pytest.mark.slow
def test_goodput_acceptance_slow_step_fault_autocaptures_once(tmp_path):
    """ISSUE 17 acceptance: an injected sustained slow-step window
    fires the watchdog, dumps flight_step_anomaly.jsonl, and
    auto-captures EXACTLY one profiler trace (cooldown blocks the
    rest)."""
    import glob

    from code2vec_tpu.model_api import Code2VecModel
    config = _drill_config(
        tmp_path, NUM_TRAIN_EPOCHS=14, NUM_BATCHES_TO_LOG_PROGRESS=4,
        TELEMETRY_FLUSH_EVERY_STEPS=8,
        FAULT_INJECT='slow_step@step=30..44')
    Code2VecModel(config).train()

    tele = tmp_path / 'tele'
    by_tag = _read_tags(tele / 'metrics.jsonl')
    assert by_tag['goodput/anomalies_total'][-1]['value'] >= 1
    assert by_tag['goodput/autocaptures_total'][-1]['value'] == 1
    records = read_records(tele / 'flight_step_anomaly.jsonl')
    assert records[0]['kind'] == 'anomaly'
    assert records[0]['shape'].startswith('packed:')
    trace_dirs = glob.glob(str(tele / 'traces' / 'step*'))
    assert len(trace_dirs) == 1
    assert os.listdir(trace_dirs[0])  # real profiler output landed
    anomalies = [r for r in read_records(tele / 'intervals.jsonl')
                 if r['kind'] == 'anomaly']
    assert sum(1 for a in anomalies if a['autocapture']) == 1
