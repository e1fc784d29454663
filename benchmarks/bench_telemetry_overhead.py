"""Measure the per-step cost of the telemetry layer in the REAL hot loop
(``Trainer.fit``), telemetry off vs on — the ISSUE 2 acceptance bound is
<1% overhead for the DISABLED path (which must reduce to ``is None``
checks) and the enabled path is reported alongside for honesty.

Methodology: ONE trainer (one compiled step program — building separate
trainers per arm was measured to add ~±10% inter-build variance on CPU,
swamping the signal), with the trainer's telemetry handle toggled
between INTERLEAVED fit windows; the headline per-arm number is the MIN
window (scheduler noise only ever adds time, so min strips it while the
systematic instrumentation cost survives), with the median reported as
the noise floor.

Prints one JSON line per measurement:

  step_ms_fit_telemetry_off   fastest fit window per step, telemetry off
  step_ms_fit_telemetry_on    same trainer/program, telemetry recording +
                              exporters into a temp dir (console line
                              rate-limited away)
  telemetry_overhead_pct      (on - off) / off * 100

BENCH_SMOKE=1 shrinks shapes for CPU validation (same convention as
bench.py).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402

SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
STEPS = 8 if SMOKE else 40
REPEATS = 7 if SMOKE else 5


def main() -> None:
    import statistics

    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower(),
                      'smoke': SMOKE, 'steps_per_window': STEPS,
                      'windows_per_arm': REPEATS}), flush=True)
    with tempfile.TemporaryDirectory() as tmp_dir:
        config = benchlib.headline_config(
            SHAPES, NUM_TRAIN_EPOCHS=1,
            NUM_BATCHES_TO_LOG_PROGRESS=max(2, STEPS // 2),
            TELEMETRY=True, TELEMETRY_DIR=tmp_dir,
            TELEMETRY_FLUSH_EVERY_STEPS=max(2, STEPS // 2),
            TELEMETRY_CONSOLE_EVERY_SECS=3600.0)
        trainer, state = benchlib.build_trainer(config, SHAPES)
        tele = trainer._telemetry
        batches = benchlib.random_batches(SHAPES, STEPS)
        # warmup epoch: compiles + capacity stickiness land here
        state = trainer.fit(state, lambda epoch: iter(batches))

        sw = benchlib.bench_timer('fit')
        windows = {'off': [], 'on': []}
        for _rep in range(REPEATS):
            # interleaved arms decorrelate slow machine-state drift
            for label, handle in (('off', None), ('on', tele)):
                trainer._telemetry = handle
                with sw.time():
                    state = trainer.fit(state,
                                        lambda epoch: iter(batches))
                windows[label].append(sw.last)
        trainer._telemetry = tele

        results = {}
        for label in ('off', 'on'):
            per_step = min(windows[label]) / STEPS
            results[label] = per_step
            print(json.dumps(
                {'measure': 'step_ms_fit_telemetry_%s' % label,
                 'value': round(per_step * 1e3, 3),
                 'p50': round(statistics.median(windows[label])
                              / STEPS * 1e3, 3)}), flush=True)
        off, on = results['off'], results['on']
        print(json.dumps({'measure': 'telemetry_overhead_pct',
                          'value': round((on - off) / off * 100, 2)}),
              flush=True)


if __name__ == '__main__':
    main()
