"""Capture the goodput plane's own numbers on the REAL hot loop
(``Trainer.fit`` with telemetry on): the achieved MFU, the goodput
fraction, and the badput breakdown of a steady-state fit window —
the observability layer measuring itself, so a capture records what
"healthy" looks like on this hardware and a later regression has a
baseline to flip against.

Methodology: one trainer, one warmup fit (compiles + capacity
stickiness land there, and are REPORTED as the warmup arm's badput
story), then a measured steady-state fit.  Each fit is one run span in
the ledger; the measures come from that span's ``run_end`` totals and
the MFU gauge of its last flush window — the same numbers
``scripts/goodput_report.py`` renders.

Prints one JSON line per measurement:

  mfu                     model FLOP utilization of the steady fit,
                          last flush window (DEVICE_PEAK_FLOPS
                          denominator — see telemetry/goodput.py)
  goodput_fraction        productive seconds / wall seconds of the
                          steady fit span
  badput_compile_pct      compile badput share of the steady span
  badput_input_wait_pct   input-wait badput share of the steady span
  arithmetic_intensity    train-step FLOPs per HBM byte (AOT
                          cost_analysis)

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


def _spans(intervals_path):
    """Run spans in ledger order, each with its cumulative ``run_end``
    totals and the last finite window MFU inside the span."""
    spans, current = [], None
    with open(intervals_path) as f:
        for line in f:
            try:
                record = json.loads(line)
            except ValueError:
                continue
            kind = record.get('kind')
            if kind == 'run_start':
                current = {'end': None, 'mfu': None}
            elif current is None:
                continue
            elif kind == 'window' and record.get('mfu'):
                current['mfu'] = record['mfu']
            elif kind == 'run_end':
                current['end'] = record
                spans.append(current)
                current = None
    return spans


def main() -> None:
    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower(),
                      'smoke': SMOKE, 'steps_per_window': STEPS}),
          flush=True)
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
        # warmup fit: compiles land in this span's badput, not the
        # measured one's
        state = trainer.fit(state, lambda epoch: iter(batches))
        # steady-state fit: the measured span
        state = trainer.fit(state, lambda epoch: iter(batches))

        spans = _spans(os.path.join(tmp_dir, 'intervals.jsonl'))
        steady, warm = spans[-1], (spans[-2] if len(spans) > 1 else None)
        # run_end totals are per-LEDGER cumulative (one ledger spans
        # both fits); the steady span's own story is its run_end minus
        # the warmup span's
        def delta(field):
            after = steady['end'].get(field, 0.0)
            before = warm['end'].get(field, 0.0) if warm else 0.0
            return after - before

        wall = max(delta('wall_s'), 1e-9)
        print(json.dumps({'measure': 'mfu',
                          'value': round(steady['mfu'] or 0.0, 5)}),
              flush=True)
        print(json.dumps({'measure': 'goodput_fraction',
                          'value': round(delta('productive_s') / wall,
                                         5)}), flush=True)
        steady_badput = steady['end'].get('badput_s', {})
        warm_badput = warm['end'].get('badput_s', {}) if warm else {}
        for kind in ('compile', 'input_wait'):
            secs = steady_badput.get(kind, 0.0) \
                - warm_badput.get(kind, 0.0)
            print(json.dumps(
                {'measure': 'badput_%s_pct' % kind,
                 'value': round(100.0 * secs / wall, 3)}), flush=True)
        print(json.dumps(
            {'measure': 'arithmetic_intensity',
             'value': round(tele.goodput.arithmetic_intensity() or 0.0,
                            3)}), flush=True)


if __name__ == '__main__':
    main()
