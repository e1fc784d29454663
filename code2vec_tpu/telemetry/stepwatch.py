"""``StepTelemetry`` — the trainer-facing telemetry bundle.

Owns the hot-loop instruments (phase timers, throughput counters), the
exporters, the jit trackers, and the on-demand trace controller, so the
trainer's integration is: create one of these when ``Config.TELEMETRY``
is on, record into its attributes, call ``after_step``/``flush_now``.
With telemetry off the trainer holds ``None`` and every instrumented
site is a single ``is None`` check.
"""
from __future__ import annotations

import os
import time
from typing import Optional

from code2vec_tpu.telemetry import core
from code2vec_tpu.telemetry import goodput as goodput_lib
from code2vec_tpu.telemetry import memory as memory_lib
from code2vec_tpu.telemetry.exporters import (ConsoleExporter, JsonlExporter,
                                              PrometheusExporter)
from code2vec_tpu.telemetry.jit_tracker import (CapacityTracker,
                                                install_compile_listener)
from code2vec_tpu.telemetry.trace import TraceController


def telemetry_dir(config) -> str:
    """Where telemetry artifacts live: ``TELEMETRY_DIR`` if set, else next
    to the model artifacts (the ``summaries/`` convention of
    metrics_writer.maybe_create), else the CWD."""
    if getattr(config, 'TELEMETRY_DIR', None):
        return config.TELEMETRY_DIR
    if config.is_saving:
        return os.path.join(os.path.dirname(config.MODEL_SAVE_PATH),
                            'telemetry')
    if config.is_loading:
        return os.path.join(config.model_load_dir, 'telemetry')
    return 'telemetry'


class StepTelemetry:
    def __init__(self, config, log=None, process_index: int = 0):
        core.enable()
        install_compile_listener()
        self.log = log or (lambda msg: None)
        self.dir = telemetry_dir(config)
        # multi-host: each process exports its own files, like log.txt
        suffix = '' if process_index == 0 else '.proc%d' % process_index
        reg = core.registry()
        self.registry = reg
        self.batch_wait = reg.timer('step/batch_wait_ms')
        self.h2d = reg.timer('step/h2d_ms')
        self.dispatch = reg.timer('step/dispatch_ms')
        self.sync = reg.timer('step/sync_ms')
        self.step_total = reg.timer('step/total_ms')
        self.steps = reg.counter('train/steps_total')
        self.examples = reg.counter('train/examples_total')
        self.contexts = reg.counter('train/contexts_total')
        self.ring_occupancy = reg.gauge('staging/ring_occupancy')
        self.capacity = CapacityTracker(log=self.log)
        self.trace = TraceController(
            self.dir,
            trace_at_step=getattr(config, 'TELEMETRY_TRACE_AT_STEP', -1),
            num_steps=getattr(config, 'TELEMETRY_TRACE_NUM_STEPS', 5),
            log=self.log)
        # MEM_NOW touch-file ledger snapshots (telemetry/memory.py),
        # polled at the flush cadence like the exporters — and route
        # the ledger's forensic dumps next to the other artifacts
        self.memwatch = memory_lib.MemoryReportController(self.dir,
                                                          log=self.log)
        memory_lib.configure(dump_dir=self.dir)
        self.flush_every = max(1, getattr(config,
                                          'TELEMETRY_FLUSH_EVERY_STEPS', 50))
        # ---- training goodput plane (telemetry/goodput.py) ----
        self.goodput = goodput_lib.GoodputLedger(
            os.path.join(self.dir, 'intervals%s.jsonl' % suffix),
            log=self.log)
        import jax
        device_kind = jax.local_devices()[0].device_kind
        self._num_devices = jax.device_count()
        self.peak_flops = goodput_lib.resolve_peak_flops(
            getattr(config, 'DEVICE_PEAK_FLOPS', -1.0), device_kind)
        sigma = getattr(config, 'GOODPUT_ANOMALY_SIGMA', 6.0)
        cooldown = getattr(config, 'GOODPUT_AUTOCAPTURE_COOLDOWN_SECS',
                           600.0)
        self.anomaly = goodput_lib.StepAnomalyWatchdog(
            sigma, cooldown, dump_dir=self.dir,
            on_capture=self.trace.request,
            on_record=self.goodput.note_anomaly,
            suffix=suffix, log=self.log)
        self._window_excluded = 0.0
        self.exporters = [
            JsonlExporter(self.dir, filename='metrics%s.jsonl' % suffix),
            PrometheusExporter(self.dir, filename='metrics%s.prom' % suffix),
            ConsoleExporter(self.log, min_interval_s=getattr(
                config, 'TELEMETRY_CONSOLE_EVERY_SECS', 30.0)),
        ]
        # rate window state: rates are computed per flush interval
        self._window_t0 = time.monotonic()
        self._window_examples = 0
        self._window_contexts = 0

    # ------------------------------------------------------------ recording
    def count_batch(self, num_examples: int, num_contexts: int) -> None:
        self.steps.inc()
        self.examples.inc(num_examples)
        self.contexts.inc(num_contexts)
        self._window_examples += num_examples
        self._window_contexts += num_contexts

    def after_step(self, step: int) -> None:
        """Periodic work at the bottom of each hot-loop iteration: rate
        gauges + exporter flush, every ``flush_every`` steps."""
        if step % self.flush_every:
            return
        self.flush_now(step)

    def flush_now(self, step: int) -> None:
        now = time.monotonic()
        elapsed = max(now - self._window_t0, 1e-9)
        reg = self.registry
        # train/examples_per_sec measures TRAIN steps: subtract the
        # window's eval/checkpoint/rewind/preempt interval seconds (the
        # goodput ledger marks them) from the wall window, so a slow
        # eval no longer dilutes the exported throughput gauge
        excluded = self.goodput.rate_excluded_total()
        excluded_delta = min(max(excluded - self._window_excluded, 0.0),
                             elapsed - 1e-9)
        self._window_excluded = excluded
        train_elapsed = max(elapsed - excluded_delta, 1e-9)
        reg.gauge('train/examples_per_sec').set(
            self._window_examples / train_elapsed)
        reg.gauge('train/contexts_per_sec').set(
            self._window_contexts / train_elapsed)
        self._window_t0 = now
        self._window_examples = 0
        self._window_contexts = 0
        # goodput/* totals + the window's MFU off the harvested FLOPs
        self.goodput.export_gauges(reg)
        window = self.goodput.harvest_window()
        mfu_value = None
        if window['flops'] > 0:
            mfu_value = goodput_lib.mfu(window['flops'], train_elapsed,
                                        self.peak_flops, self._num_devices)
            reg.gauge('train/mfu').set(mfu_value)
            flops, byts = self.goodput.current_cost()
            reg.gauge('train/step_flops').set(flops)
            reg.gauge('train/step_bytes').set(byts)
            intensity = self.goodput.arithmetic_intensity()
            if intensity is not None:
                reg.gauge('train/arithmetic_intensity').set(intensity)
        if window['steps'] or window['productive_s'] > 0:
            self.goodput.write_window(step, window, train_elapsed, mfu_value)
        # refresh the mem/* gauges so every flush exports the current
        # ledger attribution alongside the phase timers
        memory_lib.ledger().export_gauges()
        for exporter in self.exporters:
            exporter.flush(reg, step)
        self.memwatch.poll(step)

    def resume(self) -> None:
        """Re-arm recording (fit entry) — the counterpart of shutdown()'s
        disable, so fit can be called repeatedly on one trainer."""
        core.enable()
        goodput_lib.activate(self.goodput)
        self.goodput.run_start()

    def shutdown(self, step: int) -> None:
        """Final flush + stop any live capture (fit teardown), then drop
        the process-global enable flag: a finished telemetry run must not
        leave later non-telemetry trainers/readers in this process paying
        the pipeline-recording cost into an unexported registry."""
        self.trace.shutdown()
        # final window BEFORE run_end so every window record sits inside
        # its run span (goodput_report.split_spans closes a span at the
        # run_end line; a trailing window would read as a crashed span)
        self.flush_now(step)
        self.goodput.run_end(step)
        goodput_lib.deactivate(self.goodput)
        core.disable()
