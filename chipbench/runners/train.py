"""Training through ``Code2VecModel.train()``: cache reader, prefetch,
staging ring, donation and the program's own log-window syncs.

The harness adds no device sync. It sees the program's "Average loss at
batch N" records through a ``logging.Handler`` on the program's logger: one
record per log window, written right after the loop fetched the window's
losses. Warm-up is the first ``warm_log_windows`` windows of the same
``fit`` loop (the token cache is built or loaded, ``StickyPacker``'s
capacity settles, every step program compiles or loads); the measured
window starts at the record that ends them. A timer then sends SIGTERM to
the process after ``seconds``, and the loop leaves by the program's own
preemption path at a step boundary; with no ``--save`` it writes nothing.

Throughput is the median, over the log windows inside the measured window,
of examples in a log window over the time between its two syncs. The median
and not the mean, because the data set is 256 steps long where a java14m
epoch is 13,672: the pause at an epoch's turn (a new prefetch thread, the
first chunk shuffled) falls into two or three of the window's log windows
and would weigh fifty times what it weighs in a job; taken into the mean it
also spread the runs by 1.2% (PERF.md, section 6). A median cannot see what
hits fewer than half of the log windows, so the rate from the first sync to
the last is held beside it: the time the window took beyond its steps at
the median pace may be at most the mix's ``epoch_turn_allowance_s`` for
each epoch turn inside it, or the run is not ``correct`` (judged in the
untraced run; starting the profiler stalls the loop). That rate is printed
in every run and is a per-layer metric of the traced one. The data set holds
a whole number of batches and no line is filtered, which set-up checks, so
every step takes ``TRAIN_BATCH_SIZE`` valid examples.
"""
from __future__ import annotations

import logging
import math
import os
import re
import signal
import threading
import time

import numpy as np

from chipbench.runners import common
from chipbench.traffic import corpus as corpus_lib

LOG_RECORD = re.compile(r'Average loss at batch (\d+): (\S+),')
#: the program's timers a traced run reads, by their registry names
TIMERS = ('step/batch_wait_ms', 'step/h2d_ms', 'step/dispatch_ms',
          'step/sync_ms', 'step/pack_ms', 'step/total_ms')
COUNTERS = ('train/steps_total', 'train/examples_total',
            'train/contexts_total')


class _Syncs(logging.Handler):
    """Notes when each log-window record arrives and what it says; opens
    the measured window after the warm-up windows and arms its end."""

    def __init__(self, runner: 'Runner', seconds: float):
        super().__init__(level=logging.INFO)
        self.runner = runner
        self.seconds = seconds
        self.records = []          # (perf_counter, batch number, mean loss)
        self.window_start = None
        self.at_start = self.at_end = None
        self.compiles_at_start = None
        self.timer = None

    def emit(self, record: logging.LogRecord) -> None:
        match = LOG_RECORD.search(record.getMessage())
        if not match:
            return
        now = time.perf_counter()
        self.records.append((now, int(match.group(1)),
                             float(match.group(2))))
        runner = self.runner
        if self.window_start is None:
            if len(self.records) < runner.warm_log_windows:
                return
            self.window_start = now
            self.compiles_at_start = runner.compiles.value
            self.at_start = runner.read_instruments()
            self.timer = threading.Timer(self.seconds, runner.end_window)
            self.timer.daemon = True
            self.timer.start()
        elif now <= self.window_start + self.seconds:
            self.at_end = runner.read_instruments()


class Runner:
    def __init__(self, ctx: common.Context, compiles: common.CompileCounter):
        self.ctx = ctx
        self.compiles = compiles
        self.warm_log_windows = int(ctx.traffic['warm_log_windows'])
        self._training = False
        self.compiles_at_end = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        ctx = self.ctx
        self.corpus = common.make_corpus(ctx)
        settings = ctx.settings
        batch = settings['TRAIN_BATCH_SIZE']
        if self.corpus['methods'] % batch:
            raise SystemExit('chipbench: %d methods are no whole number of '
                             'batches of %d' % (self.corpus['methods'], batch))
        extra = {}
        if ctx.trace:
            log_every = settings['NUM_BATCHES_TO_LOG_PROGRESS']
            trace = ctx.traffic['trace']
            extra = dict(
                TELEMETRY=True,
                TELEMETRY_DIR=os.path.join(ctx.run_dir, 'telemetry'),
                PROFILE_DIR=ctx.trace_dir,
                PROFILE_START_STEP=(self.warm_log_windows * log_every
                                    + int(trace['start_after_steps'])),
                PROFILE_NUM_STEPS=int(trace['steps']))
        self.model = common.build_model(ctx, self.corpus['prefix'],
                                        weights_only=False, **extra)
        self.batch = batch
        self.held_lines = corpus_lib.read_lines(
            self.corpus['prefix'], int(ctx.traffic['check_methods']))
        with ctx.span('check.before_s'):
            self.loss_before = common.system_eval(
                self.model, self.held_lines)['loss']
        ctx.log('held batch: loss %.6f before training' % self.loss_before)

    def warm(self) -> None:
        """Nothing apart: the warm-up is the first windows of the one
        ``fit`` loop that ``measure`` runs (see the module's docstring)."""

    # ----------------------------------------------------------- measure
    def read_instruments(self) -> dict:
        """(total seconds, count) of the program's timers and the values
        of its counters; empty unless the run has telemetry on."""
        if not self.ctx.trace:
            return {}
        from code2vec_tpu.telemetry import core
        registry = core.registry()
        out = {}
        for name in TIMERS:
            timer = registry.timer(name)
            out[name] = (timer.total, timer.count)
        for name in COUNTERS:
            out[name] = registry.counter(name).value
        out['input/packed_fill_rate'] = \
            registry.gauge('input/packed_fill_rate').value
        return out

    def end_window(self) -> None:
        """The window's end: ask the program to stop, the way a
        preemption notice does."""
        self.compiles_at_end = self.compiles.value
        if self._training:
            os.kill(os.getpid(), signal.SIGTERM)

    def measure(self, seconds: float) -> dict:
        ctx, model = self.ctx, self.model
        syncs = _Syncs(self, seconds)
        logger = model.config.get_logger()
        logger.addHandler(syncs)
        # once train() has put back the handler it found, a late timer must
        # meet one that does nothing, not the default that ends the process
        previous = signal.signal(signal.SIGTERM, lambda *_: None)
        t_call = time.perf_counter()
        self._training = True
        try:
            model.train()
        finally:
            self._training = False
            if syncs.timer is not None:
                syncs.timer.cancel()
            logger.removeHandler(syncs)
            signal.signal(signal.SIGTERM, previous)
        if syncs.window_start is None:
            raise SystemExit('chipbench: training ended before the warm-up '
                             'windows did')
        ctx.spans['lifecycle.train_warm_s'] = syncs.window_start - t_call
        t0, t1 = syncs.window_start, syncs.window_start + seconds
        inside = [r for r in syncs.records if t0 <= r[0] <= t1]
        if len(inside) < 3:
            raise SystemExit('chipbench: %d log-window syncs inside the '
                             'window; it needs 3' % len(inside))
        (first_t, first_n, _), (last_t, last_n, _) = inside[0], inside[-1]
        chips = model.mesh.size
        steps = last_n - first_n
        per_chip = self.batch / chips
        rate = float(np.median([
            (n1 - n0) * per_chip / (t1 - t0)
            for (t0, n0, _), (t1, n1, _) in zip(inside, inside[1:])]))
        whole_window_rate = steps * per_chip / (last_t - first_t)
        steps_per_epoch = self.corpus['methods'] // self.batch
        turns = last_n // steps_per_epoch - first_n // steps_per_epoch
        lost_s = (last_t - first_t) - steps * per_chip / rate
        allowed_s = turns * float(ctx.traffic['epoch_turn_allowance_s'])
        bad_windows = sum(1 for _, _, loss in inside[1:]
                          if not math.isfinite(loss))
        log_every = model.config.NUM_BATCHES_TO_LOG_PROGRESS
        ctx.log('window: %d syncs, steps %d..%d in %.3f s (%.1f '
                'examples/s/chip over all of it: %.3f s beyond the median '
                'pace, %d epoch turns), losses %.4f -> %.4f'
                % (len(inside), first_n, last_n, last_t - first_t,
                   whole_window_rate, lost_s, turns, inside[1][2],
                   inside[-1][2]))
        self.losses_finite = bad_windows == 0
        self.stall_fault = None
        if not ctx.trace and lost_s > allowed_s:
            self.stall_fault = (
                'the window took %.3f s beyond its %d steps at the median '
                'pace; its %d epoch turn(s) allow %.3f s: stalls that the '
                'median over log windows does not see'
                % (lost_s, steps, turns, allowed_s))
        obs = {
            'window_start': syncs.window_start,
            'attempted': steps,
            'failed': bad_windows * log_every,
            'compiles_in_window': (self.compiles_at_end
                                   - syncs.compiles_at_start),
            'end_to_end': {'train_examples_per_sec_per_chip': rate},
            'examples_per_step_per_chip': self.batch // chips,
            'examples_per_sec_per_chip': rate,
            'whole_window_examples_per_sec_per_chip': whole_window_rate,
            'mean_contexts': self.corpus['mean_contexts'],
            'instruments': (syncs.at_start, syncs.at_end),
        }
        return obs

    # ------------------------------------------------------------- check
    def check(self) -> dict:
        """The deterministic forward on the held batch against the
        reference with the same (trained) parameters: the loss, and each of
        its top logits; that loss lower after the window than before it;
        every loss of the window finite."""
        spec = self.ctx.config['check']
        system = common.system_eval(self.model, self.held_lines)
        wanted = common.reference_eval(self.model, self.held_lines,
                                       system['top_indices'])
        loss_error = abs(system['loss'] - wanted['loss'])
        logit_error = float(np.abs(system['top_logits']
                                   - wanted['logits']).max())
        faults = []
        if not loss_error <= spec['loss_tolerance']:
            faults.append('held batch: loss %.6f, reference %.6f, tolerance '
                          '%g' % (system['loss'], wanted['loss'],
                                  spec['loss_tolerance']))
        if not logit_error <= spec['logit_tolerance']:
            faults.append('held batch: a top logit is %.4g off the '
                          'reference, tolerance %g'
                          % (logit_error, spec['logit_tolerance']))
        if not system['loss'] < self.loss_before:
            faults.append('held batch: loss %.6f after the window, %.6f '
                          'before it' % (system['loss'], self.loss_before))
        if not self.losses_finite:
            faults.append('a log window of the measured window had a '
                          'non-finite mean loss')
        if self.stall_fault:
            faults.append(self.stall_fault)
        self.ctx.log('check: held-batch loss %.6f (reference %.6f, before '
                     'training %.6f); top logits off by at most %.3g'
                     % (system['loss'], wanted['loss'], self.loss_before,
                        logit_error))
        return {'faults': faults}

    def close(self) -> None:
        self.model.close_stores()
