"""On-demand ``jax.profiler`` trace capture from a live run.

``Config.PROFILE_DIR`` (the pre-existing knob) captures one fixed window
near the start of a run; this controller adds captures that need no
restart:

- ``TELEMETRY_TRACE_AT_STEP`` (config field, CLI ``--trace-at-step``, or
  the environment variable of the same name): capture
  ``TELEMETRY_TRACE_NUM_STEPS`` steps once that global step is reached.
- Touch-file trigger: ``touch <telemetry_dir>/TRACE_NOW`` in a live run;
  the trainer polls for it every ``poll_every`` steps (one ``stat`` call
  per poll — nothing per step), consumes the file, and captures the next
  window.  Repeatable: touch again for another capture.

Each capture lands in its own ``<telemetry_dir>/traces/step<N>`` dir
(viewable with TensorBoard/Perfetto; decomposable offline with
``benchmarks/analyze_trace.py --trace <dir>``).  jax.profiler cannot nest
captures, so the controller is inert while ``Config.PROFILE_DIR``'s
window is active — the trainer gates on that.

Every capture, fixed or on demand, gets a legend (``ProgramLegend``): the
compiled text of the step programs that ran in it, whose ``op_name`` gives
each instruction's part (``code2vec_tpu/scopes.py``).
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

from code2vec_tpu.telemetry import core

ENV_TRACE_AT_STEP = 'TELEMETRY_TRACE_AT_STEP'
TOUCH_FILE_NAME = 'TRACE_NOW'
PROGRAMS_DIR = 'programs'
_MODULE_NAME = re.compile(r'^HloModule\s+([^\s,]+)', re.MULTILINE)


def _abstract(tree) -> Dict[str, dict]:
    """{leaf's path: shape and dtype} of a pytree of arrays."""
    import jax
    return {jax.tree_util.keystr(path): {'shape': list(leaf.shape),
                                         'dtype': str(leaf.dtype)}
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


class ProgramLegend:
    """What makes a capture readable by part: a profile's device events
    name the compiled program's instructions (``fusion.28``), and only the
    program's text says which part of the step each belongs to
    (``op_name="jit(train_step)/jvp(c2v_encode)/..."``).

    The trainer hands over each train-step program at first sight of its
    dispatch shape (``add``: the one place that compiles, in warm-up; the
    text is kept in memory), names the shapes it dispatches while a
    capture runs (``ran``), and whoever stops the capture calls ``write``:
    ``<capture dir>/programs/<module>.<shape key>.hlo.txt`` for every
    program that ran in it, and beside it ``.json`` with what the step is
    (module, shape key, mesh axes, the abstract parameters and optimizer
    state). Two packed capacities are two programs under one module name
    with different instruction numbers, so the file is the specialization
    that ran."""

    def __init__(self, mesh_axes: Dict[str, int], log=None):
        self.mesh_axes = dict(mesh_axes)
        self._log = log or (lambda msg: None)
        self._programs: Dict[str, dict] = {}
        self._ran: List[str] = []

    def add(self, shape_key: str, lowered, state) -> None:
        """Compiles ``lowered`` (a persistent-cache hit or the build the
        first dispatch would have made; that dispatch then finds the
        executable on the lowering it shares) and keeps its text."""
        text = lowered.compile().as_text()
        module = _MODULE_NAME.search(text)
        if 'c2v_' not in text:
            # JAX's persistent-cache key ignores metadata: an executable
            # that a tree from before the scopes compiled is found again
            self._log('legend: the text of the step program for %s names '
                      'no c2v_ scope (an executable from a compile cache '
                      'filled before the scopes?): captures will not read '
                      'by part until the cache is cleared or moved'
                      % shape_key)
        self._programs[shape_key] = {
            'text': text,
            'about': {'module': module.group(1) if module else 'unknown',
                      'shape_key': shape_key, 'mesh': self.mesh_axes,
                      'params': _abstract(state.params),
                      'opt_state': _abstract(state.opt_state)}}

    def ran(self, shape_key: str) -> None:
        if shape_key not in self._ran:
            self._ran.append(shape_key)

    def write(self, capture_dir: str) -> List[str]:
        """Writes the programs that ran since the last ``write`` into
        ``capture_dir``; returns the text files' paths."""
        ran, self._ran = self._ran, []
        paths = []
        for shape_key in ran:
            program = self._programs.get(shape_key)
            if program is None:
                continue
            out_dir = os.path.join(capture_dir, PROGRAMS_DIR)
            os.makedirs(out_dir, exist_ok=True)
            stem = os.path.join(out_dir, '%s.%s' % (
                program['about']['module'], shape_key.replace(':', '-')))
            with open(stem + '.hlo.txt', 'w') as f:
                f.write(program['text'])
            with open(stem + '.json', 'w') as f:
                json.dump(program['about'], f, indent=1)
            paths.append(stem + '.hlo.txt')
        return paths


class TraceController:
    def __init__(self, trace_root: str, trace_at_step: int = -1,
                 num_steps: int = 5, poll_every: int = 25,
                 log=None):
        self.trace_root = trace_root
        # config < 0 means unset; the env var then takes over, so a live
        # run launched without the flag can still be told where to look
        if trace_at_step < 0:
            trace_at_step = int(os.environ.get(ENV_TRACE_AT_STEP, -1))
        self.trace_at_step = trace_at_step
        self.num_steps = max(1, num_steps)
        self.poll_every = max(1, poll_every)
        self.touch_path = os.path.join(trace_root, TOUCH_FILE_NAME)
        self._log = log or (lambda msg: None)
        #: the trainer's ``ProgramLegend``, written into every capture
        self.legend: Optional[ProgramLegend] = None
        self._active_dir: Optional[str] = None
        self._stop_at = -1
        self._armed_at = -1   # step the touch trigger armed for (-1: none)

    @property
    def active(self) -> bool:
        return self._active_dir is not None

    def request(self, step: int) -> None:
        """Arm a one-shot capture to start at (or after — ``_should_start``
        matches exactly, so pass the next step the trainer will offer)
        step ``step``.  The anomaly watchdog's auto-capture entry; same
        arming as the touch-file trigger.  No-op while a capture is
        already active or armed."""
        if self._active_dir is None and self._armed_at < 0:
            self._armed_at = step

    def _should_start(self, step: int) -> bool:
        if step == self.trace_at_step or step == self._armed_at:
            return True
        if step % self.poll_every == 0 and os.path.exists(self.touch_path):
            try:
                os.remove(self.touch_path)  # consume: one capture per touch
            except OSError:
                pass
            self._armed_at = step  # start on THIS step
            return True
        return False

    def maybe_update(self, step: int, sync_tree=None) -> None:
        """Advance the capture state machine at the top of step ``step``.
        ``sync_tree`` (typically the train state's params) is blocked on
        before stopping so the traced window contains completed device
        work, not just dispatches."""
        if self._active_dir is None:
            if not self._should_start(step):
                return
            import jax
            trace_dir = os.path.join(self.trace_root, 'traces',
                                     'step%d' % step)
            os.makedirs(trace_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(trace_dir)
            except Exception as exc:  # another trace active, backend quirk
                self._log('telemetry: trace capture at step %d failed to '
                          'start: %s' % (step, exc))
                self._armed_at = -1
                return
            self._active_dir = trace_dir
            self._stop_at = step + self.num_steps
            self._armed_at = -1
            self._log('telemetry: profiler capture started at step %d '
                      '(%d steps) -> %s' % (step, self.num_steps, trace_dir))
        elif step >= self._stop_at:
            import jax
            if sync_tree is not None:
                jax.block_until_ready(sync_tree)
            jax.profiler.stop_trace()
            self._write_legend()
            core.registry().counter('trace/captures_total').inc()
            self._log('telemetry: profiler capture written to `%s` '
                      '(analyze: python benchmarks/analyze_trace.py '
                      '--trace %s --steps %d)'
                      % (self._active_dir, self._active_dir, self.num_steps))
            self._active_dir = None
            self._stop_at = -1

    def shutdown(self) -> None:
        """Stop a capture left active (fit teardown/exception path)."""
        if self._active_dir is not None:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._write_legend()
            self._active_dir = None
            self._stop_at = -1

    def _write_legend(self) -> None:
        if self.legend is not None:
            self.legend.write(self._active_dir)
