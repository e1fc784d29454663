"""What the runners share: the run's context, spans, the compile counter,
building the model from a configuration file, and the comparison with
``chipbench/reference.py`` that decides ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
from typing import Dict, List, Sequence

import jax
import numpy as np

from chipbench import reference
from chipbench.manifest import Cell
from chipbench.traffic import corpus as corpus_lib

COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


@dataclasses.dataclass
class Context:
    """One run of one cell, as the harness hands it to a runner."""
    cell: Cell
    seed: int
    trace: bool
    rehearsal: bool
    data_root: str          # generated data sets, kept between runs
    run_dir: str            # this run's traces and telemetry
    config: dict            # the configuration's file, as run
    settings: dict          # its Config keys, as run
    traffic: dict           # the mix's parameters, as run
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def trace_dir(self) -> str:
        """Where this run's profiler trace goes."""
        return os.path.join(self.run_dir, 'trace')

    def log(self, message: str) -> None:
        print('chipbench: %s' % message, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """Times a block into ``spans[name]`` (seconds, summed over
        repeats) and marks it in the profiler's trace."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation('chipbench/' + name):
            yield
        self.spans[name] = self.spans.get(name, 0.0) + \
            time.perf_counter() - t0


class CompileCounter:
    """Counts programs built or loaded from the persistent cache, off the
    event the program's own ``jit/compiles_total`` counts (a cache hit fires
    it too), without turning the program's telemetry on."""

    def __init__(self):
        from jax import monitoring
        self.value = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            self.value += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif name == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1


def make_config(ctx: Context, **paths):
    """The program's ``Config`` with the configuration file's settings.
    A key the program's ``Config`` does not have is an error: a file that
    names it would otherwise claim a setting that nothing reads."""
    from code2vec_tpu.config import Config
    known = {f.name for f in dataclasses.fields(Config)}
    unknown = sorted(set(ctx.settings) - known)
    if unknown:
        raise SystemExit('chipbench: configuration %s sets keys Config lacks: '
                         '%s' % (ctx.cell.config_name, ', '.join(unknown)))
    return Config(**ctx.settings, **paths)


def vocab_caps(settings: dict) -> Dict[str, int]:
    return {'token': settings['MAX_TOKEN_VOCAB_SIZE'],
            'path': settings['MAX_PATH_VOCAB_SIZE'],
            'target': settings['MAX_TARGET_VOCAB_SIZE']}


def make_corpus(ctx: Context) -> dict:
    """The mix's data set for this seed, generated unless the checkout has
    it; its parameters and the hash of its first megabyte go on a line."""
    with ctx.span('lifecycle.data_s'):
        made = corpus_lib.materialize(
            ctx.traffic['corpus'], ctx.seed, vocab_caps(ctx.settings),
            ctx.data_root,
            ctx.cell.config_name + ('-rehearsal' if ctx.rehearsal else ''))
    ctx.log('data set %s' % {k: v for k, v in made.items() if k != 'wanted'})
    ctx.log('data set parameters %s' % made['wanted'])
    return made


def build_model(ctx: Context, prefix: str, weights_only: bool, **paths):
    """``Code2VecModel`` over the data set at ``prefix``, weights from the
    program's own seeded init. ``weights_only`` drops the optimizer state
    after the constructor made it: the released model the serving cells
    deploy (the constructor cannot build weights alone; PERF.md)."""
    from code2vec_tpu.model_api import Code2VecModel
    config = make_config(ctx, TRAIN_DATA_PATH_PREFIX=prefix, **paths)
    with ctx.span('lifecycle.build_s'):
        model = Code2VecModel(config)
        if weights_only:
            model.state = None
    return model


def device_memory(devices: Sequence) -> Dict[str, int]:
    """The fullest of ``devices``, by the runtime's ``memory_stats()``.

    ``peak_bytes_in_use`` counts arrays only; the temporaries of the
    largest program loaded the runtime reports apart, as ``bytes_reserved``
    (PERF.md, section 4: 1,752,219,648 in a training run, where XLA's
    memory analysis of the step program has 1,817,522,176 bytes of
    temporaries; 896,024,576 in a serving run, the float32 scores of a
    64-query search over 3,500,000 rows). ``peak_bytes`` is therefore the
    larger of the peak of the arrays and what is in use plus what is
    reserved; both parts are on a line of every run. 0 where the runtime
    reports nothing (the CPU)."""
    out = {'bytes_in_use': 0, 'peak_bytes_in_use': 0, 'bytes_reserved': 0,
           'peak_bytes': 0}
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(stats.get('peak_bytes_in_use', 0),
                   stats.get('bytes_in_use', 0)
                   + stats.get('bytes_reserved', 0))
        if peak >= out['peak_bytes']:
            out = {key: int(stats.get(key, 0)) for key in out}
            out['peak_bytes'] = int(peak)
    return out


class TraceSlice:
    """``jax.profiler`` around a slice of the measured window, from a
    thread of its own, for runners whose program has no capture seam."""

    def __init__(self, trace_dir: str, start_after_s: float, length_s: float):
        self.trace_dir = trace_dir
        self.start_after_s = start_after_s
        self.length_s = length_s
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name='chipbench-trace')
        self._stop = threading.Event()

    def _run(self) -> None:
        if self._stop.wait(self.start_after_s):
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # one event per call swamps a server
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            self._stop.wait(self.length_s)
        finally:
            jax.profiler.stop_trace()

    def start(self) -> None:
        self._thread.start()

    def finish(self) -> None:
        """Waits for the slice to end (it ends early if the window did)."""
        self._stop.set()
        self._thread.join()


def start_trace_slice(ctx: Context):
    """The mix's slice of the window under the profiler, started; None in
    an untraced run."""
    if not ctx.trace:
        return None
    spec = ctx.traffic['trace']
    tracer = TraceSlice(ctx.trace_dir, spec['start_after_s'],
                        spec['length_s'])
    tracer.start()
    return tracer


# ------------------------------------------------------------ the check
def reference_tables(model) -> reference.Tables:
    named = model.backend.named_params(model.params)
    return reference.Tables(
        value_vocab=named.token_embedding, path_vocab=named.path_embedding,
        tags_vocab=named.target_embedding, w=named.transform,
        a=named.attention, n_tags=model.vocabs.target_vocab.size)


def parse_for_reference(model, lines: Sequence[str]) -> reference.Parsed:
    vocabs = model.vocabs
    return reference.parse_lines(
        lines, vocabs.token_vocab.word_to_index,
        vocabs.path_vocab.word_to_index, vocabs.target_vocab.word_to_index,
        {'token': vocabs.token_vocab.oov_index,
         'path': vocabs.path_vocab.oov_index,
         'tag': vocabs.target_vocab.oov_index},
        model.config.MAX_CONTEXTS)


def system_eval(model, lines: Sequence[str]) -> dict:
    """The program's deterministic forward on ``lines``
    (``Trainer.eval_step``, dropout off), over the packed wire the training
    step uses: the mean loss, and its top-k logits with their indices."""
    from code2vec_tpu.data import packed as packed_lib
    from code2vec_tpu.parallel import mesh as mesh_lib
    trainer = model.trainer
    batch = model._get_predict_reader().process_input_rows(lines)
    if model.config.wire_format_for(1) == 'packed':
        batch = packed_lib.pack_batch(
            batch, trainer._token_pad, trainer._path_pad,
            data_shards=trainer.mesh.shape[mesh_lib.DATA_AXIS])
    out = trainer.eval_step(model.params, batch)
    return {'loss': float(out['loss_sum']) / float(out['weight_sum']),
            'top_logits': np.asarray(out['topk_scores']),
            'top_indices': np.asarray(out['topk_indices'])}


def reference_eval(model, lines: Sequence[str], top_indices) -> dict:
    """The reference's mean loss on ``lines`` with the model's parameters,
    and its logits at ``top_indices``."""
    import jax.numpy as jnp
    parsed = parse_for_reference(model, lines)

    def outputs(tables, source, path, target, valid, label, indices):
        _, _, logits = reference.forward(tables, source, path, target, valid)
        picked = jnp.take_along_axis(reference.log_q(logits), label[:, None],
                                     axis=1)
        return -jnp.mean(picked), jnp.take_along_axis(logits, indices, axis=1)

    loss, logits = jax.jit(outputs)(
        reference_tables(model), parsed.source, parsed.path, parsed.target,
        parsed.valid, parsed.label, np.asarray(top_indices))
    return {'loss': float(loss), 'logits': np.asarray(logits)}


@functools.partial(jax.jit, static_argnames=('k',))
def _reference_outputs(tables, source, path, target, valid, k: int):
    """The reference's code vectors, attention and logits, and its own
    k-th best logit of each row; one program for every tier's check."""
    vector, alpha, logits = reference.forward(tables, source, path, target,
                                              valid)
    return vector, alpha, logits, reference.top_k(logits, k)[0][:, -1]


def check_results(model, lines: Sequence[str], results: Sequence,
                  tier: str, tolerance: dict) -> List[str]:
    """Compares one request's decoded results with the reference, by value:
    the reference's logits at the returned top-k words (each must be within
    the tolerance of the reference's own k-th best, and the normalized
    scores must agree), the code vector, the attention weights. Returns
    what failed, as text; an empty list is a pass."""
    faults: List[str] = []
    if len(results) != len(lines):
        return ['%d results for %d lines' % (len(results), len(lines))]
    parsed = parse_for_reference(model, lines)
    k = model.config.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION
    vector, alpha, logits, kth_best = (np.asarray(x) for x in (
        _reference_outputs(reference_tables(model), parsed.source,
                           parsed.path, parsed.target, parsed.valid, k=k)))
    tag_index = model.vocabs.target_vocab.word_to_index
    worst = {'logit': 0.0, 'score': 0.0, 'vector': 0.0, 'attention': 0.0}
    for row, result in enumerate(results):
        if tier != 'vectors':
            words = result.topk_predicted_words
            if len(words) != k or len(set(words)) != k:
                faults.append('row %d: %d distinct words of %d'
                              % (row, len(set(words)), k))
                continue
            picked = logits[row, [tag_index[w] for w in words]]
            worst['logit'] = max(worst['logit'],
                                 float(kth_best[row] - picked.min()))
            expected = np.exp(picked - picked.max())
            expected /= expected.sum()
            got = np.asarray(result.topk_predicted_words_scores, np.float64)
            worst['score'] = max(worst['score'],
                                 float(np.abs(got - expected).max()))
        if tier in ('vectors', 'full'):
            worst['vector'] = max(worst['vector'], float(np.abs(
                np.asarray(result.code_vector) - vector[row]).max()))
        if tier in ('attention', 'full'):
            want: Dict[tuple, float] = {}
            for slot, key in enumerate(parsed.contexts[row]):
                # the program keys its dict by the context's strings, so a
                # repeated context holds one slot's weight: compare those
                # that occur once
                want[key] = (alpha[row, slot] if key not in want
                             else float('nan'))
            for key, weight in want.items():
                if np.isnan(weight):
                    continue
                got_weight = result.attention_per_context.get(key)
                if got_weight is None:
                    faults.append('row %d: no attention for %r' % (row, key))
                    break
                worst['attention'] = max(worst['attention'],
                                         abs(got_weight - float(weight)))
    print('chipbench: check: %s tier off by at most %s'
          % (tier, {k: float('%.3g' % v) for k, v in worst.items()}),
          flush=True)
    for what, value in worst.items():
        if not value <= tolerance[what]:
            faults.append('%s tier: %s off by %.3g (tolerance %.3g)'
                          % (tier, what, value, tolerance[what]))
    return faults


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's clock."""
    try:
        with open('/proc/self/stat') as f:
            start_ticks = int(f.read().rsplit(')', 1)[1].split()[19])
        with open('/proc/uptime') as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf('SC_CLK_TCK')
    except (OSError, ValueError, IndexError):
        return 0.0
