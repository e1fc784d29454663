"""The quickest proof that the system still starts on the chip.

Drives the shipped path once, in ONE process, through the entry points a
user calls, at the java14m width ``Config()`` defaults to and at default
flags (flax backend, ragged fusion, rbg dropout, bf16 moments, donation):

    code2vec_tpu.cli.main (--data --test --save)      train -> eval -> save
      -> a second Code2VecModel (--load)              restore -> eval
      -> model.serving_engine() -> warmup             the default ladder
      -> engine.predict on raw .c2v lines             1, tens, 1024 rows
      -> a third model (--load --no-ragged-fusion)    the kernel's reference

The dataset is generated here from a seed: a ``.dict.c2v`` whose three
count tables overflow the default vocabulary caps (so the defaults give
the full width), >= 8 batches of train lines and >= 1 of val lines with
heavy-tailed context counts (median ~28 of 200, benchlib.JAVA14M_FILL).

It asserts, it does not log; any phase that raises ends the run non-zero.
It needs a TPU: on any other platform it names what it found and exits 1
without a result line. ``--rehearse-on-cpu`` is the explicit tiny-size CPU
rehearsal (never chosen by failing to find a chip); its output is labelled.

    python chip_smoke.py [--mesh DATAxMODEL]

Last stdout line on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pickle
import shutil
import sys
import tempfile
import time

import numpy as np

# the compile cache is placed before anything below touches JAX
from code2vec_tpu import compile_cache

SEED = 21
# full size: batches of the default 1024; the rehearsal passes
# --batch-size / --serving-buckets and generates a dictionary so small
# that the vocabularies (hence the tables) are tiny
FULL = dict(batch=1024, train_batches=9, val_lines=1280,
            token_words=1301136 + 1000, path_words=911417 + 1000,
            target_words=261245 + 1000, requests=(1, 37, 1024),
            extra_args=[])
REHEARSAL = dict(batch=32, train_batches=9, val_lines=40,
                 token_words=600, path_words=400, target_words=150,
                 requests=(1, 5, 32),
                 extra_args=['--batch-size', '32',
                             '--serving-buckets', '32'])


@contextlib.contextmanager
def phase(name: str, seconds: dict):
    """Times one phase into ``seconds[name]`` (a phase that raises is not
    recorded: the run ends there)."""
    print('--- %s' % name, flush=True)
    t0 = time.perf_counter()
    yield
    seconds[name] = round(time.perf_counter() - t0, 2)
    print('--- %s: %.1fs' % (name, seconds[name]), flush=True)


def context_counts(rng, n: int, max_contexts: int = 200) -> np.ndarray:
    """Heavy-tailed contexts/method: lognormal with median 28, clipped to
    [1, max_contexts] (corpus_stats_r4.json: p50 28 of 200)."""
    counts = np.exp(rng.normal(np.log(28.0), 0.9, size=n))
    return np.clip(np.rint(counts), 1, max_contexts).astype(np.int64)


def zipf_indices(rng, n: int, vocab: int) -> np.ndarray:
    """Skewed draws over [0, vocab): rank ~ vocab**u concentrates mass on
    the head like a real corpus while still reaching the tail."""
    return np.minimum((vocab ** rng.random(n)).astype(np.int64), vocab - 1)


def generate_dataset(workdir: str, sizes: dict, caps: dict) -> str:
    """Seeded ``<prefix>.dict.c2v`` / ``.train.c2v`` / ``.val.c2v``;
    returns the prefix. ``caps`` are the config's vocabulary caps: the
    lines only name words that survive the cut, so no row is filtered."""
    rng = np.random.default_rng(SEED)
    prefix = os.path.join(workdir, 'smoke')
    tables = {
        'token': ['tok%d' % i for i in range(sizes['token_words'])],
        'path': ['%d' % i for i in range(sizes['path_words'])],
        'target': ['get|name%d' % i for i in range(sizes['target_words'])]}
    n_train = sizes['batch'] * sizes['train_batches']
    with open(prefix + '.dict.c2v', 'wb') as f:
        for words in tables.values():  # token, path, target: file order
            # strictly decreasing counts: the top-N-by-count cut is exact
            pickle.dump({w: len(words) - i for i, w in enumerate(words)}, f)
        pickle.dump(n_train, f)
    vocab = {name: min(len(tables[name]), caps[name]) for name in tables}
    for role, n_lines in (('train', n_train), ('val', sizes['val_lines'])):
        counts = context_counts(rng, n_lines)
        total = int(counts.sum())
        src = zipf_indices(rng, total, vocab['token'])
        pth = zipf_indices(rng, total, vocab['path'])
        tgt = zipf_indices(rng, total, vocab['token'])
        labels = zipf_indices(rng, n_lines, vocab['target'])
        with open('%s.%s.c2v' % (prefix, role), 'w') as f:
            at = 0
            for row in range(n_lines):
                c = int(counts[row])
                ctxs = ' '.join(
                    '%s,%s,%s' % (tables['token'][src[at + i]],
                                  tables['path'][pth[at + i]],
                                  tables['token'][tgt[at + i]])
                    for i in range(c))
                at += c
                f.write('%s %s\n' % (tables['target'][labels[row]], ctxs))
    return prefix


def check_predictions(results, lines, top_k: int, vocab_words) -> None:
    """One request's decoded results: one per line, in order, finite
    descending normalized scores, words from the target vocabulary."""
    assert len(results) == len(lines), (len(results), len(lines))
    for result, line in zip(results, lines):
        assert result.original_name == line.split(' ', 1)[0]
        scores = np.asarray(result.topk_predicted_words_scores)
        assert scores.shape == (top_k,) and np.isfinite(scores).all()
        assert (scores >= 0).all() and scores.sum() <= 1.0 + 1e-3
        assert (np.diff(scores) <= 1e-6).all(), scores
        words = result.topk_predicted_words
        assert len(words) == top_k
        assert all(isinstance(w, str) and w in vocab_words for w in words)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--mesh', default=None,
                        help='DATAxMODEL mesh shape (default: all devices '
                             'on the data axis), passed through to the cli')
    parser.add_argument('--rehearse-on-cpu', action='store_true',
                        help='tiny-size CPU rehearsal of every phase; '
                             'labelled, never a substitute for the chip run')
    args = parser.parse_args(argv)
    if not __debug__:
        parser.error('chip_smoke checks with assert: run it without -O')
    rehearsal = args.rehearse_on_cpu
    sizes = REHEARSAL if rehearsal else FULL

    cache_dir = compile_cache.configure()
    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    print('chip_smoke: platform=%s device_kind=%r devices=%d jax=%s '
          'compile_cache=%s%s'
          % (device['platform'], device['kind'], device['count'],
             jax.__version__, cache_dir,
             ' [REHEARSAL: CPU, tiny sizes — not a chip result]'
             if rehearsal else ''), flush=True)
    wanted, need = (('cpu', 'the CPU (--rehearse-on-cpu)') if rehearsal
                    else ('tpu', 'a TPU'))
    if device['platform'] != wanted:
        print('chip_smoke: needs %s, JAX found platform %r (%s x%d); '
              'no result' % (need, device['platform'], device['kind'],
                             device['count']), file=sys.stderr)
        return 1

    from code2vec_tpu import benchlib, cli
    from code2vec_tpu.config import Config
    from code2vec_tpu.data import native
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.telemetry import core as tele_core
    from code2vec_tpu.telemetry import memory as memory_lib
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener
    from code2vec_tpu.training.trainer import Trainer

    # jit/compiles_total (the repo's own compile counter) and JAX's
    # persistent-cache events, both through jax.monitoring
    tele_core.enable()
    install_compile_listener()
    cache_events = {'/jax/compilation_cache/cache_hits': 0,
                    '/jax/compilation_cache/cache_misses': 0,
                    '/jax/compilation_cache/compile_requests_use_cache': 0}

    def on_event(name: str, **_kw) -> None:
        if name in cache_events:
            cache_events[name] += 1
    jax.monitoring.register_event_listener(on_event)
    compiles = tele_core.registry().counter('jit/compiles_total')

    # every train step's loss, observed where Trainer.fit takes its steps
    # (device scalars; fetched once after the run — no per-step sync)
    step_losses = []
    take_step = Trainer.train_step_placed

    def recording_step(self, state, arrays):
        state, loss = take_step(self, state, arrays)
        step_losses.append(loss)
        return state, loss
    Trainer.train_step_placed = recording_step

    mesh_args = ['--mesh', args.mesh] if args.mesh else []
    common_args = sizes['extra_args'] + mesh_args
    workdir = tempfile.mkdtemp(prefix='chip_smoke_')
    report = {'rehearsal': rehearsal, 'mesh': args.mesh or 'default',
              'compile_cache_dir': cache_dir}
    seconds: dict = {}
    try:
        with phase('dataset', seconds):
            prefix = generate_dataset(workdir, sizes, {
                'token': Config.MAX_TOKEN_VOCAB_SIZE,
                'path': Config.MAX_PATH_VOCAB_SIZE,
                'target': Config.MAX_TARGET_VOCAB_SIZE})
        val_path = prefix + '.val.c2v'
        save_path = os.path.join(workdir, 'model', 'saved_model')

        # ---- train -> per-epoch eval -> save, through the CLI
        with phase('train_eval_save', seconds):
            model = cli.main(['--data', prefix, '--test', val_path,
                              '--save', save_path, '--epochs', '1']
                             + common_args)
        config = model.config
        if not rehearsal:
            assert (config.DL_FRAMEWORK, config.USE_PALLAS_RAGGED_FUSION,
                    config.DROPOUT_PRNG_IMPL, config.DONATE_STAGED_BATCHES,
                    config.TRAIN_BATCH_SIZE, config.MAX_CONTEXTS) == \
                ('flax', True, 'rbg', True, 1024, 200), 'not the defaults'
            widths = (model.vocabs.token_vocab.size,
                      model.vocabs.path_vocab.size,
                      model.vocabs.target_vocab.size)
            assert widths == (config.MAX_TOKEN_VOCAB_SIZE + 1,
                              config.MAX_PATH_VOCAB_SIZE + 1,
                              config.MAX_TARGET_VOCAB_SIZE + 1), widths
        losses = np.asarray(jax.device_get(step_losses), np.float64)
        assert losses.shape[0] >= 8, 'took %d train steps' % losses.shape[0]
        assert np.isfinite(losses).all(), losses
        train_programs = model.trainer._train_step_packed._cache_size()
        assert train_programs >= 2, (
            'the packed wire met %d capacity(ies); the dataset should '
            'straddle a bucket' % train_programs)
        trained_eval = model.eval_history[-1]
        assert trained_eval['loss'] is not None and \
            np.isfinite(trained_eval['loss'])
        report.update(
            train_steps=int(losses.shape[0]),
            first_loss=float(losses[0]), last_loss=float(losses[-1]),
            train_step_programs=int(train_programs),
            trained_eval_loss=trained_eval['loss'],
            tokenizer=('native' if config.READER_USE_NATIVE
                       and native.is_available() else 'python'),
            staging_ring_depth=config.DEVICE_PREFETCH_BATCHES,
            params=int(sum(np.prod(leaf.shape) for leaf in
                           jax.tree_util.tree_leaves(model.params))))
        target_words = set(model.vocabs.target_vocab.word_to_index)
        del model  # its 3 GB of state leaves the device before the reload

        # ---- restore into a second model; its eval must reproduce the
        # loss the trainer saw on the state it saved
        with phase('restore_eval', seconds):
            config2 = Config().load_from_args(
                ['--load', save_path, '--test', val_path] + common_args)
            served = Code2VecModel(config2)
            restored_eval = served.evaluate()
        assert restored_eval.loss is not None and \
            np.isfinite(restored_eval.loss)
        assert abs(restored_eval.loss - trained_eval['loss']) <= \
            1e-6 * max(1.0, abs(trained_eval['loss'])), (
                restored_eval.loss, trained_eval['loss'])
        assert np.isfinite(restored_eval.topk_acc).all()
        report.update(
            restored_eval_loss=restored_eval.loss,
            restored_eval_bit_equal=(restored_eval.loss
                                     == trained_eval['loss']))

        # ---- the serving engine: cold warm-up of the default ladder
        with phase('engine_warmup', seconds):
            engine = served.serving_engine()
        registry = tele_core.registry()
        programs = int(registry.gauge('serving/programs_warm').value)
        report.update(warmup_seconds=seconds['engine_warmup'],
                      warmup_programs=programs,
                      serving_buckets=list(engine.buckets),
                      serving_tiers=list(engine.tiers))
        try:
            kernel_on = (config2.USE_PALLAS_RAGGED_FUSION
                         and device['platform'] == 'tpu')
            if kernel_on:
                # one AOT compile (a persistent-cache hit after warm-up)
                warm_arrays = next(iter(engine._warm_batches(
                    engine.buckets[-1])))
                from code2vec_tpu.parallel import mesh as mesh_lib
                placed = mesh_lib.shard_batch(
                    warm_arrays, engine.mesh, config2.SHARD_CONTEXTS,
                    direct=True)
                assert benchlib.mosaic_engaged(
                    served.trainer._predict_steps[('topk', 'packed')],
                    served.params, placed), (
                        'ragged fusion is on but the packed predict '
                        'program has no tpu_custom_call')
            report['mosaic_engaged'] = kernel_on

            with open(val_path) as f:
                val_lines = [line.rstrip('\n') for line in f]
            top_k = config2.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION
            compiles_before = compiles.value
            assert compiles_before > 0, 'the compile listener saw nothing'
            with phase('serve', seconds):
                at = 0
                for i, n in enumerate(sizes['requests']):
                    lines = val_lines[at:at + n]
                    at += n
                    assert len(lines) == n
                    tier = 'attention' if i == 1 else 'topk'
                    results = engine.predict(lines, tier=tier, timeout=600)
                    check_predictions(results, lines, top_k, target_words)
                    if tier == 'attention':
                        for result, line in zip(results, lines):
                            weights = np.asarray(list(
                                result.attention_per_context.values()))
                            assert weights.size > 0
                            assert np.isfinite(weights).all()
                            # the dict keys on the context triple: a
                            # line's repeated triples share one entry
                            contexts = line.split(' ')[1:]
                            if len(set(contexts)) == len(contexts):
                                assert abs(weights.sum() - 1.0) < 2e-2, \
                                    weights.sum()
            served_compiles = compiles.value - compiles_before
            assert served_compiles == 0, (
                '%d compile(s) while serving after warm-up'
                % served_compiles)
            report.update(compiles_before_serving=int(compiles_before),
                          compiles_while_serving=int(served_compiles),
                          requests=list(sizes['requests']))
            # the memory ledger against what the runtime reports: the
            # serving params are attributed, and no more is attributed
            # than the devices hold
            snap = memory_lib.ledger().snapshot()
            in_use = sum(d['bytes_in_use']
                         for d in snap['backend']['devices'])
            assert snap['buckets']['params']['bytes'] >= \
                memory_lib.tree_nbytes(served.params)
            if device['platform'] == 'tpu':
                assert 0 < snap['attributed_bytes'] <= in_use, (
                    snap['attributed_bytes'], in_use)
            report['memory_ledger'] = {
                'attributed_bytes': snap['attributed_bytes'],
                'live_array_bytes': snap['backend']['live_bytes'],
                'unattributed_bytes': snap['unattributed_bytes'],
                'devices_bytes_in_use': in_use}
        finally:
            engine.close()
        kernel_eval_loss = restored_eval.loss
        served.close_stores()
        del served, engine

        # ---- the kernel's reference: the same checkpoint evaluated
        # through the unfused unpack-then-dense path, to bf16 tolerance
        with phase('unfused_reference_eval', seconds):
            config3 = Config().load_from_args(
                ['--load', save_path, '--test', val_path,
                 '--no-ragged-fusion'] + common_args)
            reference = Code2VecModel(config3)
            reference_eval = reference.evaluate()
            reference.close_stores()
        assert abs(reference_eval.loss - kernel_eval_loss) <= \
            2e-2 * abs(reference_eval.loss), (
                reference_eval.loss, kernel_eval_loss)
        report.update(unfused_eval_loss=reference_eval.loss)

        per_device = memory_lib.backend_memory()['devices']
        if device['platform'] == 'tpu':
            assert len(per_device) == device['count']
            assert all(d['peak_bytes_in_use'] > 0 for d in per_device), \
                per_device
        report.update(
            peak_bytes_in_use={str(d['id']): d['peak_bytes_in_use']
                               for d in per_device},
            compile_cache={k.rsplit('/', 1)[1]: v
                           for k, v in cache_events.items()},
            compile_cache_hit=cache_events[
                '/jax/compilation_cache/cache_hits'] > 0,
            compiles_total=int(compiles.value),
            seconds=seconds, device=device)
    finally:
        Trainer.train_step_placed = take_step
        shutil.rmtree(workdir, ignore_errors=True)

    print('chip_smoke report%s: %s'
          % (' [REHEARSAL]' if rehearsal else '', json.dumps(report)),
          flush=True)
    final = {'ok': True, 'device': device}
    if rehearsal:
        final['rehearsal'] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
