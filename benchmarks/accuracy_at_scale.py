"""Accuracy-at-scale run: does the framework LEARN at java-small-like scale?

VERDICT r2 missing #2: the only accuracy signals were tiny-corpus overfit
tests. This drives the REAL pipeline end to end at a scale that stresses
vocab truncation, OOV rates and eval throughput:

  scripts/gen_java_corpus.py  (~24K classes / ~110K methods)
    -> c2v-extract --dir      (native extractor, all three splits)
    -> data/preprocess.py     (vocab build WITH truncation: 6K words and
                               4K targets against ~8.7K / ~6.7K corpus
                               uniques — the Zipf tail really truncates)
    -> cli train              (java-small dims: 128/128/384, C=200,
                               per-epoch val eval)
    -> a committed val-F1/loss learning curve (JSON)

The reference does this implicitly via train.sh + best-epoch-by-F1
(reference README.md:87-88). Children inherit this process's environment
unchanged: a ``cpu*`` profile is a reduced-size run, and it runs on the
CPU when the caller's environment says so (``JAX_PLATFORMS=cpu``).

Usage:
  python benchmarks/accuracy_at_scale.py --workdir /tmp/acc_r3 \
      [--profile tpu|cpu] [--epochs N]

Prints one JSON line per epoch plus a final summary line; the orchestrated
result lands in benchmarks/results/.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import corpus_stats as corpus_stats_mod  # noqa: E402 (sibling module)


# Corpus vocab statistics overflow these on purpose: the 24K-class corpus
# produces ~8.7K unique tokens and ~6.7K unique target names (measured),
# so these caps truncate the Zipf tail into real OOV pressure the way
# java14m's 1.3M-word cap does against its much larger raw vocabulary
WORD_VOCAB = 6000
PATH_VOCAB = 30000
TARGET_VOCAB = 4000

PROFILES = {
    # Base profiles pin '--adam-mu-dtype float32' explicitly: the config
    # DEFAULT flipped to bf16 mu on the 2026-07-31 on-chip A/B, and each
    # *_bf16mu twin below must differ from its base by exactly that one
    # knob — an unpinned base would silently train the twin's config and
    # destroy the A/B.
    # java-small-like: full dims, full contexts. Dropout is pinned 'rbg'
    # to match the committed accuracy_tpu.json capture (2026-07-31
    # 04:05Z, which ran after the rbg default flip landed on disk): the
    # tpu_bf16mu twin below must differ from it by the mu dtype ONLY.
    'tpu': dict(classes=24000, batch=512, contexts=200, epochs=12,
                extra_args=['--dropout-prng', 'rbg',
                            '--adam-mu-dtype', 'float32',
                            '--adam-nu-dtype', 'float32',
                            '--grads-dtype', 'float32']),
    # reduced compute (smaller dims/contexts) so the learning-loop evidence
    # does not need the chip; vocab pressure is unchanged
    'cpu': dict(classes=24000, batch=512, contexts=32, epochs=6,
                extra_args=['--dtype', 'float32',
                            '--dropout-prng', 'threefry2x32',
                            '--adam-mu-dtype', 'float32',
                            '--adam-nu-dtype', 'float32',
                            '--grads-dtype', 'float32']),
    # VERDICT r3 #5 fallback: FULL model dims (128/128/384) and C=200 on
    # CPU — fewer classes/epochs so it finishes in tens of minutes, but
    # the model being validated is the real one, not the 64-dim stand-in
    'cpu_full': dict(classes=8000, batch=512, contexts=200, epochs=5,
                     extra_args=['--dtype', 'float32',
                                 '--dropout-prng', 'threefry2x32',
                                 '--adam-mu-dtype', 'float32',
                                 '--adam-nu-dtype', 'float32',
                                 '--grads-dtype', 'float32']),
    # VERDICT r4 #2: the EXACT bench recipe (bfloat16 compute + Pallas
    # fused CE, interpreted on CPU + rbg dropout) at full dims, so the
    # 21.7K ex/s configuration is shown to reach the same F1 as its fp32
    # twin (accuracy_cpu_full_24k_20ep.json) on the identical dataset
    'cpu_full_bf16': dict(classes=8000, batch=512, contexts=200, epochs=5,
                          extra_args=['--dtype', 'bfloat16',
                                      '--dropout-prng', 'rbg',
                                      '--fused-ce',
                                      '--adam-mu-dtype', 'float32',
                                      '--adam-nu-dtype', 'float32',
                                      '--grads-dtype', 'float32']),
    # ADAM_MU_DTYPE='bfloat16' equivalence twins (the last winning knob
    # from the 2026-07-31 on-chip A/B, -5.1% step time): identical to the
    # profile each shadows plus the bf16 first moment, so the F1 curve
    # pairs 1:1 against accuracy_tpu.json / accuracy_cpu_full_bf16.json.
    'tpu_bf16mu': dict(classes=24000, batch=512, contexts=200, epochs=12,
                       extra_args=['--dropout-prng', 'rbg',
                                   '--adam-mu-dtype', 'bfloat16',
                                   '--adam-nu-dtype', 'float32',
                                   '--grads-dtype', 'float32']),
    # the SHIPPED default recipe on the device (rbg + bf16 mu + bf16 nu
    # after the 2026-07-31 nu flip): pairs 1:1 against
    # accuracy_tpu_bf16mu.json (nu knob only) and accuracy_tpu.json
    'tpu_bf16nu': dict(classes=24000, batch=512, contexts=200, epochs=12,
                       extra_args=['--dropout-prng', 'rbg',
                                   '--adam-mu-dtype', 'bfloat16',
                                   '--adam-nu-dtype', 'bfloat16',
                                   '--grads-dtype', 'float32']),
    'cpu_full_bf16mu': dict(classes=8000, batch=512, contexts=200, epochs=5,
                            extra_args=['--dtype', 'bfloat16',
                                        '--dropout-prng', 'rbg',
                                        '--fused-ce',
                                        '--adam-mu-dtype', 'bfloat16',
                                        '--adam-nu-dtype', 'float32',
                                        '--grads-dtype', 'float32']),
    # ADAM_NU_DTYPE='bfloat16' equivalence twin (flip-rule gate for the
    # bench_moment_dtypes.py A/B): identical to cpu_full_bf16mu plus the
    # bf16 second moment, so its F1 curve pairs 1:1 against
    # accuracy_cpu_full_bf16mu.json — a knob flips only with BOTH a >=2%
    # measured step-time win and this curve matching its fp32-nu twin.
    'cpu_full_bf16nu': dict(classes=8000, batch=512, contexts=200, epochs=5,
                            extra_args=['--dtype', 'bfloat16',
                                        '--dropout-prng', 'rbg',
                                        '--fused-ce',
                                        '--adam-mu-dtype', 'bfloat16',
                                        '--adam-nu-dtype', 'bfloat16',
                                        '--grads-dtype', 'float32']),
    # the C# pipeline at scale (VERDICT-style end-to-end evidence for the
    # second language frontend): gen_csharp_corpus -> c2v-extract --dir
    # over .cs -> preprocess -> train. Same dims/recipe as cpu_full so
    # the two languages' curves compare 1:1.
    'cpu_csharp': dict(classes=8000, batch=512, contexts=200, epochs=5,
                       lang='csharp',
                       extra_args=['--dtype', 'float32',
                                   '--dropout-prng', 'threefry2x32',
                                   '--adam-mu-dtype', 'float32',
                                   '--adam-nu-dtype', 'float32',
                                   '--grads-dtype', 'float32']),
    # GRADS_DTYPE='bfloat16' equivalence twin: the full combined
    # candidate recipe (bf16 grads + bf16 nu on top of the shipped
    # defaults), pairing against cpu_full_bf16nu (grads knob only) and
    # transitively cpu_full_bf16mu.
    'cpu_full_bf16grads': dict(classes=8000, batch=512, contexts=200,
                               epochs=5,
                               extra_args=['--dtype', 'bfloat16',
                                           '--dropout-prng', 'rbg',
                                           '--fused-ce',
                                           '--adam-mu-dtype', 'bfloat16',
                                           '--adam-nu-dtype', 'bfloat16',
                                           '--grads-dtype', 'bfloat16']),
}
CPU_DIMS = dict(TOKEN_EMBEDDINGS_SIZE=64, PATH_EMBEDDINGS_SIZE=64,
                CODE_VECTOR_SIZE=192, TARGET_EMBEDDINGS_SIZE=192)


def run(cmd, **kw):
    print('+ ' + ' '.join(cmd), file=sys.stderr, flush=True)
    subprocess.run(cmd, check=True, **kw)


def build_dataset(workdir: str, classes: int, contexts: int,
                  lang: str = 'java') -> str:
    # every cached artifact is keyed by the parameters that shaped it:
    # the corpus and raw extraction by the class count (and language —
    # java keeps its legacy key so committed workdirs stay warm), the
    # preprocessed dataset additionally by the sampling width — so
    # profiles sharing a workdir can never silently train on each
    # other's corpus size or contexts sampling (either would be a wrong
    # experiment)
    tag = '%d' % classes if lang == 'java' else 'cs_%d' % classes
    corpus = os.path.join(workdir, 'corpus_%s' % tag)
    data = os.path.join(workdir, 'data')
    os.makedirs(data, exist_ok=True)
    if not os.path.isdir(corpus):
        generator = ('gen_java_corpus.py' if lang == 'java'
                     else 'gen_csharp_corpus.py')
        run([sys.executable, os.path.join(REPO, 'scripts', generator),
             '-o', corpus, '--classes', str(classes)])
    extractor = os.path.join(REPO, 'extractor', 'build', 'c2v-extract')
    raw = {}
    for split in ('train', 'val', 'test'):
        raw[split] = os.path.join(data, '%s_%s.raw' % (split, tag))
        if not os.path.isfile(raw[split]):
            with open(raw[split], 'w') as f:
                run([extractor, '--dir', os.path.join(corpus, split),
                     '--max_path_length', '8', '--max_path_width', '2',
                     '--num_threads', '16'], stdout=f)
    prefix = os.path.join(data, 'acc_%s_c%d' % (tag, contexts))
    if not os.path.isfile(prefix + '.train.c2v'):
        run([sys.executable, '-m', 'code2vec_tpu.data.preprocess',
             '-trd', raw['train'], '-vd', raw['val'], '-ted', raw['test'],
             '-mc', str(contexts), '-wvs', str(WORD_VOCAB),
             '-pvs', str(PATH_VOCAB), '-tvs', str(TARGET_VOCAB),
             '-o', prefix, '--seed', '0'],
            cwd=REPO)
    return prefix


# the epoch log line wraps (numpy renders topk_acc across lines), so the
# epoch/loss head and the precision/recall/F1 tail may arrive on different
# lines — parse them separately and pair in order
EPOCH_HEAD_RE = re.compile(
    r'After epoch (\d+): loss: ([\d.]+(?:[eE][+-]?\d+)?)')
EPOCH_TAIL_RE = re.compile(
    r'precision: ([\d.eE+-]+), recall: ([\d.eE+-]+), F1: ([\d.eE+-]+)')


def dataset_stats(prefix: str, raw_train: str) -> dict:
    """Reproducible dataset facts for the artifact: the created vocab
    sizes, and raw vs TRAINED-ON row counts — the .c2v keeps every row,
    but the train reader skips rows whose target fell off the truncated
    vocab (reference parity), so the OOV-pressure number is recomputed
    here exactly the way the reader decides it."""
    import pickle

    def count_lines(path):
        with open(path) as f:
            return sum(1 for _ in f)

    with open(prefix + '.dict.c2v', 'rb') as f:
        word = pickle.load(f)
        path_d = pickle.load(f)
        target = pickle.load(f)
    with open(prefix + '.train.c2v') as f:
        trained_on = sum(1 for line in f
                         if line.split(' ', 1)[0] in target)
    return {
        'train_rows_raw': count_lines(raw_train),
        'train_rows_after_oov_target_drop': trained_on,
        'created_vocab': {'token': len(word), 'path': len(path_d),
                          'target': len(target)},
    }


def majority_baseline(prefix: str) -> dict:
    """Subtoken F1 of constantly predicting the most frequent train label —
    the floor the learned model must clear for the curve to mean anything
    (an OOV-majority predictor is the degenerate strategy vocab truncation
    invites)."""
    import pickle

    sys.path.insert(0, REPO)
    from code2vec_tpu.metrics import SubtokensEvaluationMetric
    from code2vec_tpu.vocab import SPECIAL_WORDS_ONLY_OOV

    with open(prefix + '.dict.c2v', 'rb') as f:
        pickle.load(f)          # word counts
        pickle.load(f)          # path counts
        target_to_count = pickle.load(f)
    majority = max(target_to_count, key=target_to_count.get)
    metric = SubtokensEvaluationMetric(SPECIAL_WORDS_ONLY_OOV.OOV)
    with open(prefix + '.val.c2v') as f:
        rows = [(line.split(' ', 1)[0], [majority]) for line in f if line]
    metric.update_batch(rows)
    return {'predicting': majority,
            'precision': round(metric.precision, 4),
            'recall': round(metric.recall, 4),
            'f1': round(metric.f1, 4)}


def build_mixed_dataset(workdir: str, classes_per_lang: int,
                        contexts: int) -> str:
    """Mixed Java+C# dataset for the --scenarios mode: both languages'
    raw extractions concatenated into ONE preprocess stream, so the
    trained vocab (and the served model) covers both frontends."""
    data = os.path.join(workdir, 'data')
    os.makedirs(data, exist_ok=True)
    extractor = os.path.join(REPO, 'extractor', 'build', 'c2v-extract')
    raws = {split: [] for split in ('train', 'val', 'test')}
    for lang, generator in (('java', 'gen_java_corpus.py'),
                            ('csharp', 'gen_csharp_corpus.py')):
        tag = ('%d' % classes_per_lang if lang == 'java'
               else 'cs_%d' % classes_per_lang)
        corpus = os.path.join(workdir, 'corpus_%s' % tag)
        if not os.path.isdir(corpus):
            run([sys.executable,
                 os.path.join(REPO, 'scripts', generator),
                 '-o', corpus, '--classes', str(classes_per_lang)])
        for split in ('train', 'val', 'test'):
            raw = os.path.join(data, '%s_%s.raw' % (split, tag))
            if not os.path.isfile(raw):
                with open(raw, 'w') as f:
                    run([extractor, '--dir',
                         os.path.join(corpus, split),
                         '--max_path_length', '8',
                         '--max_path_width', '2',
                         '--num_threads', '16'], stdout=f)
            raws[split].append(raw)
    mixed = {}
    for split, parts in raws.items():
        mixed[split] = os.path.join(
            data, '%s_mix_%d.raw' % (split, classes_per_lang))
        if not os.path.isfile(mixed[split]):
            with open(mixed[split], 'w') as out:
                for part in parts:
                    with open(part) as f:
                        out.write(f.read())
    prefix = os.path.join(data, 'acc_mix_%d_c%d'
                          % (classes_per_lang, contexts))
    if not os.path.isfile(prefix + '.train.c2v'):
        run([sys.executable, '-m', 'code2vec_tpu.data.preprocess',
             '-trd', mixed['train'], '-vd', mixed['val'],
             '-ted', mixed['test'], '-mc', str(contexts),
             '-wvs', str(WORD_VOCAB), '-pvs', str(PATH_VOCAB),
             '-tvs', str(TARGET_VOCAB), '-o', prefix, '--seed', '0'],
            cwd=REPO)
    return prefix


def run_scenarios(args) -> None:
    """--scenarios mode (WORKLOADS.md): train a small mixed Java+C#
    model in-process, record a mixed traffic profile, replay it
    against a live mesh under the registered scenarios, and emit
    per-scenario x per-language quality rows plus the built-in
    retrieval-vs-softmax A/B and the post-warmup compile count."""
    smoke = os.environ.get('BENCH_SMOKE') == '1'
    sys.path.insert(0, REPO)
    import numpy as np
    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.telemetry import core as tele_core
    from code2vec_tpu.telemetry.jit_tracker import \
        install_compile_listener
    from code2vec_tpu.workloads import profile as profile_lib
    from code2vec_tpu.workloads import replay as replay_lib

    classes = args.classes or (2 if smoke else 48)
    epochs = args.epochs or (1 if smoke else 4)
    contexts = 8 if smoke else 16
    os.makedirs(args.workdir, exist_ok=True)
    prefix = build_mixed_dataset(args.workdir, classes, contexts)
    config = Config(
        TRAIN_DATA_PATH_PREFIX=prefix, DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=contexts,
        TRAIN_BATCH_SIZE=64, TEST_BATCH_SIZE=64,
        NUM_TRAIN_EPOCHS=epochs, SHUFFLE_BUFFER_SIZE=512,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,16',
        SERVING_SLO_AVAILABILITY=0.99,
        # the corpus index is built from predict-path code vectors
        EXPORT_CODE_VECTORS=True,
        BLEND_NEIGHBOR_WEIGHT=args.blend_weight, **CPU_DIMS)
    tele_core.enable()
    install_compile_listener()
    compiles = tele_core.registry().counter('jit/compiles_total')
    model = Code2VecModel(config)
    model.train()

    def emit(record):
        if smoke:
            record['smoke'] = True
        print(json.dumps(record), flush=True)

    mesh = None
    try:
        # retrieval index: train-split code vectors labeled with the
        # TRUE method names — the neighbor votes the blend mixes in
        with open(prefix + '.train.c2v') as f:
            train_lines = [line.rstrip('\n') for line in f if line.strip()]
        cap = 64 if smoke else 512
        train_lines = train_lines[:cap]
        vectors, labels = [], []
        for start in range(0, len(train_lines), 64):
            chunk = train_lines[start:start + 64]
            for line, row in zip(chunk, model.predict(chunk)):
                vectors.append(np.asarray(row.code_vector,
                                          dtype=np.float32))
                labels.append(line.split(' ', 1)[0])

        class _CorpusIndex:
            def __init__(self, rows, names):
                self.vectors = np.stack(rows)
                norms = np.linalg.norm(self.vectors, axis=1,
                                       keepdims=True)
                self.vectors /= np.maximum(norms, 1e-8)
                self.labels = np.array(names, dtype=object)

            def search(self, queries, k):
                q = np.atleast_2d(np.asarray(queries,
                                             dtype=np.float32))
                q = q / np.maximum(
                    np.linalg.norm(q, axis=1, keepdims=True), 1e-8)
                scores = q @ self.vectors.T
                idx = np.argsort(-scores, axis=1)[:, :k]
                return np.take_along_axis(scores, idx, axis=1), idx

        mesh = model.serving_mesh(
            replicas=1, tiers=('topk', 'vectors'),
            memo_cache_bytes=8 << 20)
        mesh.attach_index(_CorpusIndex(vectors, labels))

        profile_dir = os.path.join(args.workdir, 'profile_src')
        records = profile_lib.build_synthetic_profile(
            config, profile_dir,
            classes_per_language=max(1, classes // 4),
            seed=args.seed, rate_rps=20.0 if smoke else 50.0)
        profile_path = os.path.join(args.workdir,
                                    'mixed_profile.jsonl')
        # round-trip through the durable format: the replayed stream is
        # exactly what a recorded profile on disk would deliver
        profile_lib.write_profile(profile_path, records,
                                  meta={'source': 'synthetic'})
        _header, records = profile_lib.read_profile(profile_path)

        def relabeled(name, weight=None):
            out = []
            for record in records:
                twin = dict(record)
                twin['scenario'] = name
                if weight is not None:
                    twin['weight'] = weight
                out.append(twin)
            return out

        # warm every entry point once, then require ZERO compiles for
        # the whole mixed-scenario steady state (the acceptance gate)
        replay_lib.replay(mesh, records, pace=False, seed=args.seed,
                          limit=min(8, len(records)))
        replay_lib.replay(
            mesh, relabeled('retrieval_naming', args.blend_weight),
            pace=False, seed=args.seed, limit=min(4, len(records)))
        warm = compiles.value

        mixed = replay_lib.replay(mesh, records,
                                  rate_scale=args.rate_scale,
                                  seed=args.seed)
        softmax = replay_lib.replay(mesh, relabeled('softmax_naming'),
                                    rate_scale=args.rate_scale,
                                    seed=args.seed)
        retrieval = replay_lib.replay(
            mesh, relabeled('retrieval_naming', args.blend_weight),
            rate_scale=args.rate_scale, seed=args.seed)
        postwarm = compiles.value - warm

        rows = []
        for report in (mixed, softmax, retrieval):
            for scenario, languages in sorted(
                    report['scenarios'].items()):
                for language, cell in sorted(languages.items()):
                    row = {'measure': 'scenario_quality',
                           'scenario': scenario,
                           'language': language, **cell}
                    rows.append(row)
                    emit(row)
        slo = mixed.get('slo') or {}
        for scenario, share in sorted(
                (slo.get('scenarios') or {}).items()):
            emit({'measure': 'scenario_slo', 'scenario': scenario,
                  **share})

        def aggregate(report, name):
            scored = exact = 0
            f1_num = 0.0
            for cell in (report['scenarios'].get(name) or {}).values():
                scored += cell['scored']
                exact += round(cell['exact_match'] * cell['scored'])
                f1_num += cell['f1'] * cell['scored']
            return {'scored': scored,
                    'exact_match': exact / scored if scored else 0.0,
                    'f1': f1_num / scored if scored else 0.0}

        soft = aggregate(softmax, 'softmax_naming')
        retr = aggregate(retrieval, 'retrieval_naming')
        verdict = ('win' if retr['exact_match'] > soft['exact_match']
                   else 'tie' if retr['exact_match']
                   >= soft['exact_match'] else 'loss')
        ab = {'measure': 'retrieval_ab',
              'blend_weight': args.blend_weight,
              'softmax_exact': round(soft['exact_match'], 4),
              'retrieval_exact': round(retr['exact_match'], 4),
              'softmax_f1': round(soft['f1'], 4),
              'retrieval_f1': round(retr['f1'], 4),
              'scored': soft['scored'], 'verdict': verdict}
        emit(ab)
        emit({'measure': 'scenario_postwarm_compiles',
              'value': postwarm})
        emit({'measure': 'scenario_replay_fingerprint',
              'value': mixed['fingerprint'],
              'admitted': mixed['admitted']})

        out = args.out or os.path.join(REPO, 'benchmarks', 'results',
                                       'accuracy_scenarios.json')
        with open(out, 'w') as f:
            json.dump({'profile_records': len(records),
                       'rows': rows, 'retrieval_ab': ab,
                       'slo': slo,
                       'postwarm_compiles': postwarm,
                       'fingerprint': mixed['fingerprint'],
                       'smoke': smoke}, f, indent=1)
        print(json.dumps({'measure': 'scenarios_done',
                          'out': os.path.relpath(out, REPO)}),
              flush=True)
    finally:
        if mesh is not None:
            mesh.close()
        model.close_stores()
        tele_core.disable()
        tele_core.reset()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--workdir', default='/tmp/acc_r3')
    parser.add_argument('--profile', choices=sorted(PROFILES),
                        default='tpu')
    parser.add_argument('--epochs', type=int, default=None)
    parser.add_argument('--classes', type=int, default=None,
                        help='override corpus size (smoke runs)')
    parser.add_argument('--out', default=None,
                        help='result JSON path (default: '
                             'benchmarks/results/accuracy_<profile>.json)')
    parser.add_argument('--scenarios', action='store_true',
                        help='run the scenario traffic plane mode '
                             'instead of a learning-curve profile: '
                             'record a mixed Java+C# profile, replay '
                             'it against a live mesh, emit '
                             'per-scenario x per-language quality '
                             'rows + the retrieval-vs-softmax A/B '
                             '(WORKLOADS.md)')
    parser.add_argument('--blend-weight', type=float, default=0.5,
                        help='retrieval blend weight for the '
                             '--scenarios A/B arm')
    parser.add_argument('--rate-scale', type=float, default=4.0,
                        help='--scenarios replay pacing multiplier '
                             'over the recorded arrival times')
    parser.add_argument('--seed', type=int, default=7,
                        help='--scenarios profile + replay seed')
    args = parser.parse_args()
    if args.scenarios:
        return run_scenarios(args)
    prof = dict(PROFILES[args.profile])
    epochs = args.epochs or prof['epochs']
    if args.classes:
        prof['classes'] = args.classes

    os.makedirs(args.workdir, exist_ok=True)
    prefix = build_dataset(args.workdir, prof['classes'], prof['contexts'],
                           lang=prof.get('lang', 'java'))

    model_dir = os.path.join(args.workdir, 'model_%s' % args.profile)
    cmd = [sys.executable, '-m', 'code2vec_tpu.cli',
           '--data', prefix, '--test', prefix + '.val.c2v',
           '--save', os.path.join(model_dir, 'saved_model'),
           '--framework', 'jax', '--epochs', str(epochs),
           '--batch-size', str(prof['batch'])] + prof['extra_args']
    if args.profile.startswith('cpu'):
        # dims are Config attributes without CLI flags (reference-style):
        # drive the CLI through a tiny wrapper instead. cpu_full keeps the
        # config's real dims (128/128/384) and only pins MAX_CONTEXTS.
        dims = CPU_DIMS if args.profile == 'cpu' else {}
        wrapper = os.path.join(args.workdir, 'cli_cpu.py')
        with open(wrapper, 'w') as f:
            f.write(
                'import sys\n'
                'sys.argv[0] = "code2vec_tpu.cli"\n'
                'sys.path.insert(0, %r)\n'
                'from code2vec_tpu import cli\n'
                'from code2vec_tpu.config import Config\n'
                'overrides = %r\n'
                'original = Config.load_from_args\n'
                'def patched(self, a=None):\n'
                '    original(self, a)\n'
                '    for k, v in overrides.items():\n'
                '        setattr(self, k, v)\n'
                '    self.MAX_CONTEXTS = %d\n'
                '    return self\n'
                'Config.load_from_args = patched\n'
                'cli.main()\n' % (REPO, dims, prof['contexts']))
        cmd = [sys.executable, wrapper] + cmd[3:]

    t0 = time.time()
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    import collections
    curve = []
    lines = collections.deque(maxlen=15)  # error tail only
    pending = None  # (epoch, loss) awaiting its precision/recall/F1 tail
    for line in proc.stdout:
        lines.append(line)
        sys.stderr.write(line)
        head = EPOCH_HEAD_RE.search(line)
        if head:
            pending = (int(head.group(1)), float(head.group(2)))
        tail = EPOCH_TAIL_RE.search(line)
        if tail and pending is not None:
            point = {'epoch': pending[0],
                     'val_loss': pending[1],
                     'precision': float(tail.group(1)),
                     'recall': float(tail.group(2)),
                     'f1': float(tail.group(3)),
                     'elapsed_s': round(time.time() - t0, 1)}
            pending = None
            curve.append(point)
            print(json.dumps({'measure': 'accuracy_epoch', **point}),
                  flush=True)
    rc = proc.wait()
    if rc != 0:
        print(json.dumps({'error': 'train_failed', 'rc': rc,
                          'tail': ''.join(lines)[-2000:]}))
        sys.exit(1)

    out = args.out or os.path.join(
        REPO, 'benchmarks', 'results',
        'accuracy_%s.json' % args.profile)
    baseline = majority_baseline(prefix)
    # corpus-shape evidence (VERDICT r3 #6): Zipf slopes, singleton tail,
    # contexts/method spread vs the reference anchors
    raw_train = os.path.join(os.path.dirname(prefix),
                             'train_%d.raw' % prof['classes'])
    result = {
        'profile': args.profile,
        'dataset': {'word_vocab': WORD_VOCAB, 'path_vocab': PATH_VOCAB,
                    'target_vocab': TARGET_VOCAB,
                    'classes': prof['classes'],
                    'max_contexts': prof['contexts'],
                    'batch': prof['batch'],
                    **dataset_stats(prefix, raw_train)},
        'corpus_stats': {
            'ours': corpus_stats_mod.scan(raw_train),
            'reference_anchor': corpus_stats_mod.REFERENCE_ANCHOR},
        'curve': curve,
        'best_f1': max((p['f1'] for p in curve), default=0.0),
        'majority_baseline': baseline,
        'total_s': round(time.time() - t0, 1),
    }
    with open(out, 'w') as f:
        json.dump(result, f, indent=1)
    print(json.dumps({'measure': 'accuracy_at_scale_best_f1',
                      'value': result['best_f1'],
                      'out': os.path.relpath(out, REPO)}), flush=True)


if __name__ == '__main__':
    main()
