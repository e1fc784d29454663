"""Full-pipeline integration test (VERDICT r1 #5).

Drives the WHOLE chain the way a user would, end to end:

  Java sources → `c2v-extract --dir` (native binary, via
  scripts/preprocess.sh exactly as documented) → histograms + vocab-aware
  sampling → `.c2v`/`.dict.c2v` → training CLI with per-epoch eval →
  F1 above threshold → `--release` → load the released model → predict
  through the extractor bridge.

A format drift anywhere in the chain (extractor output, preprocess
padding, dict pickle layout, checkpoint naming, release artifact) fails
this test.  Mirrors the reference flow preprocess.sh:41-63 + train.sh +
README's release/predict walkthrough.
"""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXTRACTOR = os.path.join(REPO, 'extractor', 'build', 'c2v-extract')

pytestmark = pytest.mark.skipif(not os.path.isfile(EXTRACTOR),
                                reason='extractor binary not built')

# Method templates: the name is fully determined by the body shape, so a
# tiny model must overfit. Fields vary per class for vocab variety.
TEMPLATES = [
    ('get{F}', 'int get{F}() {{ return this.{f}; }}'),
    ('set{F}', 'void set{F}(int value) {{ this.{f} = value; }}'),
    ('has{F}', 'boolean has{F}() {{ return this.{f} > 0; }}'),
    ('reset{F}', 'void reset{F}() {{ this.{f} = 0; }}'),
]
FIELDS = ['width', 'height', 'depth']


def _write_project(root, n_classes: int, seed_offset: int = 0) -> None:
    os.makedirs(root, exist_ok=True)
    for i in range(n_classes):
        field = FIELDS[(i + seed_offset) % len(FIELDS)]
        methods = '\n'.join(
            body.format(F=field.capitalize(), f=field)
            for _name, body in TEMPLATES)
        with open(os.path.join(root, f'C{seed_offset}_{i}.java'), 'w') as f:
            f.write('class C%d_%d {\n  int %s;\n%s\n}\n'
                    % (seed_offset, i, field, methods))


def _env() -> dict:
    # a minimal environment: venv python, repo-only PYTHONPATH, CPU pin
    return {
        'PATH': os.pathsep.join([os.path.dirname(sys.executable),
                                 '/usr/bin', '/bin']),
        'HOME': os.environ.get('HOME', '/root'),
        'PYTHONPATH': REPO,
        'JAX_PLATFORMS': 'cpu',
    }


def _run(cmd, cwd, timeout=420, **extra_env):
    proc = subprocess.run(cmd, cwd=cwd, env={**_env(), **extra_env},
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        'command %r failed:\nstdout: %s\nstderr: %s'
        % (cmd, proc.stdout[-3000:], proc.stderr[-3000:]))
    return proc.stdout + proc.stderr


def test_full_pipeline_extract_train_release_predict(tmp_path):
    # --- offline dataset production via the documented script -----------
    _write_project(tmp_path / 'dataset' / 'train', n_classes=30)
    _write_project(tmp_path / 'dataset' / 'train', n_classes=30,
                   seed_offset=1)
    _write_project(tmp_path / 'dataset' / 'val', n_classes=4)
    _write_project(tmp_path / 'dataset' / 'test', n_classes=4,
                   seed_offset=2)
    _run(['bash', os.path.join(REPO, 'scripts', 'preprocess.sh')],
         cwd=str(tmp_path),  # env defaults: dataset/{train,val,test}
         EXTRACTOR=EXTRACTOR, NUM_THREADS='8')
    # preprocess.sh env defaults name the dataset java14m
    data_prefix = tmp_path / 'data' / 'java14m' / 'java14m'
    for suffix in ['.train.c2v', '.val.c2v', '.test.c2v', '.dict.c2v']:
        assert (str(data_prefix) + suffix), suffix
        assert os.path.getsize(str(data_prefix) + suffix) > 0

    # every train row is padded to exactly MAX_CONTEXTS fields
    with open(str(data_prefix) + '.train.c2v') as f:
        first = f.readline().rstrip('\n')
    assert len(first.split(' ')) == 1 + 200  # preprocess.sh default

    # --- train with per-epoch eval via the CLI --------------------------
    save_path = tmp_path / 'models' / 'pipe' / 'saved_model'
    out = _run([sys.executable, '-m', 'code2vec_tpu.cli',
                '--data', str(data_prefix),
                '--test', str(data_prefix) + '.val.c2v',
                '--save', str(save_path),
                '--epochs', '12', '--batch-size', '16',
                '--framework', 'jax', '--dtype', 'float32'],
               cwd=str(tmp_path), timeout=540)
    f1_scores = [float(m) for m in re.findall(r'F1: ([0-9.]+)', out)]
    assert f1_scores, 'no eval F1 reported:\n' + out[-2000:]
    # name is a deterministic function of the body: must overfit
    assert f1_scores[-1] > 0.5, out[-2000:]

    # --- release + load released + evaluate -----------------------------
    _run([sys.executable, '-m', 'code2vec_tpu.cli',
          '--load', str(save_path), '--release'], cwd=str(tmp_path))
    assert (tmp_path / 'models' / 'pipe'
            / 'saved_model__only-weights').is_dir()
    out = _run([sys.executable, '-m', 'code2vec_tpu.cli',
                '--load', str(save_path),
                '--test', str(data_prefix) + '.val.c2v'],
               cwd=str(tmp_path))
    released_f1 = [float(m) for m in re.findall(r'F1: ([0-9.]+)', out)]
    assert released_f1 and abs(released_f1[-1] - f1_scores[-1]) < 1e-6

    # --- predict through the real extractor bridge ----------------------
    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.serving.extractor_bridge import Extractor
    from code2vec_tpu.serving.predict import predict_file

    input_java = tmp_path / 'Input.java'
    input_java.write_text(
        'class Q { int width; int getWidth() { return this.width; } }\n')
    config = Config(MODEL_LOAD_PATH=str(save_path), DL_FRAMEWORK='jax',
                    COMPUTE_DTYPE='float32', VERBOSE_MODE=0,
                    READER_USE_NATIVE=False)
    model = Code2VecModel(config)
    extractor = Extractor(config, extractor_command=[EXTRACTOR])
    reports = predict_file(model, extractor, str(input_java))
    assert len(reports) == 1
    method_result, _raw = reports[0]
    assert method_result.original_name == 'get|width'
    # prediction names are subtoken lists (reference common.py:135-158)
    top_names = [p['name'] for p in method_result.predictions]
    assert ['get', 'width'] in top_names[:3], top_names


CS_TEMPLATES = [
    ('Get{F}', 'int Get{F}() {{ return this.{f}; }}'),
    ('Set{F}', 'void Set{F}(int value) {{ this.{f} = value; }}'),
    ('Has{F}', 'bool Has{F}() {{ return this.{f} > 0; }}'),
    ('Reset{F}', 'void Reset{F}() {{ this.{f} = 0; }}'),
]


def _write_cs_project(root, n_classes: int, seed_offset: int = 0) -> None:
    os.makedirs(root, exist_ok=True)
    for i in range(n_classes):
        field = FIELDS[(i + seed_offset) % len(FIELDS)]
        methods = '\n'.join(
            body.format(F=field.capitalize(), f=field)
            for _name, body in CS_TEMPLATES)
        with open(os.path.join(root, f'C{seed_offset}_{i}.cs'), 'w') as f:
            f.write('class C%d_%d {\n  int %s;\n%s\n}\n'
                    % (seed_offset, i, field, methods))


def test_full_pipeline_csharp(tmp_path):
    """BASELINE.json acceptance config: 'C# method-name prediction
    (CSharpExtractor -> path_context_reader)' — the documented
    preprocess_csharp.sh flow end to end into training + eval."""
    _write_cs_project(tmp_path / 'dataset' / 'train', n_classes=30)
    _write_cs_project(tmp_path / 'dataset' / 'train', n_classes=30,
                      seed_offset=1)
    _write_cs_project(tmp_path / 'dataset' / 'val', n_classes=4)
    _write_cs_project(tmp_path / 'dataset' / 'test', n_classes=4,
                      seed_offset=2)
    _run(['bash', os.path.join(REPO, 'scripts', 'preprocess_csharp.sh')],
         cwd=str(tmp_path), EXTRACTOR=EXTRACTOR, NUM_THREADS='8')
    data_prefix = tmp_path / 'data' / 'csharp' / 'csharp'
    for suffix in ['.train.c2v', '.val.c2v', '.test.c2v', '.dict.c2v']:
        assert os.path.getsize(str(data_prefix) + suffix) > 0, suffix

    out = _run([sys.executable, '-m', 'code2vec_tpu.cli',
                '--data', str(data_prefix),
                '--test', str(data_prefix) + '.val.c2v',
                '--save', str(tmp_path / 'models' / 'cs' / 'saved_model'),
                '--epochs', '12', '--batch-size', '16',
                '--framework', 'jax', '--dtype', 'float32'],
               cwd=str(tmp_path), timeout=540)
    f1_scores = [float(m) for m in re.findall(r'F1: ([0-9.]+)', out)]
    assert f1_scores, 'no eval F1 reported:\n' + out[-2000:]
    assert f1_scores[-1] > 0.5, out[-2000:]
