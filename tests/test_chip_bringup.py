"""Bring-up pieces (ISSUE 21), CPU only and cheap: chip_smoke.py's device
gate and its labelled rehearsal, the compile-cache placement rule, the
typed error of a forced Pallas kernel off-TPU, and the typed refusal of
locally spawned mesh workers under a TPU-holding parent."""
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')


def _run(cmd, cwd, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS='cpu', **env_overrides)
    env.pop('XLA_FLAGS', None)  # one CPU device, like a bare sandbox
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def test_chip_smoke_without_a_chip_exits_nonzero_and_names_the_platform(
        tmp_path):
    proc = _run([sys.executable, SMOKE], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "platform=cpu device_kind='cpu' devices=1" in proc.stdout
    assert "needs a TPU, JAX found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / 'chip_smoke.py')
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    proc = subprocess.run([sys.executable, 'chip_smoke.py'],
                          cwd=str(tmp_path), env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert 'code2vec_tpu' in proc.stderr  # the import that failed


def test_chip_smoke_rehearsal_runs_every_phase_and_is_labelled(tmp_path):
    proc = _run([sys.executable, SMOKE, '--rehearse-on-cpu'],
                cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert json.loads(lines[-1]) == {
        'ok': True, 'rehearsal': True,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 1}}
    assert 'REHEARSAL' in lines[0]
    (report_line,) = [ln for ln in lines
                      if ln.startswith('chip_smoke report [REHEARSAL]: ')]
    report = json.loads(report_line.split(': ', 1)[1])
    assert set(report['seconds']) == {
        'dataset', 'train_eval_save', 'restore_eval', 'engine_warmup',
        'serve', 'unfused_reference_eval'}
    assert report['train_steps'] >= 8
    assert report['train_step_programs'] >= 2
    assert report['restored_eval_loss'] == report['trained_eval_loss']
    assert report['compiles_before_serving'] > 0
    assert report['compiles_while_serving'] == 0
    assert report['mosaic_engaged'] is False  # CPU: the jnp twin ran


def test_compile_cache_sets_nothing_when_the_env_var_places_it(
        monkeypatch, tmp_path):
    from code2vec_tpu import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda *args: updates.append(args))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / 'outside'))
    assert compile_cache.configure() == str(tmp_path / 'outside')
    assert updates == []
    # unset: the one fixed directory inside the checkout
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.configure() == os.path.join(REPO, '.jax_cache')
    assert updates == [('jax_compilation_cache_dir',
                        os.path.join(REPO, '.jax_cache'))]


def test_compile_cache_path_is_the_same_from_another_process(tmp_path):
    code = ('from code2vec_tpu import compile_cache; import jax; '
            'print(compile_cache.configure()); '
            'print(jax.config.jax_compilation_cache_dir)')
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env['PYTHONPATH'] = REPO
    proc = subprocess.run([sys.executable, '-c', code], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [os.path.join(REPO, '.jax_cache')] * 2


def test_forced_kernel_off_tpu_raises_typed():
    """No interpret=True, no interpret_kernels(): a kernel that is asked
    for on this CPU platform fails typed — it does not reach the
    interpreter or a twin."""
    from code2vec_tpu.models import functional
    from code2vec_tpu.ops import pallas_ce, pallas_encode, pallas_ragged
    from code2vec_tpu.ops._pallas_common import KernelRequiresTPU
    rng = np.random.default_rng(0)
    code = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    label = jnp.zeros((8,), jnp.int32)
    weight = jnp.ones((8,), jnp.float32)
    with pytest.raises(KernelRequiresTPU, match="devices are 'cpu'"):
        pallas_ce.fused_weighted_ce_sums(table, code, label, weight, 16)
    rows = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(KernelRequiresTPU):
        pallas_encode.fused_context_transform(
            rows, rows, rows, jnp.ones((24, 16)), jnp.ones((16, 1)))
    params = functional.init_params(
        jax.random.PRNGKey(0), token_vocab_size=8, path_vocab_size=8,
        target_vocab_size=8, token_dim=8, path_dim=8, code_dim=16)
    ctx = jnp.zeros((1, 64, 3), jnp.int32).at[0, :5].set(1)
    count = jnp.asarray([3, 2], jnp.int32)
    with pytest.raises(KernelRequiresTPU):
        pallas_ragged.ragged_encode(
            params.token_embedding, params.path_embedding,
            params.transform, params.attention, ctx, count,
            max_contexts=4, token_pad=0, path_pad=0, use_kernel=True)
    # the twin needs no device
    code_vectors, _ = pallas_ragged.ragged_encode(
        params.token_embedding, params.path_embedding, params.transform,
        params.attention, ctx, count, max_contexts=4, token_pad=0,
        path_pad=0, use_kernel=False)
    assert np.isfinite(np.asarray(code_vectors)).all()


@pytest.mark.parametrize('mode', ['process', 'socket'])
def test_worker_mode_mesh_refused_typed_under_a_tpu_holding_parent(mode):
    """The refusal happens at construction, before any spawn: a stand-in
    model whose mesh devices report 'tpu' is enough to reach it."""
    from code2vec_tpu.config import Config
    from code2vec_tpu.serving.errors import (LocalWorkerNeedsHeldChip,
                                             ServingError)
    from code2vec_tpu.serving.mesh import ServingMesh
    device = types.SimpleNamespace(platform='tpu')
    model = types.SimpleNamespace(
        config=Config(TRAIN_DATA_PATH_PREFIX='unused', VERBOSE_MODE=0),
        mesh=types.SimpleNamespace(
            devices=types.SimpleNamespace(flat=[device])))
    with pytest.raises(LocalWorkerNeedsHeldChip,
                       match='already in use by process') as excinfo:
        ServingMesh(model, replicas=1, mode=mode)
    assert isinstance(excinfo.value, ServingError)
