"""The traced run's reduction as run.py makes it, off the chip: the
committed TPU trace of five full-fill train steps through ``read_layers``
and the train cell's readers, with the chip's peaks."""
import os
import types

import pytest

from chipbench import manifest, run
from chipbench.runners import common

ROOT = manifest.ROOT
#: the allocated rows of the java14m tables, as the backend reports them
SIZES = {'token_vocab_size': 1301248, 'path_vocab_size': 911488,
         'target_vocab_size': 261248, 'token_dim': 128, 'path_dim': 128,
         'code_dim': 384}


def test_read_layers_on_the_committed_tpu_trace(tmp_path):
    cell = manifest.load_cell('train-corpus')
    os.symlink(os.path.join(ROOT, 'profiles', 'java14m_step'),
               tmp_path / 'trace')
    ctx = common.Context(
        cell=cell, seed=1, trace=True, rehearsal=False, data_root='',
        run_dir=str(tmp_path), config=cell.config,
        settings=cell.config['settings'], traffic=cell.traffic,
        spans={'lifecycle.start_s': 12.0, 'lifecycle.build_s': 6.0})
    model = types.SimpleNamespace(
        backend=types.SimpleNamespace(sizes=SIZES),
        config=types.SimpleNamespace(ADAM_MU_DTYPE='bfloat16',
                                     ADAM_NU_DTYPE='bfloat16'))
    peaks = manifest.read_json(os.path.join(
        manifest.PACKAGE_DIR, 'peaks.json'))['TPU v5 lite']
    started = run.Started(
        ctx=ctx, runner=types.SimpleNamespace(model=model),
        readers=manifest.layer_readers(cell.per_layer),
        compiles=types.SimpleNamespace(value=56, cache_hits=56,
                                       cache_misses=0),
        device={'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1},
        used=[], peaks=peaks, age_at_t0=0.0)
    obs = {'examples_per_step_per_chip': 1024, 'mean_contexts': 200.0,
           'examples_per_sec_per_chip': 17165.7,
           'whole_window_examples_per_sec_per_chip': 17000.0,
           'instruments': (None, None),
           'memory_at_window_end': {'peak_bytes': 4841576448,
                                    'bytes_reserved': 1752219648}}
    result = {}
    values = run.read_layers(started, obs, result)
    assert started.device['busy_s'] == pytest.approx(0.2297, abs=1e-3)
    assert started.device['busy_s'] < started.device['window_s'] < 0.3
    assert len(result['breakdown']['device_ops']) == 10
    assert result['breakdown']['idle_gaps']
    assert values['model.device_ms_per_step'] == pytest.approx(45.95,
                                                               abs=0.05)
    # full fill: the floor is the 12.4 ms of fill 0.18 and the gathers and
    # scatters of five times the contexts
    assert 25 < values['kernels.step_roofline'] < 45
    assert 0 < values['model.mfu'] < 30
    assert 10 < values['device.idle_share'] < 25
    assert values['device.peak_hbm_bytes'] == 4841576448
    assert values['device.reserved_bytes'] == 1752219648
    assert values['lifecycle.compiles'] == values['lifecycle.cache_hits'] == 56
    # one chip: no collective, so the mesh's metrics are left out
    assert not any(name.startswith('mesh.') for name in values)
    # every value belongs to a metric of the cell
    wanted = {m['name'].rsplit('-', 1)[0] for m in cell.per_layer} \
        | {m['name'] for m in cell.per_layer}
    assert set(values) <= wanted
