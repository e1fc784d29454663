"""What the benchmark needs of the program (``chipbench/configs/*.json``,
read here and never written): the ``Config`` fields its configurations
pin by name, and at toy widths the comparison with
``chipbench/reference.py`` that decides ``correct`` on the chip. A PR
that removes a pinned field, or moves the program's numbers, fails here
and not in the chip run."""
import glob
import json
import os
import pickle
import types

import numpy as np
import pytest

from chipbench.runners import common
from code2vec_tpu.model_api import Code2VecModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_NAMES = sorted(
    os.path.splitext(os.path.basename(path))[0]
    for path in glob.glob(os.path.join(REPO, 'chipbench', 'configs',
                                       '*.json')))
#: the cut of chipbench/tests/test_reference.py, and float32 so that the
#: two writings of the equations agree to rounding
TOY = dict(MAX_CONTEXTS=7, MAX_TOKEN_VOCAB_SIZE=40, MAX_PATH_VOCAB_SIZE=24,
           MAX_TARGET_VOCAB_SIZE=16, TOKEN_EMBEDDINGS_SIZE=4,
           PATH_EMBEDDINGS_SIZE=6, CODE_VECTOR_SIZE=8,
           TARGET_EMBEDDINGS_SIZE=8, TRAIN_BATCH_SIZE=16,
           TEST_BATCH_SIZE=16, COMPUTE_DTYPE='float32')
TOLERANCE = 1e-5


def context_for(name, **cut):
    """As much of a run's ``common.Context`` as building a model reads."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           name + '.json')) as f:
        spec = json.load(f)
    settings = dict(spec['settings'])
    settings.update({k: v for k, v in cut.items() if k in settings})
    return types.SimpleNamespace(
        settings=settings, cell=types.SimpleNamespace(config_name=name))


@pytest.mark.parametrize('name', CONFIG_NAMES)
def test_settings_are_config_fields_and_verify(name):
    config = common.make_config(context_for(name),
                                TRAIN_DATA_PATH_PREFIX='data/prefix')
    config.verify()


def write_corpus(tmp_path, methods=16, tags=12):
    """``methods`` lines over 30 tokens, 20 paths and ``tags`` names (more
    than the ten a prediction returns), and their dictionary."""
    rng = np.random.default_rng(3)
    counts = ({}, {}, {})
    lines = []
    for method in range(methods):
        words = [(rng.integers(30), rng.integers(20), rng.integers(30))
                 for _ in range(rng.integers(1, TOY['MAX_CONTEXTS'] + 1))]
        tag = 'name|n%d' % (method % tags)
        lines.append(' '.join([tag] + ['t%d,p%d,t%d' % w for w in words]))
        for table, keys in zip(counts, (
                [k for s, _, t in words for k in ('t%d' % s, 't%d' % t)],
                ['p%d' % p for _, p, _ in words], [tag])):
            for key in keys:
                table[key] = table.get(key, 0) + 1
    prefix = str(tmp_path / 'toy')
    with open(prefix + '.train.c2v', 'w') as f:
        f.write('\n'.join(lines) + '\n')
    with open(prefix + '.dict.c2v', 'wb') as f:
        for table in counts:
            pickle.dump(table, f)
        pickle.dump(len(lines), f)
    return prefix, lines


def language_model_pieces(family):
    """(the runner that names the model's config.json keys and maps the
    program's weights to the reference's, the reference) of a language
    model's family."""
    if family == 'mellum':
        from chipbench import reference_mellum2
        from chipbench.runners import serve_lm
        return serve_lm, reference_mellum2
    if family == 'minicpm_sala':
        from chipbench import reference_minicpm_sala
        from chipbench.runners import serve_lm_sessions
        return serve_lm_sessions, reference_minicpm_sala
    if family == 'mistral4':
        from chipbench import reference_mistral4
        from chipbench.runners import serve_lm_latent
        return serve_lm_latent, reference_mistral4
    raise AssertionError('no reference is known for family %r' % family)


def language_model_agrees_at_toy_width(tmp_path, name, family):
    """A language model's configuration at its own ``rehearsal`` widths, in
    float32: a prompt longer than a ring and than ``dense_len``, through
    chunked prefill and decode (every layer kind the widths hold, the
    sparse branch where there is one, latent pages and a share of the
    experts where the model has them), against the family's reference."""
    from chipbench import run
    from code2vec_tpu import model_api
    runner, reference = language_model_pieces(family)
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           name + '.json')) as f:
        spec = json.load(f)
    spec = run.merged(spec, spec['rehearsal'])
    model_config = {k: spec[k] for k in runner.MODEL_KEYS if k in spec}
    path = tmp_path / 'config.json'
    path.write_text(json.dumps(model_config))
    ctx = types.SimpleNamespace(
        settings=dict(spec['settings'], COMPUTE_DTYPE='float32'),
        cell=types.SimpleNamespace(config_name=name))
    model = model_api.create_model(common.make_config(
        ctx, LM_CONFIG_PATH=str(path), LM_PARAM_SEED=5, VERBOSE_MODE=0))
    prompt = np.random.default_rng(3).integers(
        0, model_config['vocab_size'], 75)
    with model.serving_engine() as engine:
        result = engine.submit(prompt, tier='generate', max_new_tokens=6,
                               return_logits=True).result(timeout=300)
        if family == 'minicpm_sala':
            lm = engine.stats()['lm']
            assert lm['sparse_blocks_chosen_total'] > 0   # the sparse branch
            assert lm['sparse_dense_branch_total'] > 0    # and the dense one
        if family == 'mistral4':
            lm = engine.stats()['lm']
            # decode rows read latent pages, chunks up-projected them, and
            # some of the routing choices fell on the experts held
            assert lm['latent_positions_read_total'] > 0
            assert lm['latent_positions_upprojected_total'] > 0
            assert 0 < lm['held_choices_total'] < \
                lm['routing_choices_total']
    wanted = np.asarray(reference.forward(
        model_config, runner.reference_weights(model.params, model_config),
        np.concatenate([prompt, result.token_ids[:-1]]),
        first_logit=len(prompt) - 1))
    np.testing.assert_allclose(
        np.stack([np.asarray(row) for row in result.logits]), wanted,
        atol=1e-4)


@pytest.mark.parametrize('name', CONFIG_NAMES)
def test_program_agrees_with_reference_at_toy_width(tmp_path, name):
    family = context_for(name).settings.get('MODEL_FAMILY', 'code2vec')
    if family != 'code2vec':
        return language_model_agrees_at_toy_width(tmp_path, name, family)
    prefix, lines = write_corpus(tmp_path)
    ctx = context_for(name, **TOY)
    devices = (ctx.settings['MESH_DATA_AXIS_SIZE']
               * ctx.settings['MESH_MODEL_AXIS_SIZE'])
    model = Code2VecModel(common.make_config(
        ctx, TRAIN_DATA_PATH_PREFIX=prefix, VERBOSE_MODE=0,
        READER_USE_NATIVE=False,
        MESH_DEVICE_INDICES=','.join(map(str, range(devices)))))
    assert model.trainer.mesh.size == devices
    if 'TRAIN_BATCH_SIZE' in ctx.settings:
        system = common.system_eval(model, lines)
        wanted = common.reference_eval(model, lines, system['top_indices'])
        assert abs(system['loss'] - wanted['loss']) <= TOLERANCE
        np.testing.assert_allclose(system['top_logits'], wanted['logits'],
                                   atol=TOLERANCE)
    else:
        faults = common.check_results(
            model, lines, model.predict(lines), 'attention',
            dict.fromkeys(('logit', 'score', 'vector', 'attention'),
                          TOLERANCE))
        assert faults == []
