"""The model seam: which model a configuration names, and what that model
declares to the layers that serve it.

``Config.MODEL_FAMILY`` selects a model; ``Config.DL_FRAMEWORK`` goes on
selecting the framework of the ``code2vec`` family's one set of equations
(``models/backends.py::create_backend``).  A family declares what the
serving path has to know without knowing the model:

- ``input_layout``: what one request is made of;
- ``tiers``: the output tiers its engine serves (``ServingEngine`` checks
  a requested tier against these, and keeps one queue a tier);
- ``param_specs``: how its parameter pytree lies on a device mesh;
- ``reference``: the plain float32 statement of its equations that tests
  and the benchmark's check compare with;
- ``module``: the module that holds its equations; for a language model
  also the seam the step loop serves it through (its configuration,
  weights, step program and the host's side of a step:
  ``serving/lm_scheduler.py``);
- ``build(config)``: the model behind ``model_api.create_model``, with
  ``serving_engine()`` and ``close_stores()``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple


class ModelFamily(NamedTuple):
    name: str
    input_layout: str
    tiers: Tuple[str, ...]
    param_specs: Callable
    reference: str
    module: str
    build: Callable


def _code2vec_param_specs(*args, **kwargs):
    from code2vec_tpu.parallel import mesh as mesh_lib
    return mesh_lib.param_specs(*args, **kwargs)


def _build_code2vec(config):
    from code2vec_tpu.model_api import Code2VecModel
    return Code2VecModel(config)


def _decoder_param_specs(params):
    """Every array whole on every device: a decoder serves from one chip.
    What that chip holds of a layer is the model's configuration to say (a
    share of the experts: ``n_routed_experts`` from ``first_held_expert``,
    ``ops/grouped_experts.py``), not a sharding of the arrays here."""
    import jax
    from jax.sharding import PartitionSpec
    return jax.tree_util.tree_map(lambda _: PartitionSpec(), params)


def _build_decoder(config):
    from code2vec_tpu.model_api import DecoderLMModel
    return DecoderLMModel(config)


FAMILIES = {
    'code2vec': ModelFamily(
        name='code2vec',
        input_layout='a bag of <= MAX_CONTEXTS (source token, path, target '
                     'token) contexts a method, packed wire '
                     '(data/packed.py)',
        # training/trainer.py::PREDICT_TIERS, without importing the trainer
        tiers=('topk', 'attention', 'full', 'vectors'),
        param_specs=_code2vec_param_specs,
        reference='chipbench/reference.py',
        module='code2vec_tpu.models.functional',
        build=_build_code2vec),
    # every expert of each layer held on one chip: the expert layer can hold
    # a share (the mistral4 family's), this configuration holds them all
    'mellum': ModelFamily(
        name='mellum',
        input_layout='a prompt of token ids and max_new_tokens; a step is '
                     'a flat batch of tokens with per-sequence cache '
                     'metadata (models/decoder.py::batch_shapes)',
        tiers=('generate',),
        param_specs=_decoder_param_specs,
        reference='chipbench/reference_mellum2.py',
        module='code2vec_tpu.models.decoder',
        build=_build_decoder),
    'minicpm_sala': ModelFamily(
        name='minicpm_sala',
        input_layout='a prompt of token ids, max_new_tokens and optionally '
                     'a session whose cache stays resident between turns; '
                     'a step is a flat batch of tokens with per-sequence '
                     'page tables and state slots '
                     '(models/hybrid_decoder.py::batch_shapes)',
        tiers=('generate',),
        param_specs=_decoder_param_specs,
        reference='chipbench/reference_minicpm_sala.py',
        module='code2vec_tpu.models.hybrid_decoder',
        build=_build_decoder),
    'mistral4': ModelFamily(
        name='mistral4',
        input_layout='a prompt of token ids (no image inputs), '
                     'max_new_tokens and optionally a session whose cache '
                     'stays resident between turns; a step is a flat batch '
                     'of tokens with per-sequence page tables of latent '
                     'pages (models/latent_decoder.py::batch_shapes)',
        tiers=('generate',),
        param_specs=_decoder_param_specs,
        reference='chipbench/reference_mistral4.py',
        module='code2vec_tpu.models.latent_decoder',
        build=_build_decoder),
}


def family_of(config) -> ModelFamily:
    name = getattr(config, 'MODEL_FAMILY', 'code2vec')
    if name not in FAMILIES:
        raise ValueError('Unknown MODEL_FAMILY: %r (have %s)'
                         % (name, ', '.join(sorted(FAMILIES))))
    return FAMILIES[name]
