"""The two swappable model backends: 'jax' (raw pytree) and 'flax' (linen).

Mirrors the reference's runtime-selected dual backends (TF1 graph vs
tf.keras, reference code2vec.py:7-13) in a TPU-native way: both call the
same pure math in :mod:`code2vec_tpu.models.functional`; they differ only in
how parameters are created and stored. The trainer and serving layers are
backend-agnostic — a backend exposes:

- ``init(rng) -> params``                   (pytree of fp32 arrays)
- ``loss_fn(params, arrays, dropout_rng, mesh=None)`` → (loss, aux)
- ``forward(params, arrays)``               → (code_vectors, attention, logits)
- ``named_params(params) -> Code2VecParams`` (for export / sharding)
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.config import Config
from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.models import functional
from code2vec_tpu.models.flax_model import Code2VecModule
from code2vec_tpu.vocab import Code2VecVocabs

# arrays order produced by Batch.device_arrays()
# (source, path, target, mask, label, weight)


def compute_dtype(config: Config) -> jnp.dtype:
    return jnp.bfloat16 if config.COMPUTE_DTYPE == 'bfloat16' else jnp.float32


def target_row_alignment(config: Config) -> int:
    """Row alignment of the TARGET table allocation. Folds in the fused-CE
    tile so the kernel's own pad is a no-op (otherwise every step would
    physically copy the ~400 MB table to a tile multiple, twice); on a
    model-sharded mesh the kernel streams PER-SHARD rows, so the no-copy
    condition is V/model_axis % VOCAB_TILE == 0. The resulting padded row
    count is recorded in checkpoint metadata ('target_vocab_rows') since
    it determines the saved array's shape; restore ADAPTS a differing row
    count by padding/slicing the masked padding rows (checkpoints.py), so
    the allocation being topology-dependent does not make checkpoints
    topology-dependent (ADVICE r3)."""
    align = max(config.PARAM_ROW_ALIGNMENT, 1)
    if config.USE_PALLAS_FUSED_CE:
        import math

        from code2vec_tpu.ops.pallas_ce import VOCAB_TILE
        align = math.lcm(align,
                         VOCAB_TILE * max(config.MESH_MODEL_AXIS_SIZE, 1))
    return align


class JaxBackend:
    """Raw functional backend: params are a ``Code2VecParams`` NamedTuple."""

    name = 'jax'

    def __init__(self, config: Config, vocabs: Code2VecVocabs):
        self.config = config
        align = max(config.PARAM_ROW_ALIGNMENT, 1)
        # fused CE grows the target alignment to its vocab tile; padded
        # columns are masked by num_valid_targets everywhere, so only the
        # allocation grows
        target_align = target_row_alignment(config)
        # tables padded for even row-sharding over the model axis; padded
        # token/path rows are never gathered, padded target columns are
        # masked out of the softmax via num_valid_targets
        self.num_valid_targets = vocabs.target_vocab.size
        # PAD indices for the packed wire format's device-side unpack
        # (data/packed.py): must match the reader's pack-time fill.
        # SizeOnlyVocabs (benchmarks/graft) carries no pad_index — the
        # joined PAD==OOV policy puts both at 0 there.
        self.token_pad_index = getattr(vocabs.token_vocab, 'pad_index', 0)
        self.path_pad_index = getattr(vocabs.path_vocab, 'pad_index', 0)
        # the packer pads its touched-row arrays past these two counts
        token_rows, path_rows = packed_lib.embedding_table_rows(vocabs,
                                                                align)
        self.sizes = dict(
            token_vocab_size=token_rows, path_vocab_size=path_rows,
            target_vocab_size=packed_lib.table_rows(
                vocabs.target_vocab.size, target_align),
            token_dim=config.TOKEN_EMBEDDINGS_SIZE,
            path_dim=config.PATH_EMBEDDINGS_SIZE,
            code_dim=config.CODE_VECTOR_SIZE)
        self.dtype = compute_dtype(config)

    def init(self, rng: jax.Array) -> functional.Code2VecParams:
        return functional.init_params(rng, **self.sizes)

    def param_shapes(self) -> functional.Code2VecParams:
        return functional.param_shapes(**self.sizes)

    def loss_fn(self, params, arrays, dropout_rng,
                mesh=None) -> Tuple[jax.Array, Any]:
        source, path, target, mask, label, weight = arrays
        return functional.loss_and_aux(
            params, source, path, target, mask, label, weight,
            dropout_rng=dropout_rng,
            dropout_keep_rate=self.config.DROPOUT_KEEP_RATE,
            dropout_prng_impl=self.config.DROPOUT_PRNG_IMPL,
            dtype=self.dtype, num_valid_targets=self.num_valid_targets,
            use_fused_ce=self.config.USE_PALLAS_FUSED_CE,
            fused_ce_mesh=mesh,
            remat_encode=self.config.REMAT_ENCODE)

    def forward(self, params, arrays):
        source, path, target, mask = arrays[:4]
        code_vectors, attention = functional.encode(
            params, source, path, target, mask, dtype=self.dtype,
            use_pallas=self.config.USE_PALLAS_FUSED_ENCODE)
        logits = functional.compute_logits(
            params, code_vectors, dtype=self.dtype,
            num_valid_targets=self.num_valid_targets)
        return code_vectors, attention, logits

    def loss_fn_packed(self, params, packed_arrays, dropout_rng,
                       mesh=None) -> Tuple[jax.Array, Any]:
        """``loss_fn`` straight off the packed wire (USE_PALLAS_RAGGED_
        FUSION): the ragged fused encoder consumes the (D, cap, 3)
        triples + counts directly — no device-side unpack, no (B, C, .)
        planes — and its custom VJP recomputes the backward off the same
        segments instead of storing per-slot residuals
        (ops/pallas_ragged.py). RAGGED_TRAIN_KERNEL additionally routes
        both train passes through the Pallas kernel pair (TPU only: off
        a TPU the forced kernel raises ``KernelRequiresTPU``); off, the
        jnp twin pair runs — the default pending the >=2% flip verdict
        (scripts/flip_verdict.py). A training batch's touched-row
        arrays, where it has them, follow the four wire arrays."""
        ctx, count, label, weight = packed_arrays[:4]
        return functional.loss_and_aux_packed(
            params, ctx, count, label, weight,
            max_contexts=self.config.MAX_CONTEXTS,
            token_pad=self.token_pad_index,
            path_pad=self.path_pad_index,
            dropout_rng=dropout_rng,
            dropout_keep_rate=self.config.DROPOUT_KEEP_RATE,
            dropout_prng_impl=self.config.DROPOUT_PRNG_IMPL,
            dtype=self.dtype, num_valid_targets=self.num_valid_targets,
            use_fused_ce=self.config.USE_PALLAS_FUSED_CE,
            fused_ce_mesh=mesh,
            remat_encode=self.config.REMAT_ENCODE,
            use_ragged_kernel=self.config.RAGGED_TRAIN_KERNEL,
            ragged_mesh=mesh, rows=tuple(packed_arrays[4:]))

    def forward_packed(self, params, packed_arrays, mesh=None,
                       use_kernel: bool = False):
        """Deterministic forward off the packed wire: the fused Pallas
        kernel when ``use_kernel`` (the trainer's once-per-mesh platform
        decision; shard_mapped over ``mesh`` when multi-device), the jnp
        twin otherwise."""
        ctx, count = packed_arrays[0], packed_arrays[1]
        code_vectors, attention = functional.encode_packed(
            params, ctx, count, max_contexts=self.config.MAX_CONTEXTS,
            token_pad=self.token_pad_index,
            path_pad=self.path_pad_index, dtype=self.dtype,
            use_kernel=use_kernel, mesh=mesh)
        logits = functional.compute_logits(
            params, code_vectors, dtype=self.dtype,
            num_valid_targets=self.num_valid_targets)
        return code_vectors, attention, logits

    def named_params(self, params) -> functional.Code2VecParams:
        return params

    def from_canonical(self, named: dict) -> functional.Code2VecParams:
        """Canonical {name: array} checkpoint layout → backend layout."""
        return functional.Code2VecParams(**named)


class FlaxBackend:
    """flax.linen backend: params are the module's ``{'params': {...}}``
    dict."""

    name = 'flax'

    def __init__(self, config: Config, vocabs: Code2VecVocabs):
        self.config = config
        self.dtype = compute_dtype(config)
        self._jax_twin = JaxBackend(config, vocabs)
        sizes = self.sizes = self._jax_twin.sizes
        self.num_valid_targets = self._jax_twin.num_valid_targets
        self.token_pad_index = self._jax_twin.token_pad_index
        self.path_pad_index = self._jax_twin.path_pad_index
        self.module = Code2VecModule(
            token_vocab_size=sizes['token_vocab_size'],
            path_vocab_size=sizes['path_vocab_size'],
            target_vocab_size=sizes['target_vocab_size'],
            token_dim=config.TOKEN_EMBEDDINGS_SIZE,
            path_dim=config.PATH_EMBEDDINGS_SIZE,
            code_dim=config.CODE_VECTOR_SIZE,
            dropout_keep_rate=config.DROPOUT_KEEP_RATE,
            compute_dtype=self.dtype,
            num_valid_targets=self.num_valid_targets,
            use_pallas=config.USE_PALLAS_FUSED_ENCODE)

    def init(self, rng: jax.Array):
        dummy = jnp.zeros((1, self.config.MAX_CONTEXTS), dtype=jnp.int32)
        dummy_mask = jnp.zeros((1, self.config.MAX_CONTEXTS),
                               dtype=jnp.float32)
        return self.module.init(rng, dummy, dummy, dummy, dummy_mask)

    def param_shapes(self):
        shapes = self._jax_twin.param_shapes()
        return {'params': shapes._asdict()}

    def loss_fn(self, params, arrays, dropout_rng,
                mesh=None) -> Tuple[jax.Array, Any]:
        # Delegate the loss math to functional via the extracted params so
        # both backends are numerically identical.
        return self._jax_twin.loss_fn(self.named_params(params), arrays,
                                      dropout_rng, mesh=mesh)

    def forward(self, params, arrays):
        source, path, target, mask = arrays[:4]
        return self.module.apply(params, source, path, target, mask,
                                 deterministic=True)

    def loss_fn_packed(self, params, packed_arrays, dropout_rng,
                       mesh=None) -> Tuple[jax.Array, Any]:
        # same delegation as loss_fn: the packed-wire math is identical
        # across backends by construction
        return self._jax_twin.loss_fn_packed(
            self.named_params(params), packed_arrays, dropout_rng,
            mesh=mesh)

    def forward_packed(self, params, packed_arrays, mesh=None,
                       use_kernel: bool = False):
        return self._jax_twin.forward_packed(
            self.named_params(params), packed_arrays, mesh=mesh,
            use_kernel=use_kernel)

    def named_params(self, params) -> functional.Code2VecParams:
        inner = params['params']
        return functional.Code2VecParams(
            token_embedding=inner['token_embedding'],
            path_embedding=inner['path_embedding'],
            target_embedding=inner['target_embedding'],
            transform=inner['transform'],
            attention=inner['attention'])

    def from_canonical(self, named: dict):
        """Canonical {name: array} checkpoint layout → flax module layout."""
        return {'params': dict(named)}


def create_backend(config: Config, vocabs: Code2VecVocabs):
    """Runtime backend selection (reference code2vec.py:7-13)."""
    if config.DL_FRAMEWORK == 'flax':
        return FlaxBackend(config, vocabs)
    if config.DL_FRAMEWORK == 'jax':
        return JaxBackend(config, vocabs)
    raise ValueError('Unknown DL_FRAMEWORK: {!r}'.format(config.DL_FRAMEWORK))
