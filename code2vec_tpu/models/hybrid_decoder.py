"""A decoder whose layers are linear attention with a recurrent state and
block-sparse softmax attention, mixed (``model_type`` ``minicpm_sala``), on
the serving path: the layer equations, the seeded weights and the one step
program the engine runs.  The sibling of ``models/decoder.py`` behind the
same seam: it shares that module's RMSNorm, RoPE table, product helper and
``take_row``, the step loop of ``serving/lm_scheduler.py`` and the page pool of
``serving/lm_cache.py``.

Residual stream ``h``, ``L`` the PUBLISHED depth (a depth cut keeps it)::

    h_0 = E[id] * scale_emb
    h   = h + Mixer_l(RMSNorm(h)) * scale_depth / sqrt(L)
    h   = h + W_down(silu(W_gate x) * W_up x) * scale_depth / sqrt(L)
    logits = W_head RMSNorm(h_L) / (hidden_size / dim_model_base)

- ``lightning-attn``: ``q, k, v = W x`` as ``lightning_nh`` heads; ``q``,
  ``k`` per-head RMSNorm with a learned gain, then RoPE (rotate-half, every
  dimension, absolute position); ``S_t = gamma_h S_{t-1} + k_t^T v_t``,
  ``o_t = q_t S_t / sqrt(d)`` (``ops/linear_attention.py``); per-head
  RMSNorm of ``o``, ``o * sigmoid(W_g x)``, ``W_o``.  ``gamma_h =
  exp(-2^(-8 (h + 1) / heads) (1 - l / (L - 1) + 1e-5))``, ``l`` the
  published layer index.
- ``minicpm4``: ``q`` as query heads, ``k, v`` as key/value heads, per-head
  RMSNorm of ``q`` and ``k``, no rotary embedding; a query whose visible
  length ``i + 1`` is at most ``dense_len`` attends causally to every key
  (``dense_attention``), a later one to its ``topk`` chosen blocks
  (``sparse_attention``, both ``ops/sparse_attention.py``);
  ``W_o (o * sigmoid(W_g x))``.

Weights are held and multiplied in bfloat16 with float32 accumulation; the
residual stream, the norms, the recurrent state, the stage-1 scores and
every softmax are float32.  The plain float32 statement of the same
equations is ``chipbench/reference_minicpm_sala.py``; it shares no code
with this file.

One **step** carries a token for every decoding sequence (rows
``0 .. slots - 1``) and a chunk of the prompt of the sequence in prefill
(the rows after them).  Its cache is three arrays (``serving/lm_cache.py``):
``pages`` (keys and values of the sparse layers), ``pooled`` (their stride
rows for stage 1, one every ``kernel_stride`` positions, page for page) and
``states`` (one float32 ``heads x d x d`` state a slot and lightning
layer).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu.models.decoder import (_matmul, _rms_norm, _rotate,
                                         rope_inv_freq, take_row)
from code2vec_tpu.ops import linear_attention, pallas_sparse, \
    sparse_attention
from code2vec_tpu.ops.sparse_attention import SparseGeometry
from code2vec_tpu.serving import lm_cache

LIGHTNING = 'lightning-attn'
SPARSE = 'minicpm4'

#: the family's public ``sparse_config`` (openbmb/MiniCPM4.1-8B): what a
#: config.json without that key is read as
SPARSE_DEFAULTS = {'kernel_size': 32, 'kernel_stride': 16, 'block_size': 64,
                   'window_size': 2048, 'topk': 64, 'init_blocks': 1,
                   'dense_len': 8192}
#: the gain the sparse layers' q and k norms start at: attention logits of
#: standard deviation QK_GAIN^2, as trained attention has.  With unit gains
#: a softmax over thousands of random keys is flat, the layer's output is
#: the mean of its values and which blocks were read leaves no trace in the
#: logits
QK_GAIN = 1.75

@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """What of a published ``config.json`` the equations need."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_heads: int
    lightning_head_dim: int
    intermediate_size: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    mixer_types: Tuple[str, ...]        # one entry a layer that is held
    first_layer: int                    # published index of the first
    published_layers: int
    sparse: SparseGeometry

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def depth_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.hidden_size

    @classmethod
    def from_dict(cls, d: dict) -> 'HybridConfig':
        published = tuple(d['mixer_types'])
        first = int(d.get('first_hidden_layer', 0))
        layers = int(d['num_hidden_layers'])
        kinds = published[first:first + layers]
        if len(kinds) != layers:
            raise ValueError('mixer_types has %d entries; layers %d..%d '
                             'were asked for' % (len(published), first,
                                                 first + layers - 1))
        unknown = sorted(set(kinds) - {LIGHTNING, SPARSE})
        if unknown:
            raise NotImplementedError(
                'mixer_types entries %s are not implemented (have %s, %s)'
                % (unknown, LIGHTNING, SPARSE))
        if d.get('lightning_scale', '1/sqrt(d)') != '1/sqrt(d)':
            raise NotImplementedError(
                'lightning_scale %r is not implemented (have 1/sqrt(d))'
                % d['lightning_scale'])
        wanted = {'use_output_gate': True, 'use_output_norm': True,
                  'attn_use_output_gate': True, 'qk_norm': True,
                  'attn_use_rope': False, 'lightning_use_rope': True,
                  'attention_bias': False, 'tie_word_embeddings': False,
                  'hidden_act': 'silu'}
        for key, value in wanted.items():
            if d.get(key, value) != value:
                raise NotImplementedError(
                    '%s %r is not implemented (have %r)'
                    % (key, d[key], value))
        heads = int(d['lightning_nh'])
        if int(d.get('lightning_nkv', heads)) != heads:
            raise NotImplementedError(
                'lightning_nkv %s differs from lightning_nh %d: grouped '
                'linear attention is not implemented'
                % (d['lightning_nkv'], heads))
        sparse = SparseGeometry(**{
            key: int(dict(SPARSE_DEFAULTS, **d.get('sparse_config', {}))[key])
            for key in SPARSE_DEFAULTS})
        return cls(
            hidden_size=int(d['hidden_size']),
            num_attention_heads=int(d['num_attention_heads']),
            num_key_value_heads=int(d['num_key_value_heads']),
            head_dim=int(d['head_dim']), lightning_heads=heads,
            lightning_head_dim=int(d['lightning_head_dim']),
            intermediate_size=int(d['intermediate_size']),
            vocab_size=int(d['vocab_size']),
            rms_norm_eps=float(d['rms_norm_eps']),
            rope_theta=float(d['rope_theta']),
            scale_emb=float(d['scale_emb']),
            scale_depth=float(d['scale_depth']),
            dim_model_base=int(d['dim_model_base']), mixer_types=kinds,
            first_layer=first, published_layers=len(published),
            sparse=sparse)

    def parameters(self) -> int:
        return sum(int(np.prod(leaf.shape)) for leaf in
                   jax.tree_util.tree_leaves(param_shapes(self)))


# --------------------------------------------------------------- weights
def _layer_shapes(cfg: HybridConfig, kind: str) -> dict:
    h = cfg.hidden_size

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    if kind == LIGHTNING:
        d, wide = cfg.lightning_head_dim, \
            cfg.lightning_heads * cfg.lightning_head_dim
        mixer = {'wqkvg': s(h, 4 * wide), 'wo': s(wide, h),
                 'o_norm': s(d)}
    else:
        d = cfg.head_dim
        wide = cfg.num_attention_heads * d
        mixer = {'wqkvg': s(h, 2 * wide + 2 * cfg.num_key_value_heads * d),
                 'wo': s(wide, h)}
    return dict(mixer, attn_norm=s(h), q_norm=s(d), k_norm=s(d),
                mlp_norm=s(h), w_gate_up=s(h, 2 * cfg.intermediate_size),
                w_down=s(cfg.intermediate_size, h))


def param_shapes(cfg: HybridConfig) -> dict:
    """The parameter pytree as ``ShapeDtypeStruct``.  ``wqkvg`` fuses the
    products of one input: q, k, v and the output gate, in that order."""
    h = cfg.hidden_size
    bf16 = jnp.bfloat16
    return {'embed': jax.ShapeDtypeStruct((cfg.vocab_size, h), bf16),
            'head': jax.ShapeDtypeStruct((h, cfg.vocab_size), bf16),
            'final_norm': jax.ShapeDtypeStruct((h,), bf16),
            'layers': [_layer_shapes(cfg, kind)
                       for kind in cfg.mixer_types]}


def init_params(cfg: HybridConfig, seed: int) -> dict:
    """Seeded weights made on the device in bfloat16, a layer a call.
    Products ``N(0, 1/fan_in)``; the embedding ``N(0, 1/scale_emb^2)`` (the
    scaling it is multiplied by gives the stream unit variance, which is
    what muP's ``scale_emb`` is for); gains one, but for the sparse layers'
    q and k norms (``QK_GAIN``)."""
    root = jax.random.PRNGKey(seed)

    def draw(key, kind):
        tree = _layer_shapes(cfg, kind)
        keys = jax.random.split(key, len(tree))
        out = {}
        for k, (name, leaf) in zip(keys, sorted(tree.items())):
            if len(leaf.shape) == 1:
                gain = QK_GAIN if kind == SPARSE and name in (
                    'q_norm', 'k_norm') else 1.0
                out[name] = jnp.full(leaf.shape, gain, leaf.dtype)
            else:
                out[name] = (jax.random.normal(k, leaf.shape, jnp.float32)
                             * leaf.shape[0] ** -0.5).astype(leaf.dtype)
        return out
    drawers = {kind: jax.jit(lambda key, kind=kind: draw(key, kind))
               for kind in set(cfg.mixer_types)}
    layers = [drawers[kind](jax.random.fold_in(root, i))
              for i, kind in enumerate(cfg.mixer_types)]
    h = cfg.hidden_size

    def ends(key):
        k_embed, k_head = jax.random.split(key)
        return {
            'embed': (jax.random.normal(k_embed, (cfg.vocab_size, h),
                                        jnp.float32)
                      / cfg.scale_emb).astype(jnp.bfloat16),
            'head': (jax.random.normal(k_head, (h, cfg.vocab_size),
                                       jnp.float32)
                     * h ** -0.5).astype(jnp.bfloat16),
            'final_norm': jnp.ones((h,), jnp.bfloat16)}
    draw_ends = jax.jit(ends)
    params = draw_ends(jax.random.fold_in(root, cfg.num_layers))
    params['layers'] = layers
    return params


# -------------------------------------------------------------- the step
@dataclasses.dataclass(frozen=True)
class StepShape:
    """The static shape of one step program."""
    tokens: int            # rows of the flat batch: decode slots + chunk
    chunk: int             # the chunk bucket (0: a decode-only step)
    outputs: int           # rows whose logits are computed
    slots: int             # decode rows (and state slots but the spare)
    full_seqs: int         # rows of the page table: the slots, the chunk
    full_pages: int        # its columns
    strides: int           # stride rows a step may complete


def batch_shapes(shape: StepShape) -> Dict[str, tuple]:
    """The int32 arrays a step takes from the host, by name."""
    return {
        'tokens': (shape.tokens,), 'token_src': (shape.tokens,),
        'positions': (shape.tokens,), 'valid': (shape.tokens,),
        'out_rows': (shape.outputs,),
        # where each token's key and value go (page x page_size + offset
        # in one layer's slab), and each sequence's pages: a row a decode
        # slot, then the chunk's
        'full_rows': (shape.tokens,),
        'full_page_indices': (shape.full_seqs, shape.full_pages),
        # 1 at a token that takes the sparse branch (i + 1 > dense_len)
        'sparse': (shape.tokens,),
        # the state slot of each decode row, then the chunk's; the spare
        # slot at a row that holds no sequence
        'state_slots': (shape.slots + 1,),
        # [the chunk's row of the page table, whether its state carries
        # over from before the chunk (0 at position 0)]
        'chunk_seq': (2,),
        # flat rows (in one layer's slab) where each completed stride
        # starts in `pages` and where its mean goes in `pooled`
        'stride_src': (shape.strides,), 'stride_dst': (shape.strides,)}


def layer_pool_index(cfg: HybridConfig) -> List[int]:
    seen = {LIGHTNING: 0, SPARSE: 0}
    out = []
    for kind in cfg.mixer_types:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def make_step(cfg: HybridConfig, shape: StepShape,
              geometry: lm_cache.CacheGeometry, dtype=jnp.bfloat16,
              sparse_stage2: str = 'jnp'):
    """The step function for one shape (jit it with ``cache`` donated).

    ``step(params, cache, prev_ids, batch)`` -> ``(cache, next_ids
    [outputs], logits [outputs, vocab] float32, counts [sparse layers, 4])``
    with ``counts`` the blocks chosen and the blocks visible, summed over
    the step's sparse-branch queries and key/value heads, those queries
    again where their stage 2 ran in a kernel, and the stride rows the
    decode rows' stage 1 scored.  ``sparse_stage2`` names stage 2's form
    (``STAGE2``, as ``step_kernels`` chooses it).
    """
    geo = cfg.sparse
    stage2 = STAGE2[sparse_stage2]
    # the pages ONE sparse layer owns (its last takes the padding rows'
    # writes), as in models/decoder.py
    pool_pages, page_size = geometry.pool_layer_pages, geometry.page_size
    geo.check(page_size)
    slots, chunk = shape.slots, shape.chunk
    prefill = chunk > 0
    stride_rows = page_size // geo.kernel_stride
    inv_freq, _ = rope_inv_freq({'rope_theta': cfg.rope_theta},
                                cfg.lightning_head_dim)
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    own = layer_pool_index(cfg)
    rates = [jnp.asarray(linear_attention.decay_rates(
        cfg.lightning_heads, cfg.first_layer + i, cfg.published_layers),
        jnp.float32) for i in range(cfg.num_layers)]
    eps = cfg.rms_norm_eps
    prefix = 'lmhybrid/'

    def lightning(layer, index, normed, cos, sin, batch, states):
        heads, d = cfg.lightning_heads, cfg.lightning_head_dim
        wide = heads * d
        mixed = _matmul(normed, layer['wqkvg'], dtype)
        q, k, v, gate = (mixed[:, i * wide:(i + 1) * wide]
                         for i in range(4))
        q = _rotate(_rms_norm(q.reshape(-1, heads, d), layer['q_norm'],
                              eps), cos, sin).astype(dtype)
        k = _rotate(_rms_norm(k.reshape(-1, heads, d), layer['k_norm'],
                              eps), cos, sin).astype(dtype)
        v = v.reshape(-1, heads, d).astype(dtype)
        base = own[index] * (slots + 1)
        where = batch['state_slots'] + base
        valid = batch['valid']
        with jax.named_scope(prefix + 'linear_decode'):
            out, new = linear_attention.decode_update(
                q[:slots], k[:slots], v[:slots], rates[index],
                valid[:slots], states[where[:slots]])
            states = states.at[where[:slots]].set(new)
        if prefill:
            with jax.named_scope(prefix + 'linear_prefill'):
                carried = states[where[slots]] * \
                    batch['chunk_seq'][1].astype(jnp.float32)
                tail, after = linear_attention.chunk_scan(
                    q[slots:], k[slots:], v[slots:], rates[index],
                    valid[slots:], carried)
                states = states.at[where[slots]].set(after)
            out = jnp.concatenate([out, tail], axis=0)
        out = _rms_norm(out, layer['o_norm'], eps).reshape(-1, wide)
        return out * jax.nn.sigmoid(gate), states

    def sparse(layer, index, normed, batch, pages, pooled):
        heads, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                        cfg.head_dim)
        wide = heads * d
        mixed = _matmul(normed, layer['wqkvg'], dtype)
        q = mixed[:, :wide].reshape(-1, heads, d)
        k = mixed[:, wide:wide + kv * d].reshape(-1, kv, d)
        v = mixed[:, wide + kv * d:wide + 2 * kv * d].reshape(-1, kv, d)
        gate = mixed[:, wide + 2 * kv * d:]
        q = _rms_norm(q, layer['q_norm'], eps).astype(dtype)
        k = _rms_norm(k, layer['k_norm'], eps).astype(dtype)
        offset = own[index] * pool_pages
        with jax.named_scope(prefix + 'sparse_pool'):
            pages = sparse_attention.write_kv(
                pages, batch['full_rows'] + offset * page_size, k, v)
            means = sparse_attention.stride_means(
                pages, batch['stride_src'] + offset * page_size,
                geo.kernel_stride)
            flat_pooled = pooled.reshape(-1, kv, d)
            flat_pooled = flat_pooled.at[
                batch['stride_dst'] + offset * stride_rows].set(
                    means.astype(pooled.dtype))
            pooled = flat_pooled.reshape(pooled.shape)
        valid = batch['valid'] > 0
        live = batch['sparse'] * batch['valid']
        plain = valid & (batch['sparse'] == 0)
        positions = batch['positions']
        table = batch['full_page_indices'] + offset

        def row_means(pages_of_row):
            return pooled[pages_of_row].reshape(-1, kv, d)

        def branch(name, needed, work, shape):
            """``work()`` where any token needs it, else zeros."""
            def nothing():
                return (jnp.zeros(shape, jnp.float32),
                        jnp.zeros((4,), jnp.int32))
            with jax.named_scope(prefix + name):
                return jax.lax.cond(jnp.any(needed), work, nothing)

        def dense_rows():
            out = jax.vmap(lambda qr, at, pages_of_row:
                           sparse_attention.dense_attention(
                               qr[None], at[None], pages_of_row, pages, geo,
                               page_size)[0])(
                q[:slots], positions[:slots], table[:slots])
            return out, jnp.zeros((4,), jnp.int32)

        def sparse_rows():
            return sparse_attention.sparse_attention_rows(
                q[:slots], positions[:slots], live[:slots], pooled,
                table[:slots], pages, geo, page_size, kernel=stage2)
        rows = (slots,) + q.shape[1:]
        direct, _ = branch('dense_attention', plain[:slots], dense_rows,
                           rows)
        chosen, counted = branch('sparse_decode', live[:slots] > 0,
                                 sparse_rows, rows)
        if prefill:
            pages_of_chunk = table[batch['chunk_seq'][0]]

            def dense_chunk():
                return (sparse_attention.dense_attention(
                    q[slots:], positions[slots:], pages_of_chunk, pages,
                    geo, page_size), jnp.zeros((4,), jnp.int32))

            def sparse_chunk():
                out, counts = sparse_attention.sparse_attention_chunk(
                    q[slots:], positions[slots:], live[slots:],
                    row_means(pages_of_chunk), pages_of_chunk, pages, geo,
                    page_size, kernel=stage2)
                # no decode row's stride rows
                return out, jnp.concatenate(
                    [counts, jnp.zeros((1,), jnp.int32)])
            rows = (chunk,) + q.shape[1:]
            more, _ = branch('dense_attention', plain[slots:], dense_chunk,
                             rows)
            direct = jnp.concatenate([direct, more], axis=0)
            tail, more = branch('sparse_prefill', live[slots:] > 0,
                                sparse_chunk, rows)
            chosen = jnp.concatenate([chosen, tail], axis=0)
            counted = counted + more
        attended = jnp.where((live > 0)[:, None, None], chosen, direct)
        # padding rows come back unspecified: keep them finite
        attended = jnp.where(valid[:, None, None], attended, 0)
        return (attended.reshape(-1, wide) * jax.nn.sigmoid(gate), pages,
                pooled, counted)

    def step(params, cache, prev_ids, batch):
        tokens = jnp.where(batch['token_src'] >= 0,
                           prev_ids[jnp.maximum(batch['token_src'], 0)],
                           batch['tokens'])
        angle = batch['positions'].astype(jnp.float32)[:, None] \
            * inv_freq[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x = params['embed'][tokens].astype(jnp.float32) * cfg.scale_emb
        pages, pooled, states = (cache['pages'], cache['pooled'],
                                 cache['states'])
        counts = []
        for index, (kind, layer) in enumerate(zip(cfg.mixer_types,
                                                  params['layers'])):
            normed = _rms_norm(x, layer['attn_norm'], eps)
            if kind == LIGHTNING:
                mixed, states = lightning(layer, index, normed, cos, sin,
                                          batch, states)
            else:
                mixed, pages, pooled, counted = sparse(
                    layer, index, normed, batch, pages, pooled)
                counts.append(counted)
            x = x + _matmul(mixed, layer['wo'], dtype) * cfg.depth_scale
            normed = _rms_norm(x, layer['mlp_norm'], eps)
            gate_up = _matmul(normed, layer['w_gate_up'], dtype)
            wide = cfg.intermediate_size
            x = x + _matmul(jax.nn.silu(gate_up[:, :wide])
                            * gate_up[:, wide:], layer['w_down'], dtype) \
                * cfg.depth_scale
        last = _rms_norm(x[batch['out_rows']], params['final_norm'], eps)
        logits = _matmul(last, params['head'], dtype) * cfg.logit_scale
        next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, 4), jnp.int32))
        return ({'pages': pages, 'pooled': pooled, 'states': states},
                next_ids, logits, counts)

    return step


def cache_shapes(cfg: HybridConfig, geometry: lm_cache.CacheGeometry
                 ) -> Dict[str, tuple]:
    """Shapes of the three arrays: every sparse layer owns
    ``pool_layer_pages`` pages, every lightning layer a state a slot and
    one more, the spare."""
    kinds = cfg.mixer_types
    slots, pool_pages, page_size = (geometry.slots,
                                    geometry.pool_layer_pages,
                                    geometry.page_size)
    sparse_layers = max(kinds.count(SPARSE), 1)
    kv, d = cfg.num_key_value_heads, cfg.head_dim
    block = cfg.sparse.block_size
    return {
        # keys and values apart, each by block and head:
        # ops/sparse_attention.py
        'pages': (2, sparse_layers * pool_pages * (page_size // block), kv,
                  block, d),
        'pooled': (sparse_layers * pool_pages,
                   page_size // cfg.sparse.kernel_stride, kv, d),
        'states': (max(kinds.count(LIGHTNING), 1) * (slots + 1),
                   cfg.lightning_heads, cfg.lightning_head_dim,
                   cfg.lightning_head_dim)}


def cache_dtypes(dtype) -> Dict[str, object]:
    return {'pages': dtype, 'pooled': dtype, 'states': jnp.float32}


def zero_cache(cfg: HybridConfig, geometry: lm_cache.CacheGeometry,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shapes = cache_shapes(cfg, geometry)
    dtypes = cache_dtypes(dtype)
    return {name: jnp.zeros(shape, dtypes[name])
            for name, shape in shapes.items()}


def describe(cfg: HybridConfig) -> str:
    geo = cfg.sparse
    return ('%d layers (published %d..%d of %d: %d lightning linear '
            'attention, %d block-sparse top-%d of %d-position blocks past '
            '%d), hidden %d, %d query / %d key-value heads of %d, '
            'feed-forward %d, vocabulary %d: %.3fB parameters'
            % (cfg.num_layers, cfg.first_layer,
               cfg.first_layer + cfg.num_layers - 1, cfg.published_layers,
               cfg.mixer_types.count(LIGHTNING),
               cfg.mixer_types.count(SPARSE), geo.topk, geo.block_size,
               geo.dense_len, cfg.hidden_size, cfg.num_attention_heads,
               cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
               cfg.vocab_size, cfg.parameters() / 1e9))


# ------------------------------------------ what the step loop asks of it
# (the same names as models/decoder.py's: serving/lm_scheduler.py serves
# whichever module it is handed)
load_config = HybridConfig.from_dict
#: the gauge a slot's share is read on: in this model a slot is one
#: recurrent state a lightning layer
SLOT_GAUGE = 'serving/lm_state_pool_fill'
#: the counters of the model's own that ``log_counts`` feeds
COUNTERS = ('serving/lm_sparse_blocks_chosen_total',
            'serving/lm_sparse_blocks_visible_total',
            'serving/lm_sparse_dense_branch_total',
            'serving/lm_sparse_kernel_queries_total',
            'serving/lm_sparse_decode_stride_rows_total')
#: the name ``stats()`` gives the sum of every step's counts
COUNTS_STAT = 'sparse_blocks'


#: stage 2 of the sparse layers by name: the ``jax.numpy`` form, the
#: Pallas kernel, and the kernel in Pallas's TPU interpreter (tests)
STAGE2 = {'jnp': None, 'pallas': pallas_sparse.attend_planned,
          'interpret': functools.partial(pallas_sparse.attend_planned,
                                         interpret=True)}


def step_kernels(platform: str) -> dict:
    """The names of the model's own kernels ``make_step`` takes, for step
    programs that run on ``platform`` (``stats()['lm']`` reports them):
    stage 2 of the sparse layers is the Pallas kernel on a TPU and the
    ``jax.numpy`` form elsewhere."""
    return {'sparse_stage2': 'pallas' if platform == 'tpu' else 'jnp'}


def ring_window(cfg: HybridConfig) -> int:
    """No layer keeps a ring: a slot is a recurrent state."""
    return 0


def check_geometry(cfg: HybridConfig,
                   geometry: lm_cache.CacheGeometry) -> None:
    """A page holds whole blocks and whole strides."""
    cfg.sparse.check(geometry.page_size)


def counts_shape(cfg: HybridConfig) -> Tuple[int, int]:
    return cfg.mixer_types.count(SPARSE), 4


def step_shape(cfg: HybridConfig, geometry: lm_cache.CacheGeometry,
               chunk: int, subchunk: int) -> StepShape:
    """The shape of the step that carries ``chunk`` prompt tokens beside
    the decode rows (``subchunk`` is the sliding layers' of the other
    model).  A decode row completes at most one stride, a chunk one every
    ``kernel_stride`` tokens and the one it began inside."""
    g, stride = geometry, cfg.sparse.kernel_stride
    return StepShape(
        tokens=g.slots + chunk, chunk=chunk, outputs=g.slots + 1,
        slots=g.slots, full_seqs=g.slots + (1 if chunk else 0),
        full_pages=g.pages_per_seq,
        strides=g.slots + (chunk // stride + 1 if chunk else 0))


def program_name(shape: StepShape) -> str:
    """The name its jitted program goes by in a trace: one a shape, which
    is how the trace's reader tells the programs apart."""
    return 'lmhybrid_step_%d' % shape.chunk


def pad_rows(cfg: HybridConfig, geometry: lm_cache.CacheGeometry,
             views: dict) -> None:
    """A step's inputs of this model's own with no sequence in them: the
    spare state slot, and strides read from and written to the spare
    page."""
    g = geometry
    views['state_slots'][:] = g.slots
    views['stride_src'][:] = g.pool_pages * g.page_size
    views['stride_dst'][:] = g.pool_pages \
        * (g.page_size // cfg.sparse.kernel_stride)


class StepPlan:
    """The host's side of one step: which branch each token takes, whose
    state each row updates and which strides the step completes, filled by
    the step loop a decode row at a time, then the chunk.  The loop itself
    fills what every model's step has (tokens, positions, output rows, the
    page pool's rows and table)."""

    def __init__(self, cfg: HybridConfig, geometry: lm_cache.CacheGeometry,
                 views: dict, subchunk: int):
        self.g = geometry
        self.stride = cfg.sparse.kernel_stride
        self.dense_len = cfg.sparse.dense_len
        self.sparse = views['sparse']
        self.state_slots = views['state_slots']
        self.chunk_seq = views['chunk_seq']
        self.stride_src = views['stride_src']
        self.stride_dst = views['stride_dst']
        self.strides = 0
        self.dense_tokens = 0

    def decode_row(self, row: int, lease: lm_cache.Lease, at: int) -> None:
        self.state_slots[row] = lease.slot
        if at + 1 > self.dense_len:
            self.sparse[row] = 1
        else:
            self.dense_tokens += 1
        if (at + 1) % self.stride == 0:
            src, dst = lm_cache.completed_strides(self.g, lease, at, 1,
                                                  self.stride)
            self.stride_src[self.strides] = src[0]
            self.stride_dst[self.strides] = dst[0]
            self.strides += 1

    def end_decode(self, n: int) -> int:
        """The ``n`` decode rows are in.  Returns the row of the batch, and
        of the page table, the chunk starts at: the program's decode rows
        are its first ``slots`` whether in use or not (a row's recurrent
        state is gathered by its place)."""
        return self.g.slots

    def chunk(self, n: int, lease: lm_cache.Lease, first: int,
              taken: int) -> None:
        """``taken`` prompt tokens at positions ``first ..`` of the
        sequence that holds ``lease``."""
        at_chunk = self.g.slots
        where = np.arange(first, first + taken)
        beyond = where + 1 > self.dense_len
        self.sparse[at_chunk:at_chunk + taken] = beyond
        self.dense_tokens += int(taken - beyond.sum())
        self.state_slots[-1] = lease.slot
        # its state carries over from before the chunk, but at position 0
        self.chunk_seq[:] = (at_chunk, 1 if first > 0 else 0)
        src, dst = lm_cache.completed_strides(self.g, lease, first, taken,
                                              self.stride)
        self.stride_src[self.strides:self.strides + src.shape[0]] = src
        self.stride_dst[self.strides:self.strides + dst.shape[0]] = dst

    def close(self) -> dict:
        """The step is whole.  Returns what ``log_counts`` is to know of
        the plan: the tokens within a sparse layer's ``dense_len``."""
        return {'dense_tokens': self.dense_tokens}


def log_counts(counts: np.ndarray, note: dict) -> Tuple[dict, dict]:
    """(what the step log keeps of a step's ``counts`` [sparse layers,
    (chosen, visible, kernel queries, decode stride rows)], {counter: its
    increment})."""
    chosen, visible, kernel, strides = (int(x) for x in counts.sum(axis=0))
    dense_tokens = note['dense_tokens']
    return ({'blocks_chosen': chosen, 'blocks_visible': visible,
             'dense_tokens': dense_tokens},
            {'serving/lm_sparse_blocks_chosen_total': chosen,
             'serving/lm_sparse_blocks_visible_total': visible,
             'serving/lm_sparse_kernel_queries_total': kernel,
             'serving/lm_sparse_decode_stride_rows_total': strides,
             # a sparse layer took its dense branch for these tokens
             'serving/lm_sparse_dense_branch_total':
                 dense_tokens * counts.shape[0]})


def step_gauges(counts: np.ndarray) -> Dict[str, float]:
    """{gauge: value} of a step's counts, where telemetry is on."""
    return {}
