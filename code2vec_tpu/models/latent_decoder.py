"""A decoder whose attention keeps one latent vector a position (multi-head
latent attention, MLA) and whose expert layer holds a share of the routed
experts beside a shared expert (``model_type`` ``mistral4``), on the
serving path: the layer equations, the seeded weights and the one step
program the engine runs.  The sibling of ``models/decoder.py`` and
``models/hybrid_decoder.py`` behind the same seam: it takes that module's
RMSNorm, YaRN table, rotation, product helper and ``take_row``, the step
loop of ``serving/lm_scheduler.py`` and the page pool of
``serving/lm_cache.py``.

Every layer is the same (``first_k_dense_replace`` 0)::

    h = x + MLA(RMSNorm(x))
    y = h + Shared(z) + sum_{e in top_k(z), held} p_e E_e(z),   z = RMSNorm(h)

- MLA: ``q = RMSNorm_q(x W_qa) W_qb`` as heads of ``[q_nope | q_rope]``;
  ``[c | kr] = x W_kva``, ``c`` normed (``RMSNorm_kv``), one rope key
  ``kr`` for all heads; ``[k_nope | v] = c W_kvb`` per head.  ``q_rope``
  and ``kr`` rotated by the YaRN table, pairs ``(2i, 2i + 1)``
  (``rope_interleave``; cos and sin scaled by ``mscale / mscale_all_dim``
  of YaRN, 1 here); the whole query times ``1 + beta ln(1 + floor(pos /
  original_max_position_embeddings))`` (``llama_4_scaling_beta``);
  ``score = (q_nope . k_nope + q_rope . kr) / sqrt(qk_head_dim) * m^2``
  with ``m = 0.1 mscale_all_dim ln(factor) + 1``; a float32 softmax over
  ``j <= i``; ``o = concat_h(sum_j p_j v_j) W_o``.
- MoE: ``p = softmax(z W_r)`` in float32 over every routed expert, the
  ``top_k`` largest renormalised and times ``routed_scaling_factor``;
  ``E_e(z) = W_down,e(silu(W_gate,e z) * W_up,e z)``.  This chip holds
  ``n_routed_experts`` of them, from ``first_held_expert`` on, of the
  router's ``n_routed_experts_published``; a choice of an expert not held
  adds nothing here (``ops/grouped_experts.py``).  ``Shared`` is the same
  form at ``n_shared_experts x moe_intermediate_size``, every token.
- head: ``logits = RMSNorm(x_L) W_head``, untied.

Weights are held and multiplied in bfloat16 with float32 accumulation; the
residual stream, the norms, the router and the softmax are float32.  The
plain float32 statement of the same equations is
``chipbench/reference_mistral4.py``; it shares no code with this file.

**The cache** is one page pool of latents (``serving/lm_cache.py``): a
position keeps ``[c | rotated kr]`` (``kv_lora + rope`` values) in every
layer, never a head's key or value.  One **step** carries a token for every
decoding sequence (rows ``0 .. slots - 1``, an idle row of length 0) and a
chunk of the prompt of the sequence in prefill (the rows after them).  A
decode row attends by the **absorbed** form (``q_nope`` folded into the
latent space, the history's latents read once for all heads), the chunk by
the **expanded** form (the history up-projected a block at a time), chosen
by the row's kind: ``ops/latent_attention.py`` says both, and on a TPU each
is a kernel of ``ops/pallas_latent.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu.models.decoder import (_matmul, _rms_norm, _rotate,
                                         rope_inv_freq, take_row)
from code2vec_tpu.ops import grouped_experts, latent_attention, pallas_latent
from code2vec_tpu.serving import lm_cache


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """What of a published ``config.json`` the equations need."""
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_layers: int                 # layers held (the first ones)
    routed_experts: int             # the router's width: every routed one
    held_experts: int               # experts held here
    first_held_expert: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_width: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab_size: int
    rms_norm_eps: float
    rope: Tuple[Tuple[str, object], ...]    # rope_parameters, sorted items

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a position keeps a layer: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        rope = dict(self.rope)
        scale = self.qk_head_dim ** -0.5
        if rope.get('rope_type', 'default') == 'yarn':
            m = _yarn_mscale(float(rope['factor']),
                             float(rope.get('mscale_all_dim', 0) or 0))
            scale *= m * m
        return scale

    @classmethod
    def from_dict(cls, d: dict) -> 'LatentConfig':
        refused = []
        if d.get('scoring_func', 'softmax') != 'softmax' or \
                d.get('topk_method', 'greedy') not in ('greedy', None):
            refused.append('scoring_func %r / topk_method %r (have softmax '
                           'over every expert, greedy top-k)'
                           % (d.get('scoring_func'), d.get('topk_method')))
        if (d.get('n_group') or 1) != 1 or (d.get('topk_group') or 1) != 1:
            refused.append('n_group %r / topk_group %r: a group limit'
                           % (d.get('n_group'), d.get('topk_group')))
        if int(d.get('first_k_dense_replace', 0)):
            refused.append('first_k_dense_replace %r: dense leading layers'
                           % d['first_k_dense_replace'])
        for key in ('attention_bias', 'mlp_bias', 'tie_word_embeddings'):
            if d.get(key):
                refused.append('%s true' % key)
        if not d.get('rope_interleave', True):
            refused.append('rope_interleave false (have the interleaved '
                           'pairing)')
        for key in ('vision_config', 'image_token_index', 'image_token_id'):
            if key in d:
                refused.append('%s: image inputs (this model serves token '
                               'ids; the vision tower is not held)' % key)
        if d.get('hidden_act', 'silu') != 'silu':
            refused.append('hidden_act %r' % d['hidden_act'])
        rope = dict(d['rope_parameters'])
        if rope.get('rope_type', 'default') not in ('default', 'yarn'):
            refused.append('rope_type %r' % rope['rope_type'])
        if refused:
            raise NotImplementedError('not implemented: ' + '; '.join(
                refused))
        routed = int(d.get('n_routed_experts_published',
                           d['n_routed_experts']))
        held = int(d['n_routed_experts'])
        first = int(d.get('first_held_expert', 0))
        if first < 0 or first + held > routed:
            raise ValueError('experts %d..%d are held of %d routed'
                             % (first, first + held - 1, routed))
        return cls(
            hidden_size=int(d['hidden_size']),
            num_attention_heads=int(d['num_attention_heads']),
            q_lora_rank=int(d['q_lora_rank']),
            kv_lora_rank=int(d['kv_lora_rank']),
            qk_nope_head_dim=int(d['qk_nope_head_dim']),
            qk_rope_head_dim=int(d['qk_rope_head_dim']),
            v_head_dim=int(d['v_head_dim']),
            num_layers=int(d['num_hidden_layers']),
            routed_experts=routed, held_experts=held,
            first_held_expert=first,
            num_experts_per_tok=int(d['num_experts_per_tok']),
            moe_intermediate_size=int(d['moe_intermediate_size']),
            shared_width=int(d.get('n_shared_experts', 0))
            * int(d['moe_intermediate_size']),
            norm_topk_prob=bool(d['norm_topk_prob']),
            routed_scaling_factor=float(d.get('routed_scaling_factor', 1)),
            vocab_size=int(d['vocab_size']),
            rms_norm_eps=float(d['rms_norm_eps']),
            rope=tuple(sorted(rope.items())))

    def parameters(self) -> int:
        return sum(int(np.prod(leaf.shape)) for leaf in
                   jax.tree_util.tree_leaves(param_shapes(self)))


# --------------------------------------------------------------- weights
def _layer_shapes(cfg: LatentConfig) -> dict:
    h, heads = cfg.hidden_size, cfg.num_attention_heads

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    layer = {
        'attn_norm': s(h),
        # W_qa and W_kva: the two products of x, fused
        'wa': s(h, cfg.q_lora_rank + cfg.latent_width),
        'q_norm': s(cfg.q_lora_rank),
        'wq_b': s(cfg.q_lora_rank, heads * cfg.qk_head_dim),
        'kv_norm': s(cfg.kv_lora_rank),
        'wkv_b': s(cfg.kv_lora_rank,
                   heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        'wo': s(heads * cfg.v_head_dim, h), 'mlp_norm': s(h),
        'router': s(h, cfg.routed_experts),
        'w_gate_up': s(cfg.held_experts, h, 2 * cfg.moe_intermediate_size),
        'w_down': s(cfg.held_experts, cfg.moe_intermediate_size, h)}
    if cfg.shared_width:
        layer['shared_gate_up'] = s(h, 2 * cfg.shared_width)
        layer['shared_down'] = s(cfg.shared_width, h)
    return layer


def param_shapes(cfg: LatentConfig) -> dict:
    """The parameter pytree as ``ShapeDtypeStruct``."""
    h = cfg.hidden_size
    bf16 = jnp.bfloat16
    return {'embed': jax.ShapeDtypeStruct((cfg.vocab_size, h), bf16),
            'head': jax.ShapeDtypeStruct((h, cfg.vocab_size), bf16),
            'final_norm': jax.ShapeDtypeStruct((h,), bf16),
            'layers': [_layer_shapes(cfg) for _ in range(cfg.num_layers)]}


def init_params(cfg: LatentConfig, seed: int) -> dict:
    """Seeded weights made on the device in bfloat16, a layer a call.
    Every product keeps its input's variance (``N(0, 1/fan_in)``), the
    embedding is ``N(0, 1)``, the norms' gains are one."""
    root = jax.random.PRNGKey(seed)
    shapes = _layer_shapes(cfg)

    def draw(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, leaf) in zip(keys, sorted(shapes.items())):
            if len(leaf.shape) == 1:
                out[name] = jnp.ones(leaf.shape, leaf.dtype)
            else:
                out[name] = (jax.random.normal(k, leaf.shape, jnp.float32)
                             * leaf.shape[-2] ** -0.5).astype(leaf.dtype)
        return out
    draw_layer = jax.jit(draw)
    layers = [draw_layer(jax.random.fold_in(root, i))
              for i in range(cfg.num_layers)]
    h = cfg.hidden_size

    def ends(key):
        k_embed, k_head = jax.random.split(key)
        return {
            'embed': jax.random.normal(
                k_embed, (cfg.vocab_size, h), jnp.float32
            ).astype(jnp.bfloat16),
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
    slots: int             # decode rows
    full_pages: int        # columns of the page table


def batch_shapes(shape: StepShape) -> Dict[str, tuple]:
    """The int32 arrays a step takes from the host, by name."""
    return {
        'tokens': (shape.tokens,), 'token_src': (shape.tokens,),
        'positions': (shape.tokens,), 'valid': (shape.tokens,),
        'out_rows': (shape.outputs,),
        # where each token's latent goes (page x page_size + offset in one
        # layer's slab), and each sequence's pages: a row a decode slot,
        # then the chunk's
        'full_rows': (shape.tokens,),
        'full_page_indices': (shape.slots + 1, shape.full_pages),
        # the keys each decode row sees, its own token's among them (0: an
        # idle row, which the absorbed kernel skips)
        'latent_lens': (shape.slots,),
        # the keys the chunk sees: every position before it and its own
        'chunk_len': (1,)}


def rope_tables(cfg: LatentConfig):
    """(inverse frequencies [rope / 2] float32, the factor cos and sin are
    scaled by, llama-4's beta, the positions it divides by)."""
    rope = dict(cfg.rope)
    factor = float(rope.get('factor', 1))
    if rope.get('rope_type', 'default') == 'yarn':
        # YaRN of the DeepSeek family: cos and sin times mscale over
        # mscale_all_dim, and m^2 of the latter in the softmax's scale
        rope['attention_factor'] = (
            _yarn_mscale(factor, float(rope.get('mscale', 0) or 0))
            / _yarn_mscale(factor, float(rope.get('mscale_all_dim', 0)
                                         or 0)))
    inv_freq, cos_scale = rope_inv_freq(rope, cfg.qk_rope_head_dim)
    return (jnp.asarray(inv_freq, jnp.float32), cos_scale,
            float(rope.get('llama_4_scaling_beta', 0) or 0),
            float(rope.get('original_max_position_embeddings', 1)))


def _interleaved(x):
    """Rotation pairs ``(2i, 2i + 1)`` as ``_rotate``'s ``(i, i + half)``:
    the even dimensions, then the odd.  A query and the keys it meets are
    reordered alike, so every score is the interleaved pairing's."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def make_step(cfg: LatentConfig, shape: StepShape,
              geometry: lm_cache.CacheGeometry, dtype=jnp.bfloat16,
              latent_decode: str = 'jnp', latent_prefill: str = 'jnp'):
    """The step function for one shape (jit it with ``cache`` donated).

    ``step(params, cache, prev_ids, batch)`` -> ``(cache, next_ids
    [outputs], logits [outputs, vocab] float32, counts [layers, held
    experts])`` with ``counts`` the valid tokens each held expert received.
    ``cache`` is ``{'latents'}`` (``cache_shapes``), donated and returned.
    ``latent_decode`` and ``latent_prefill`` name the decode rows'
    absorbed product and the chunk's expanded one (``DECODE``,
    ``PREFILL``, as ``step_kernels`` chooses them)."""
    heads = cfg.num_attention_heads
    nope, rope_d, v_dim = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
    kv_lora, q_lora = cfg.kv_lora_rank, cfg.q_lora_rank
    pool_pages, page_size = geometry.pool_layer_pages, geometry.page_size
    slots, chunk = shape.slots, shape.chunk
    prefill = chunk > 0
    decode = DECODE[latent_decode]
    expanded = PREFILL[latent_prefill]
    inv_freq, cos_scale, beta, original = rope_tables(cfg)
    scale = cfg.softmax_scale
    eps = cfg.rms_norm_eps

    def attention(layer, index, normed, cos, sin, q_scale, batch, pool):
        mixed = _matmul(normed, layer['wa'], dtype)
        q = _matmul(_rms_norm(mixed[:, :q_lora], layer['q_norm'], eps),
                    layer['wq_b'], dtype).reshape(-1, heads, nope + rope_d)
        c = _rms_norm(mixed[:, q_lora:q_lora + kv_lora], layer['kv_norm'],
                      eps)
        kr = _rotate(_interleaved(mixed[:, None, q_lora + kv_lora:]), cos,
                     sin)[:, 0]
        q_nope = (q[..., :nope] * q_scale).astype(dtype)
        q_rope = (_rotate(_interleaved(q[..., nope:]), cos, sin)
                  * q_scale).astype(dtype)
        offset = index * pool_pages
        latents = jnp.concatenate([c, kr], axis=-1)
        table = batch['full_page_indices'] + offset
        pool = latent_attention.write_rows(
            pool, batch['full_rows'][:slots] + offset * page_size,
            latents[:slots])
        if prefill:
            first = batch['positions'][slots]
            pool = latent_attention.write_chunk(
                pool, table[slots], first, batch['chunk_len'][0] - first,
                latents[slots:], offset + geometry.pool_pages)
        w_kvb = layer['wkv_b'].reshape(kv_lora, heads, nope + v_dim)
        with jax.named_scope('lm/latent_decode'):
            absorbed = latent_attention.absorb_query(
                q_nope[:slots], q_rope[:slots], w_kvb[..., :nope]
            ).astype(dtype)
            o_lat = decode(absorbed, pool, batch['latent_lens'],
                           table[:slots], kv_lora=kv_lora, scale=scale)
            out = latent_attention.expand_output(o_lat, w_kvb[..., nope:],
                                                 dtype)
        if prefill:
            with jax.named_scope('lm/latent_prefill'):
                tail = expanded(q_nope[slots:], q_rope[slots:], first,
                                table[slots], batch['chunk_len'][0], pool,
                                w_kvb, kv_lora=kv_lora, scale=scale)
            out = jnp.concatenate([out, tail], axis=0)
        # padding rows come back unspecified: keep them finite
        out = jnp.where((batch['valid'] > 0)[:, None, None], out, 0.0)
        return _matmul(out.reshape(-1, heads * v_dim), layer['wo'],
                       dtype), pool

    def experts(layer, normed, valid):
        with jax.named_scope('lm/router'):
            probs, chosen = grouped_experts.route(
                normed, layer['router'], cfg.num_experts_per_tok,
                cfg.norm_topk_prob)
            probs = probs * cfg.routed_scaling_factor
        held = normed.astype(dtype)
        with jax.named_scope('lm/experts'):
            mixed, counted = grouped_experts.expert_ffn(
                held, probs, chosen, layer['w_gate_up'], layer['w_down'],
                valid, first=cfg.first_held_expert)
        if cfg.shared_width:
            with jax.named_scope('lm/shared_expert'):
                mixed = mixed + grouped_experts.shared_expert(
                    held, layer['shared_gate_up'], layer['shared_down'])
        return mixed, counted

    def step(params, cache, prev_ids, batch):
        tokens = jnp.where(batch['token_src'] >= 0,
                           prev_ids[jnp.maximum(batch['token_src'], 0)],
                           batch['tokens'])
        valid = batch['valid'] > 0
        positions = batch['positions'].astype(jnp.float32)
        angle = positions[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angle) * cos_scale, jnp.sin(angle) * cos_scale
        q_scale = (1.0 + beta * jnp.log1p(jnp.floor(positions / original))
                   )[:, None, None]
        x = params['embed'][tokens].astype(jnp.float32)
        pool = cache['latents']
        counts = []
        for index, layer in enumerate(params['layers']):
            normed = _rms_norm(x, layer['attn_norm'], eps)
            mixed, pool = attention(layer, index, normed, cos, sin, q_scale,
                                    batch, pool)
            x = x + mixed
            mixed, counted = experts(
                layer, _rms_norm(x, layer['mlp_norm'], eps), valid)
            x = x + mixed
            counts.append(counted)
        last = _rms_norm(x[batch['out_rows']], params['final_norm'], eps)
        logits = _matmul(last, params['head'], dtype)
        next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return {'latents': pool}, next_ids, logits, jnp.stack(counts)

    return step


def cache_shapes(cfg: LatentConfig, geometry: lm_cache.CacheGeometry
                 ) -> Dict[str, tuple]:
    """The one pool: every layer owns ``pool_layer_pages`` latent pages of
    ``[kv_lora + rope, page_size]`` (its last takes the padding rows'
    writes)."""
    return {'latents': (cfg.num_layers * geometry.pool_layer_pages,
                        cfg.latent_width, geometry.page_size)}


def zero_cache(cfg: LatentConfig, geometry: lm_cache.CacheGeometry,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    return {name: jnp.zeros(shape, dtype)
            for name, shape in cache_shapes(cfg, geometry).items()}


def describe(cfg: LatentConfig) -> str:
    return ('%d layers of latent attention (%d heads, q_lora %d, kv_lora '
            '%d, qk %d+%d, v %d) and experts (%d held from %d of %d '
            'routed, top-%d, width %d; shared %d), hidden %d, vocabulary '
            '%d: %.3fB parameters'
            % (cfg.num_layers, cfg.num_attention_heads, cfg.q_lora_rank,
               cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
               cfg.v_head_dim, cfg.held_experts, cfg.first_held_expert,
               cfg.routed_experts, cfg.num_experts_per_tok,
               cfg.moe_intermediate_size, cfg.shared_width, cfg.hidden_size,
               cfg.vocab_size, cfg.parameters() / 1e9))


# ------------------------------------------ what the step loop asks of it
# (the same names as models/decoder.py's: serving/lm_scheduler.py serves
# whichever module it is handed)
load_config = LatentConfig.from_dict
#: the gauge a slot's share is read on: a slot here is a decode row and
#: holds nothing of its own
SLOT_GAUGE = 'serving/lm_slot_fill'
#: the counters of the model's own that ``log_counts`` feeds
COUNTERS = ('serving/lm_routing_choices_total',
            'serving/lm_held_choices_total',
            'serving/lm_latent_positions_read_total',
            'serving/lm_latent_positions_upprojected_total')
#: the name ``stats()`` gives the sum of every step's counts
COUNTS_STAT = 'expert_tokens'

#: the decode rows' absorbed product by name: the ``jax.numpy`` form, the
#: Pallas kernel, and the kernel in Pallas's TPU interpreter (tests)
DECODE = {'jnp': latent_attention.absorbed_reference,
          'pallas': pallas_latent.absorbed_decode,
          'interpret': functools.partial(pallas_latent.absorbed_decode,
                                         interpret=True)}


#: the chunk's expanded product by name, as ``DECODE``
PREFILL = {'jnp': latent_attention.expanded_chunk,
           'pallas': pallas_latent.expanded_prefill,
           'interpret': functools.partial(pallas_latent.expanded_prefill,
                                          interpret=True)}


def step_kernels(platform: str) -> dict:
    """The names of the model's own kernels ``make_step`` takes, for step
    programs that run on ``platform``: both products are the Pallas
    kernels on a TPU and the ``jax.numpy`` forms elsewhere."""
    name = 'pallas' if platform == 'tpu' else 'jnp'
    return {'latent_decode': name, 'latent_prefill': name}


def ring_window(cfg: LatentConfig) -> int:
    """No layer keeps a ring."""
    return 0


def check_geometry(cfg: LatentConfig,
                   geometry: lm_cache.CacheGeometry) -> None:
    """Any page size serves; the kernel's own compile refuses a page that
    is not a whole number of lanes."""


def counts_shape(cfg: LatentConfig) -> Tuple[int, int]:
    return cfg.num_layers, cfg.held_experts


def step_shape(cfg: LatentConfig, geometry: lm_cache.CacheGeometry,
               chunk: int, subchunk: int) -> StepShape:
    """The shape of the step that carries ``chunk`` prompt tokens beside
    the decode rows (``subchunk`` is the sliding layers' of another
    model)."""
    g = geometry
    return StepShape(tokens=g.slots + chunk, chunk=chunk,
                     outputs=g.slots + 1, slots=g.slots,
                     full_pages=g.pages_per_seq)


def program_name(shape: StepShape) -> str:
    """The name its jitted program goes by in a trace: one a shape."""
    return 'lmlatent_step_%d' % shape.chunk


def pad_rows(cfg: LatentConfig, geometry: lm_cache.CacheGeometry,
             views: dict) -> None:
    """Nothing of this model's own: an idle decode row has length 0."""


class StepPlan:
    """The host's side of one step: each decode row's length and the
    chunk's, filled by the step loop a decode row at a time, then the
    chunk; and what the step reads, for its counters.  The loop itself
    fills what every model's step has (tokens, positions, output rows, the
    page pool's rows and table)."""

    def __init__(self, cfg: LatentConfig, geometry: lm_cache.CacheGeometry,
                 views: dict, subchunk: int):
        self.g = geometry
        self.top_k = cfg.num_experts_per_tok
        self.lens = views['latent_lens']
        self.chunk_len = views['chunk_len']
        self.tokens = self.read = self.upprojected = 0

    def decode_row(self, row: int, lease: lm_cache.Lease, at: int) -> None:
        self.lens[row] = at + 1
        self.read += at + 1
        self.tokens += 1

    def end_decode(self, n: int) -> int:
        """The ``n`` decode rows are in.  The chunk starts behind every
        decode row of the program, in use or not."""
        return self.g.slots

    def chunk(self, n: int, lease: lm_cache.Lease, first: int,
              taken: int) -> None:
        self.chunk_len[0] = first + taken
        self.upprojected = first + taken
        self.tokens += taken

    def close(self) -> dict:
        """The step is whole.  Returns what ``log_counts`` is to know of
        the plan."""
        return {'choices': self.tokens * self.top_k,
                'latent_read': self.read, 'upprojected': self.upprojected}


def log_counts(counts: np.ndarray, note: dict) -> Tuple[dict, dict]:
    """(what the step log keeps of a step's ``counts`` [layers, held
    experts], {counter: its increment})."""
    layers = counts.shape[0]
    held = int(counts.sum())
    return ({'experts_touched': (counts > 0).sum(axis=1),
             'held_choices': held},
            {'serving/lm_routing_choices_total': note['choices'] * layers,
             'serving/lm_held_choices_total': held,
             'serving/lm_latent_positions_read_total':
                 note['latent_read'] * layers,
             'serving/lm_latent_positions_upprojected_total':
                 note['upprojected'] * layers})


def step_gauges(counts: np.ndarray) -> Dict[str, float]:
    """{gauge: value} of a step's counts, where telemetry is on: the held
    experts' load."""
    per_layer = counts.max(axis=1) / np.maximum(counts.mean(axis=1), 1e-9)
    return {'serving/lm_expert_load_max_over_mean': float(per_layer.mean())}
