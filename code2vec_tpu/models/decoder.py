"""A decoder-only token language model on the serving path: the layer
equations, the seeded weights, and the one step program the engine runs.

The architecture is read from a published ``config.json`` (``model_type``
``mellum``: grouped-query attention, sliding-window and full layers mixed,
each with its own RoPE table, and a sparse expert layer after every
attention).  For layer ``l``::

    h = x + Attn_l(RMSNorm(x))        y = h + MoE(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g

- attention: ``q = xW_q``, ``k = xW_k``, ``v = xW_v``, no biases; ``q``
  and ``k`` rotated by the layer type's RoPE at the token's position
  (rotate-half pairing ``(i, i + head_dim/2)``); query heads share
  key/value heads in groups; scores ``q.k / sqrt(head_dim)``, softmax in
  float32 over keys ``j <= i``, in sliding layers also ``i - j < window``.
- RoPE: ``default`` is ``theta^(-2i/d)``; ``yarn`` blends that with the
  same divided by ``factor`` along a linear ramp between the rotations
  ``beta_fast`` and ``beta_slow`` make in the original context, and scales
  cos and sin by ``attention_factor``.
- MoE: ``p = softmax(hW_r)`` in float32 over all experts, the ``top_k``
  largest renormalised; ``sum_e p_e W_down,e(silu(W_gate,e h) * W_up,e h)``.
- head: ``logits = RMSNorm(x_L) W_head``, untied.

Weights are held in bfloat16 and multiplied in bfloat16 with float32
accumulation; the residual stream, the norms, the router and the softmax
are float32.  The plain float32 statement of the same equations, which the
tests and the benchmark's check compare with, is
``chipbench/reference_mellum2.py``; it shares no code with this file.

One **step** (``make_step``) carries, in one flat batch of tokens, a
single token for every decoding sequence and a chunk of the prompt of the
sequence that is in prefill; the key/value cache it reads and writes is
the two pools of ``serving/lm_cache.py`` (rings for the sliding layers,
pages for the full ones).  The scheduler that plans steps is
``serving/lm_scheduler.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu.ops import grouped_experts, lm_attention
from code2vec_tpu.serving import lm_cache

SLIDING = 'sliding_attention'
FULL = 'full_attention'


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """What of a published ``config.json`` the equations need."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]       # one entry a layer that is held
    sliding_window: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    vocab_size: int
    rms_norm_eps: float
    rope_full: Tuple[Tuple[str, object], ...]       # rope_parameters, as
    rope_sliding: Tuple[Tuple[str, object], ...]    # sorted items

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_dict(cls, d: dict) -> 'DecoderConfig':
        layers = int(d['num_hidden_layers'])
        kinds = tuple(d['layer_types'][:layers])
        if len(kinds) != layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError('layer_types must name %d sliding_attention/'
                             'full_attention layers, got %r'
                             % (layers, kinds))
        mlp = set(d.get('mlp_layer_types', ['sparse'] * layers)[:layers])
        if mlp != {'sparse'}:
            raise NotImplementedError(
                'only sparse expert layers are implemented; '
                'mlp_layer_types has %s' % sorted(mlp))
        if d.get('attention_bias') or d.get('tie_word_embeddings'):
            raise NotImplementedError('attention biases and tied '
                                      'embeddings are not implemented')
        rope = d['rope_parameters']
        return cls(
            hidden_size=int(d['hidden_size']),
            num_attention_heads=int(d['num_attention_heads']),
            num_key_value_heads=int(d['num_key_value_heads']),
            head_dim=int(d['head_dim']), layer_types=kinds,
            sliding_window=int(d['sliding_window']),
            num_experts=int(d['num_experts']),
            num_experts_per_tok=int(d['num_experts_per_tok']),
            moe_intermediate_size=int(d['moe_intermediate_size']),
            norm_topk_prob=bool(d['norm_topk_prob']),
            vocab_size=int(d['vocab_size']),
            rms_norm_eps=float(d['rms_norm_eps']),
            rope_full=tuple(sorted(rope[FULL].items())),
            rope_sliding=tuple(sorted(rope[SLIDING].items())))

    def parameters(self) -> int:
        h, d = self.hidden_size, self.head_dim
        attention = h * d * (2 * self.num_attention_heads
                             + 2 * self.num_key_value_heads)
        experts = self.num_experts * 3 * h * self.moe_intermediate_size
        layer = attention + h * self.num_experts + 2 * h + experts
        return self.num_layers * layer + 2 * self.vocab_size * h + h


# ------------------------------------------------------------------ RoPE
def rope_inv_freq(rope: dict, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [head_dim / 2] float64, the factor cos and sin
    are scaled by) of one ``rope_parameters`` entry."""
    half = head_dim // 2
    base = float(rope['rope_theta'])
    extrapolated = base ** (-2.0 * np.arange(half, dtype=np.float64)
                            / head_dim)
    kind = rope.get('rope_type', 'default')
    if kind == 'default':
        return extrapolated, 1.0
    if kind != 'yarn':
        raise NotImplementedError('rope_type %r' % kind)
    factor = float(rope['factor'])
    original = float(rope['original_max_position_embeddings'])

    def turn(rotations: float) -> float:
        # the dimension that makes `rotations` turns over the original
        # context
        return head_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(turn(float(rope['beta_fast']))), 0)
    high = min(math.ceil(turn(float(rope['beta_slow']))), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    blended = extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    scale = rope.get('attention_factor')
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return blended, float(scale)


def _rotate(x, cos, sin):
    """``x`` [tokens, heads, head_dim] rotated, pairing (i, i + half)."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    first, second = x32[..., :half], x32[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _matmul(a, w, dtype):
    """``a @ w`` accumulated in float32: operands in bfloat16 as the
    weights are held, or (``COMPUTE_DTYPE`` float32, the tests' exact
    mode) cast up and multiplied at full precision."""
    if dtype == jnp.float32:
        return jnp.dot(a.astype(jnp.float32), w.astype(jnp.float32),
                       precision='highest')
    return jnp.dot(a.astype(dtype), w, preferred_element_type=jnp.float32)


def _rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * gain.astype(jnp.float32)


# --------------------------------------------------------------- weights
def param_shapes(cfg: DecoderConfig) -> dict:
    """The parameter pytree as ``ShapeDtypeStruct``: the model's
    declaration of what it holds (``models/families.py``)."""
    h, d = cfg.hidden_size, cfg.head_dim
    qkv = d * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads)
    bf16 = jnp.bfloat16

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, bf16)
    layer = {
        'attn_norm': s(h), 'wqkv': s(h, qkv),
        'wo': s(d * cfg.num_attention_heads, h), 'mlp_norm': s(h),
        'router': s(h, cfg.num_experts),
        'w_gate_up': s(cfg.num_experts, h, 2 * cfg.moe_intermediate_size),
        'w_down': s(cfg.num_experts, cfg.moe_intermediate_size, h)}
    return {'embed': s(cfg.vocab_size, h), 'head': s(h, cfg.vocab_size),
            'final_norm': s(h),
            'layers': [dict(layer) for _ in range(cfg.num_layers)]}


def init_params(cfg: DecoderConfig, seed: int) -> dict:
    """Seeded weights made on the device in bfloat16, one layer a call so
    that the float32 draws of one layer are all that is held beside the
    result.  Every product keeps its input's variance (``N(0, 1/fan_in)``),
    the embedding is ``N(0, 1)``, the norms' gains are one."""
    shapes = param_shapes(cfg)

    def draw(key, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, leaf in zip(keys, leaves):
            if len(leaf.shape) == 1:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            fan_in = leaf.shape[-2]
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * fan_in ** -0.5).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    root = jax.random.PRNGKey(seed)
    draw_layer = jax.jit(lambda key: draw(key, shapes['layers'][0]))
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
    full_seqs: int         # sequences the full layers' metadata holds
    full_pages: int        # columns of their page table
    window_seqs: int       # (sub)sequences of the window layers' metadata
    window_pages: int      # columns of their rebased page table


#: the int32 arrays a step takes from the host, by name, with their shapes
def batch_shapes(shape: StepShape) -> Dict[str, tuple]:
    return {
        'tokens': (shape.tokens,), 'token_src': (shape.tokens,),
        'positions': (shape.tokens,), 'valid': (shape.tokens,),
        'out_rows': (shape.outputs,),
        'full_rows': (shape.tokens,), 'full_kv_lens': (shape.full_seqs,),
        'full_page_indices': (shape.full_seqs, shape.full_pages),
        'full_cu_q_lens': (shape.full_seqs + 1,), 'full_num_seqs': (1,),
        'window_rows': (shape.tokens,),
        'window_kv_lens': (shape.window_seqs,),
        'window_page_indices': (shape.window_seqs, shape.window_pages),
        'window_cu_q_lens': (shape.window_seqs + 1,),
        'window_num_seqs': (1,)}


def layer_pool_index(cfg: DecoderConfig) -> List[int]:
    """For each layer, its index among the layers of its own kind (which
    slab of its kind's pool is its own)."""
    seen = {SLIDING: 0, FULL: 0}
    out = []
    for kind in cfg.layer_types:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def make_step(cfg: DecoderConfig, shape: StepShape,
              geometry: lm_cache.CacheGeometry, dtype=jnp.bfloat16):
    """The step function for one shape (jit it with ``cache`` donated).

    ``step(params, cache, prev_ids, batch)`` ->
    ``(cache, next_ids [outputs], logits [outputs, vocab] float32,
    expert_counts [layers, experts])``.  ``cache`` is ``{'ring', 'pages'}``
    (``serving/lm_cache.py``), each ``[layers of the kind x pages of one
    layer, page_size, 2 x kv_heads, head_dim]``, donated and returned.
    ``prev_ids`` is the previous step's ``next_ids`` still on the device: a
    token whose ``token_src`` is not negative is read from there, so the
    host never waits for a sampled token before it plans the next step.
    ``dtype`` is what products are multiplied and the cache is held in.
    """
    h, d = cfg.hidden_size, cfg.head_dim
    q_heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    # the pages one layer owns of each pool
    ring_pages, pool_pages = (geometry.ring_layer_pages,
                              geometry.pool_layer_pages)
    tables = {}
    for kind, rope in ((SLIDING, dict(cfg.rope_sliding)),
                       (FULL, dict(cfg.rope_full))):
        inv_freq, factor = rope_inv_freq(rope, d)
        tables[kind] = (jnp.asarray(inv_freq, jnp.float32), factor)
    own = layer_pool_index(cfg)
    prefill = shape.chunk > 0
    scopes = {SLIDING: 'lm/window_attention' if prefill
              else 'lm/decode_attention',
              FULL: 'lm/full_attention' if prefill
              else 'lm/decode_attention'}

    def step(params, cache, prev_ids, batch):
        tokens = jnp.where(batch['token_src'] >= 0,
                           prev_ids[jnp.maximum(batch['token_src'], 0)],
                           batch['tokens'])
        valid = batch['valid'] > 0
        positions = batch['positions'].astype(jnp.float32)
        angles = {}
        for kind, (inv_freq, factor) in tables.items():
            angle = positions[:, None] * inv_freq[None, :]
            angles[kind] = (jnp.cos(angle) * factor, jnp.sin(angle) * factor)
        x = params['embed'][tokens].astype(jnp.float32)
        ring, pages = cache['ring'], cache['pages']
        counts = []
        for index, (kind, layer) in enumerate(zip(cfg.layer_types,
                                                  params['layers'])):
            window = kind == SLIDING
            pool = ring if window else pages
            per_layer = ring_pages if window else pool_pages
            prefix = 'window_' if window else 'full_'
            offset = own[index] * per_layer
            normed = _rms_norm(x, layer['attn_norm'], cfg.rms_norm_eps)
            qkv = _matmul(normed, layer['wqkv'], dtype)
            q = qkv[:, :q_heads * d].reshape(-1, q_heads, d)
            k = qkv[:, q_heads * d:(q_heads + kv_heads) * d].reshape(
                -1, kv_heads, d)
            v = qkv[:, (q_heads + kv_heads) * d:].reshape(-1, kv_heads, d)
            cos, sin = angles[kind]
            q = _rotate(q, cos, sin).astype(dtype)
            k = _rotate(k, cos, sin).astype(dtype)
            # K and V heads interleaved, as the pages hold them
            new = jnp.stack([k, v.astype(dtype)], axis=2).reshape(
                -1, 2 * kv_heads, d)
            page_size = pool.shape[1]
            flat = pool.reshape(-1, 2 * kv_heads, d)
            flat = flat.at[batch[prefix + 'rows']
                           + offset * page_size].set(new)
            pool = flat.reshape(pool.shape)
            with jax.named_scope(scopes[kind]):
                attended = lm_attention.paged_attention(
                    q, pool, batch[prefix + 'kv_lens'],
                    batch[prefix + 'page_indices'] + offset,
                    batch[prefix + 'cu_q_lens'], batch[prefix + 'num_seqs'],
                    sm_scale=d ** -0.5,
                    sliding_window=cfg.sliding_window if window else None,
                    prefill=prefill)
            # padding rows come back unspecified: keep them finite
            attended = jnp.where(valid[:, None, None], attended, 0)
            if window:
                ring = pool
            else:
                pages = pool
            x = x + _matmul(attended.reshape(-1, q_heads * d), layer['wo'],
                            dtype)
            normed = _rms_norm(x, layer['mlp_norm'], cfg.rms_norm_eps)
            with jax.named_scope('lm/experts'):
                probs, experts = grouped_experts.route(
                    normed, layer['router'], cfg.num_experts_per_tok,
                    cfg.norm_topk_prob)
                mixed, counted = grouped_experts.expert_ffn(
                    normed.astype(dtype), probs, experts,
                    layer['w_gate_up'], layer['w_down'], valid)
            x = x + mixed
            counts.append(counted)
        last = _rms_norm(x[batch['out_rows']], params['final_norm'],
                         cfg.rms_norm_eps)
        logits = _matmul(last, params['head'], dtype)
        next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return ({'ring': ring, 'pages': pages}, next_ids, logits,
                jnp.stack(counts))

    return step


def take_row(logits, row):
    """One row of a step's logits, kept on the device until its request is
    delivered."""
    return jax.lax.dynamic_index_in_dim(logits, row, axis=0, keepdims=False)


def cache_shapes(cfg: DecoderConfig, geometry: lm_cache.CacheGeometry
                 ) -> Dict[str, tuple]:
    """Shapes of the two pools: every layer of a kind owns
    ``ring_layer_pages``/``pool_layer_pages`` pages of its kind's (the last
    takes the padding rows' writes)."""
    kinds = cfg.layer_types
    combined = 2 * cfg.num_key_value_heads
    g = geometry
    return {
        'ring': (max(kinds.count(SLIDING), 1) * g.ring_layer_pages,
                 g.page_size, combined, cfg.head_dim),
        'pages': (max(kinds.count(FULL), 1) * g.pool_layer_pages,
                  g.page_size, combined, cfg.head_dim)}


def zero_cache(cfg: DecoderConfig, geometry: lm_cache.CacheGeometry,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    return {name: jnp.zeros(shape, dtype)
            for name, shape in cache_shapes(cfg, geometry).items()}


def describe(cfg: DecoderConfig) -> str:
    return ('%d layers (%d sliding of window %d, %d full), hidden %d, %d '
            'query / %d key-value heads of %d, %d experts top-%d of width '
            '%d, vocabulary %d: %.3fB parameters'
            % (cfg.num_layers, cfg.layer_types.count(SLIDING),
               cfg.sliding_window, cfg.layer_types.count(FULL),
               cfg.hidden_size, cfg.num_attention_heads,
               cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts,
               cfg.num_experts_per_tok, cfg.moe_intermediate_size,
               cfg.vocab_size, cfg.parameters() / 1e9))


# ------------------------------------------ what the step loop asks of it
# serving/lm_scheduler.py serves whichever model's module it is handed
# through these names and `batch_shapes`, `make_step`, `zero_cache`,
# `take_row` above, and knows nothing else of the model.
load_config = DecoderConfig.from_dict
#: the gauge a slot's share is read on: in this model a slot is a ring
SLOT_GAUGE = 'serving/lm_ring_pool_fill'
#: the counters of the model's own that ``log_counts`` feeds
COUNTERS: Tuple[str, ...] = ()
#: the name ``stats()`` gives the sum of every step's counts
COUNTS_STAT = 'expert_tokens'


def step_kernels(platform: str) -> dict:
    """The names of the model's own kernels ``make_step`` takes: none (the
    attention and expert kernels are the library's, and
    ``ops/lm_attention.py`` and ``ops/grouped_experts.py`` choose them)."""
    return {}


def ring_window(cfg: DecoderConfig) -> int:
    """Positions a slot's ring has to keep behind a query."""
    return cfg.sliding_window


def check_geometry(cfg: DecoderConfig,
                   geometry: lm_cache.CacheGeometry) -> None:
    """Any page size serves: keys and values lie a position a row."""


def counts_shape(cfg: DecoderConfig) -> Tuple[int, int]:
    return cfg.num_layers, cfg.num_experts


def step_shape(cfg: DecoderConfig, geometry: lm_cache.CacheGeometry,
               chunk: int, subchunk: int) -> StepShape:
    """The shape of the step that carries ``chunk`` prompt tokens beside
    the decode rows.  A chunk is one sequence to the full layers and one
    every ``subchunk`` tokens to the sliding ones, each with a window's
    worth of pages."""
    g = geometry
    return StepShape(
        tokens=g.slots + chunk, chunk=chunk, outputs=g.slots + 1,
        full_seqs=g.slots + (1 if chunk else 0), full_pages=g.pages_per_seq,
        window_seqs=g.slots + lm_cache.ceil_div(chunk, subchunk),
        window_pages=g.window_table_pages(
            min(subchunk, chunk) if chunk else 1))


def program_name(shape: StepShape) -> str:
    """The name its jitted program goes by in a trace."""
    return 'run'


def pad_rows(cfg: DecoderConfig, geometry: lm_cache.CacheGeometry,
             views: dict) -> None:
    """A step's inputs of this model's own with no sequence in them: the
    ring's writes go to the spare page."""
    g = geometry
    views['window_rows'][:] = g.slots * g.ring_pages * g.page_size


class StepPlan:
    """The host's side of one step: the metadata of the two attention
    calls, filled by the step loop a decode row at a time, then the chunk.
    The loop itself fills what every model's step has (tokens, positions,
    output rows, the page pool's rows and table)."""

    def __init__(self, cfg: DecoderConfig, geometry: lm_cache.CacheGeometry,
                 views: dict, subchunk: int):
        self.g, self.subchunk = geometry, subchunk
        self.full_lens = views['full_kv_lens']
        self.full_cu = views['full_cu_q_lens']
        self.full_num = views['full_num_seqs']
        self.window_rows = views['window_rows']
        self.window_lens = views['window_kv_lens']
        self.window_table = views['window_page_indices']
        self.window_cu = views['window_cu_q_lens']
        self.window_num = views['window_num_seqs']
        self.width = self.window_table.shape[1]
        self.full_seqs = self.window_seqs = 0

    def decode_row(self, row: int, lease: lm_cache.Lease, at: int) -> None:
        g = self.g
        self.full_lens[row] = at + 1
        self.window_rows[row] = lm_cache.ring_rows(g, lease.slot, at)
        self.window_lens[row], self.window_table[row] = \
            lm_cache.window_view(g, lease.slot, at, 1, self.width)

    def end_decode(self, n: int) -> int:
        """The ``n`` decode rows are in.  Returns the row of the batch, and
        of the page table, the chunk starts at: right behind them."""
        self.full_cu[:n + 1] = np.arange(n + 1)
        self.window_cu[:n + 1] = np.arange(n + 1)
        self.full_seqs = self.window_seqs = n
        return n

    def chunk(self, n: int, lease: lm_cache.Lease, first: int,
              taken: int) -> None:
        """``taken`` prompt tokens at positions ``first ..`` of the
        sequence that holds ``lease``, behind ``n`` decode rows."""
        g = self.g
        self.full_lens[n] = first + taken
        self.full_cu[n + 1] = n + taken
        self.full_seqs = n + 1
        self.window_rows[n:n + taken] = lm_cache.ring_rows(
            g, lease.slot, np.arange(first, first + taken))
        for begin in range(0, taken, self.subchunk):
            q_len = min(self.subchunk, taken - begin)
            seq = self.window_seqs
            self.window_lens[seq], self.window_table[seq] = \
                lm_cache.window_view(g, lease.slot, first + begin, q_len,
                                     self.width)
            self.window_cu[seq + 1] = n + begin + q_len
            self.window_seqs += 1

    def close(self) -> dict:
        """The step is whole.  Returns what ``log_counts`` is to know of
        the plan (nothing here)."""
        self.full_cu[self.full_seqs + 1:] = self.full_cu[self.full_seqs]
        self.full_num[0] = self.full_seqs
        self.window_cu[self.window_seqs + 1:] = \
            self.window_cu[self.window_seqs]
        self.window_num[0] = self.window_seqs
        return {}


def log_counts(counts: np.ndarray, note: dict) -> Tuple[dict, dict]:
    """(what the step log keeps of a step's ``counts`` [layers, experts],
    {counter: its increment})."""
    return {'experts_touched': (counts > 0).sum(axis=1)}, {}


def step_gauges(counts: np.ndarray) -> Dict[str, float]:
    """{gauge: value} of a step's counts, where telemetry is on."""
    per_layer = counts.max(axis=1) / np.maximum(counts.mean(axis=1), 1e-9)
    return {'serving/lm_expert_load_max_over_mean': float(per_layer.mean())}
