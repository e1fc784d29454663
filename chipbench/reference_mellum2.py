"""The plain reference of the ``mellum2-12b-a2.5b`` configuration: the full
forward pass of the decoder in float32 ``jax.numpy``, matrix products at
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching.  It shares no code with the program's model
(``code2vec_tpu/models/decoder.py``): it is given the same bfloat16-rounded
weights, cast up, and the ids of one whole sequence (prompt and generated).

The equations, from ``config.json`` of
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct (``model_type``
``mellum``).  For layer ``l`` with ``layer_types[l]`` sliding or full::

    h = x + Attn_l(RMSNorm(x))          y = h + MoE(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + 1e-6) * g

Attention: ``q = xW_q`` (32 heads x 128), ``k = xW_k``, ``v = xW_v`` (4
heads x 128), no biases; q and k rotated by the layer type's RoPE at the
token's position; each 8 query heads share one key/value head; scores
``q.k / sqrt(128)``, softmax over keys ``j <= i``, in sliding layers also
``i - j < 1024``; ``out = concat(heads) W_o``.

RoPE, sliding layers: ``inv_freq_i = 500000^(-2i/128)``, i = 0..63, pairing
(i, i + 64) ("rotate half").  RoPE, full layers (YaRN: factor 16, original
8192, beta_fast 32, beta_slow 1): ``extrap_i = 500000^(-2i/128)``,
``interp_i = extrap_i / 16``,
``low = floor(128 ln(8192 / (32 * 2 pi)) / (2 ln 500000))``,
``high = ceil(128 ln(8192 / (1 * 2 pi)) / (2 ln 500000))``, clipped to
[0, 127], ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``; cos and sin are
multiplied by ``attention_factor`` 1.2772588722239782.

MoE: ``p = softmax(hW_r)`` over the 64 experts; the 8 largest, renormalised
to sum 1 (``norm_topk_prob``);
``MoE(h) = sum_e p_e W_down,e (silu(W_gate,e h) * W_up,e h)``, widths
2304 -> 896 -> 2304; no shared expert, no router bias.

Head: ``logits = RMSNorm(x_L) W_head``, untied, 98304 wide.

Departures and assumptions (the configuration's file lists them under
``assumed``): the config is silent on a normalisation of q and k (none is
applied) and on the rotary pairing (rotate-half, the family's convention);
``intermediate_size`` 7168 is unused because every ``mlp_layer_types`` entry
is ``sparse``; no multi-token-prediction head (the config has no key for
one).  Every size is read from the configuration, so the CPU tests run the
same code at a tiny size.

So that it fits beside 10.9 GB of weights it computes layer by layer, the
attention a block of queries at a time and the experts one at a time, every
expert over every token with the probability zero where it was not chosen:
eight times the products, the same sum.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


class LayerWeights(NamedTuple):
    attn_norm: jax.Array    # [hidden]
    wq: jax.Array           # [hidden, heads * head_dim]
    wk: jax.Array           # [hidden, kv_heads * head_dim]
    wv: jax.Array
    wo: jax.Array           # [heads * head_dim, hidden]
    mlp_norm: jax.Array
    router: jax.Array       # [hidden, experts]
    w_gate: jax.Array       # [experts, hidden, width]
    w_up: jax.Array
    w_down: jax.Array       # [experts, width, hidden]


class Weights(NamedTuple):
    embed: jax.Array        # [vocab, hidden]
    head: jax.Array         # [hidden, vocab]
    final_norm: jax.Array
    layers: Iterable[LayerWeights]   # in order; may make each when asked


def inv_freq(rope: dict, head_dim: int) -> tuple:
    """(inverse frequencies [head_dim / 2], factor on cos and sin)."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    extrap = float(rope['rope_theta']) ** (-2.0 * i / head_dim)
    if rope.get('rope_type', 'default') == 'default':
        return extrap, 1.0
    assert rope['rope_type'] == 'yarn', rope
    interp = extrap / float(rope['factor'])
    span = float(rope['original_max_position_embeddings'])
    log_base = 2.0 * math.log(float(rope['rope_theta']))
    low = math.floor(head_dim * math.log(
        span / (float(rope['beta_fast']) * 2 * math.pi)) / log_base)
    high = math.ceil(head_dim * math.log(
        span / (float(rope['beta_slow']) * 2 * math.pi)) / log_base)
    low, high = max(low, 0), min(high, head_dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp), \
        float(rope['attention_factor'])


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def rotate(x, cos, sin):
    """x [n, heads, head_dim]; pairs (i, i + head_dim / 2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, n_valid):
    """q [n, heads, d], k and v [n, kv_heads, d]; causal, and inside
    ``window`` keys where it is not None; a block of queries at a time."""
    n, heads, d = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    at = jnp.arange(n)

    def block(start):
        rows = start + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=0)
        qb = qb.reshape(QUERY_BLOCK, kv_heads, group, d)
        scores = jnp.einsum('qkgd,skd->kgqs', qb, k) / math.sqrt(d)
        seen = (at[None, :] <= rows[:, None]) & (at[None, :] < n_valid)
        if window is not None:
            seen &= rows[:, None] - at[None, :] < window
        # a finite mask: a padding row (past n_valid) that sees no key
        # stays finite and cannot spoil a product it is multiplied into
        scores = jnp.where(seen[None, None], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum('kgqs,skd->qkgd', weights, v)
        return out.reshape(QUERY_BLOCK, heads * d)

    starts = jnp.arange(0, n, QUERY_BLOCK)
    return jax.lax.map(block, starts).reshape(n, heads * d)


def experts(h, layer: LayerWeights, top_k: int, normalize: bool):
    probs = jax.nn.softmax(h @ layer.router.astype(jnp.float32), axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    if normalize:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    n_experts = probs.shape[-1]
    # p_e of every token for every expert, zero where not chosen
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked)

    def one(e, total):
        gate = h @ layer.w_gate[e].astype(jnp.float32)
        up = h @ layer.w_up[e].astype(jnp.float32)
        down = (jax.nn.silu(gate) * up) @ layer.w_down[e].astype(jnp.float32)
        return total + weight[:, e][:, None] * down
    return jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(h)), chosen


@functools.partial(jax.jit, static_argnames=(
    'heads', 'kv_heads', 'head_dim', 'window', 'eps', 'top_k', 'normalize'))
def layer_forward(x, layer: LayerWeights, cos, sin, n_valid, *, heads,
                  kv_heads, head_dim, window, eps, top_k, normalize):
    with jax.default_matmul_precision('highest'):
        n = x.shape[0]
        normed = rms_norm(x, layer.attn_norm, eps)
        q = (normed @ layer.wq.astype(jnp.float32)).reshape(
            n, heads, head_dim)
        k = (normed @ layer.wk.astype(jnp.float32)).reshape(
            n, kv_heads, head_dim)
        v = (normed @ layer.wv.astype(jnp.float32)).reshape(
            n, kv_heads, head_dim)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        h = x + attention(q, k, v, window, n_valid) \
            @ layer.wo.astype(jnp.float32)
        mixed, chosen = experts(rms_norm(h, layer.mlp_norm, eps), layer,
                                top_k, normalize)
        return h + mixed, chosen


@functools.partial(jax.jit, static_argnames=('eps',))
def head_forward(x, final_norm, head, *, eps):
    with jax.default_matmul_precision('highest'):
        return rms_norm(x, final_norm, eps) @ head.astype(jnp.float32)


def forward(config: dict, weights: Weights, token_ids, first_logit: int = 0,
            with_routing: bool = False):
    """Logits [len(token_ids) - first_logit, vocab] float32 of the whole
    sequence ``token_ids`` at positions ``first_logit ..``; with
    ``with_routing`` also the experts chosen, [layers, tokens, top_k]."""
    ids = np.asarray(token_ids, np.int64)
    n_valid = int(ids.shape[0])
    n = -(-n_valid // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.pad(ids, (0, n - n_valid))
    head_dim = int(config['head_dim'])
    positions = np.arange(n, dtype=np.float32)
    tables = {}
    for kind, rope in config['rope_parameters'].items():
        freq, factor = inv_freq(rope, head_dim)
        angle = jnp.asarray(positions)[:, None] \
            * jnp.asarray(freq, jnp.float32)[None, :]
        tables[kind] = (jnp.cos(angle) * factor, jnp.sin(angle) * factor)
    x = weights.embed[jnp.asarray(ids)].astype(jnp.float32)
    routing = []
    layers = int(config['num_hidden_layers'])
    for kind, layer in zip(config['layer_types'][:layers], weights.layers):
        cos, sin = tables[kind]
        x, chosen = layer_forward(
            x, layer, cos, sin, n_valid,
            heads=int(config['num_attention_heads']),
            kv_heads=int(config['num_key_value_heads']), head_dim=head_dim,
            window=(int(config['sliding_window'])
                    if kind == 'sliding_attention' else None),
            eps=float(config['rms_norm_eps']),
            top_k=int(config['num_experts_per_tok']),
            normalize=bool(config['norm_topk_prob']))
        if with_routing:
            routing.append(np.asarray(chosen)[:n_valid])
    logits = head_forward(x[first_logit:n_valid], weights.final_norm,
                          weights.head, eps=float(config['rms_norm_eps']))
    if with_routing:
        return logits, np.stack(routing)
    return logits
