"""The plain reference of the ``mistral-small-4-119b-ep4-l6`` configuration:
the full forward pass in float32 ``jax.numpy``, matrix products at
``jax.default_matmul_precision("highest")``, no cache, no kernels, no
batching, and the attention in its expanded (un-absorbed) form: every
head's keys and values are made from the latents and attended as they
stand.  It shares no code with the program's model
(``code2vec_tpu/models/latent_decoder.py``, ``code2vec_tpu/ops/``): it is
given the same bfloat16-rounded weights, cast up, and the ids of one whole
sequence (for a session: every prompt and every generated token in order).

The equations, from ``config.json`` of
https://huggingface.co/mistralai/Mistral-Small-4-119B-2603 (``model_type``
``mistral4``); what that file leaves out is listed under ``assumed`` in the
configuration's file.  Every layer is the same (``first_k_dense_replace``
0); for a layer and residual ``x``::

    h = x + MLA(RMSNorm(x))
    y = h + Shared(z) + sum_{e in top4(z), held} p_e E_e(z),  z = RMSNorm(h)
    RMSNorm(x) = x / sqrt(mean(x^2) + 1e-6) * g

MLA (32 heads; q_lora 1024, kv_lora 256, qk_nope 64, qk_rope 64, v 128)::

    q         = RMSNorm_q(x W_qa) W_qb          per head [q_nope | q_rope]
    [c | kr]  = x W_kva;  cbar = RMSNorm_kv(c)  one rope key for all heads
    [k_nope | v] per head = cbar W_kvb
    q_rope, kr rotated by YaRN RoPE (theta 1e4, factor 128 over 8,192,
        beta_fast 32, beta_slow 1), pairs (2i, 2i + 1); mscale = mscale_all_dim
        = 1, so cos and sin are unscaled
    q        *= 1 + 0.1 ln(1 + floor(pos / 8192))
    score     = (q_nope . k_nope + q_rope . kr) / sqrt(128) * m^2,
                m = 0.1 ln(128) + 1
    o_h       = sum_{j <= i} softmax_j(score) v_j;   out = concat_h(o_h) W_o

MoE: ``p = softmax(z W_r)`` over all 128 routed experts, the top 4
renormalised, times ``routed_scaling_factor`` (1);
``E_e(z) = W_down,e(silu(W_gate,e z) * W_up,e z)`` of width 2,048.  Of the
routed experts this chip holds ``n_routed_experts`` from
``first_held_expert`` on: a choice of an expert not held adds nothing, as
in the program (the configuration's deployment divides each layer's
experts over four chips).  ``Shared(z)`` is the same form, every token.
``logits = RMSNorm(x_L) W_head``, untied, over the vocabulary slice.

So that a pass over some 18,000 positions fits beside 10.9 GB of weights it
goes layer by layer and product by product: the attention a block of
queries at a time, every expert a block of tokens at a time.  Every size is
read from the configuration, so the CPU tests run the same code at a tiny
size.  (The same pass with one thing wrong, for the readings the check's
tolerance is set against, is ``chipbench/controls_mistral4.py``'s: the
functions it swaps are ``latents`` and, through the configuration,
``rope_interleave`` and ``llama_4_scaling_beta``.)
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64
TOKEN_BLOCK = 2048


class LayerWeights(NamedTuple):
    attn_norm: jax.Array    # [hidden]
    wq_a: jax.Array         # [hidden, q_lora]
    q_norm: jax.Array       # [q_lora]
    wq_b: jax.Array         # [q_lora, heads * (nope + rope)]
    wkv_a: jax.Array        # [hidden, kv_lora + rope]
    kv_norm: jax.Array      # [kv_lora]
    wkv_b: jax.Array        # [kv_lora, heads * (nope + v)]
    wo: jax.Array           # [heads * v, hidden]
    mlp_norm: jax.Array
    router: jax.Array       # [hidden, routed experts]
    w_gate: jax.Array       # [held, hidden, width]
    w_up: jax.Array
    w_down: jax.Array       # [held, width, hidden]
    shared_gate: jax.Array  # [hidden, shared width]
    shared_up: jax.Array
    shared_down: jax.Array  # [shared width, hidden]


class Weights(NamedTuple):
    embed: jax.Array        # [vocab, hidden]
    head: jax.Array         # [hidden, vocab]
    final_norm: jax.Array
    layers: Iterable[LayerWeights]   # in order; may make each when asked


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * f32(gain)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(rope: dict, dim: int) -> np.ndarray:
    """The inverse frequencies [dim / 2] of YaRN (DeepSeek's form): the
    original frequencies where they turn fast over the original context,
    divided by ``factor`` where slowly, a linear ramp between."""
    base = float(rope['rope_theta'])
    original = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get('rope_type', 'default') != 'yarn':
        return original
    factor = float(rope['factor'])
    context = float(rope['original_max_position_embeddings'])

    def dimension(rotations):
        return dim * math.log(context / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dimension(float(rope['beta_fast']))), 0)
    high = min(math.ceil(dimension(float(rope['beta_slow']))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return original / factor * ramp + original * (1.0 - ramp)


def rotate(x, cos, sin, interleaved: bool):
    """x [n, heads, d] rotated: pairs (2i, 2i + 1) where ``interleaved``,
    else (i, i + d / 2)."""
    cos, sin = cos[:, None, :], sin[:, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=('heads', 'eps'))
def queries(x, norm, wq_a, q_norm, wq_b, *, heads, eps):
    with jax.default_matmul_precision('highest'):
        h = rms_norm(x, norm, eps)
        return (rms_norm(h @ f32(wq_a), q_norm, eps) @ f32(wq_b)).reshape(
            x.shape[0], heads, -1)


@functools.partial(jax.jit, static_argnames=('kv_lora', 'eps',
                                              'interleaved'))
def latents(x, norm, wkv_a, kv_norm, cos, sin, *, kv_lora, eps,
            interleaved):
    """What a position keeps: (cbar [n, kv_lora], rotated kr [n, rope])."""
    with jax.default_matmul_precision('highest'):
        kv = rms_norm(x, norm, eps) @ f32(wkv_a)
        cbar = rms_norm(kv[:, :kv_lora], kv_norm, eps)
        kr = rotate(kv[:, None, kv_lora:], cos, sin, interleaved)[:, 0]
        return cbar, kr


@functools.partial(jax.jit, static_argnames=('heads', 'nope', 'n_valid',
                                              'scale', 'interleaved'))
def attention(q, cbar, kr, wkv_b, wo, cos, sin, q_scale, *, heads, nope,
              n_valid, scale, interleaved):
    """The expanded attention, a block of queries at a time: q [n, heads,
    nope + rope] before rotation.  Returns ``out`` [n, hidden]."""
    with jax.default_matmul_precision('highest'):
        n = q.shape[0]
        kv = (cbar @ f32(wkv_b)).reshape(n, heads, -1)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_nope = q[..., :nope] * q_scale[:, None, None]
        q_rope = rotate(q[..., nope:], cos, sin, interleaved) \
            * q_scale[:, None, None]
        key_at = jnp.arange(n)

        def block(start):
            at = start + jnp.arange(QUERY_BLOCK)
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, QUERY_BLOCK)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, QUERY_BLOCK)
            scores = (jnp.einsum('qhd,khd->qhk', qn, k_nope)
                      + jnp.einsum('qhd,kd->qhk', qr, kr)) * scale
            mask = (key_at[None, :] <= at[:, None]) & (key_at[None, :]
                                                      < n_valid)
            scores = jnp.where(mask[:, None, :], scores, -1e30)
            weights = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum('qhk,khd->qhd', weights, v).reshape(
                QUERY_BLOCK, -1)
        out = jax.lax.map(block, jnp.arange(0, n, QUERY_BLOCK))
        return out.reshape(n, -1) @ f32(wo)


@functools.partial(jax.jit, static_argnames=('top_k', 'normalize',
                                              'scaling', 'first', 'held',
                                              'eps'))
def routing(x, norm, router, *, top_k, normalize, scaling, first, held, eps):
    """(z, the weight of each held expert for each token [n, held]): p_e
    where e is among the token's top_k, else 0."""
    with jax.default_matmul_precision('highest'):
        z = rms_norm(x, norm, eps)
        probs = jax.nn.softmax(z @ f32(router), axis=-1)
        picked, chosen = jax.lax.top_k(probs, top_k)
        if normalize:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        dense = jnp.zeros_like(probs).at[
            jnp.arange(x.shape[0])[:, None], chosen].set(picked * scaling)
        return z, dense[:, first:first + held]


@jax.jit
def expert(z, weight, w_gate, w_up, w_down):
    """``weight[:, None] * W_down(silu(W_gate z) * W_up z)``."""
    with jax.default_matmul_precision('highest'):
        inner = jax.nn.silu(z @ f32(w_gate)) * (z @ f32(w_up))
        return weight[:, None] * (inner @ f32(w_down))


@functools.partial(jax.jit, static_argnames=('eps',))
def head_forward(x, final_norm, head, *, eps):
    with jax.default_matmul_precision('highest'):
        return rms_norm(x, final_norm, eps) @ f32(head)


def moe(config: dict, layer: LayerWeights, x):
    """``x + Shared(z) + sum over held experts``, a block of tokens at a
    time."""
    eps = float(config['rms_norm_eps'])
    held = int(config['n_routed_experts'])
    out = []
    for start in range(0, x.shape[0], TOKEN_BLOCK):
        block = x[start:start + TOKEN_BLOCK]
        z, weight = routing(
            block, layer.mlp_norm, layer.router,
            top_k=int(config['num_experts_per_tok']),
            normalize=bool(config['norm_topk_prob']),
            scaling=float(config.get('routed_scaling_factor', 1)),
            first=int(config.get('first_held_expert', 0)), held=held,
            eps=eps)
        total = block + expert(z, jnp.ones((z.shape[0],), jnp.float32),
                               layer.shared_gate, layer.shared_up,
                               layer.shared_down)
        for e in range(held):
            total = total + expert(z, weight[:, e], layer.w_gate[e],
                                   layer.w_up[e], layer.w_down[e])
        out.append(total)
    return jnp.concatenate(out)


def forward(config: dict, weights: Weights, token_ids, first_logit: int = 0,
            logit_positions=None):
    """Logits float32 of the whole sequence ``token_ids``: at positions
    ``first_logit ..`` or, where given, at ``logit_positions``."""
    ids = np.asarray(token_ids, np.int64)
    n_valid = int(ids.shape[0])
    n = -(-n_valid // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.pad(ids, (0, n - n_valid))
    eps = float(config['rms_norm_eps'])
    heads = int(config['num_attention_heads'])
    nope = int(config['qk_nope_head_dim'])
    rope_dim = int(config['qk_rope_head_dim'])
    kv_lora = int(config['kv_lora_rank'])
    rope = dict(config['rope_parameters'])
    interleaved = bool(config.get('rope_interleave', True))
    scale = (nope + rope_dim) ** -0.5
    if rope.get('rope_type', 'default') == 'yarn':
        factor = float(rope['factor'])
        m = yarn_mscale(factor, float(rope.get('mscale_all_dim') or 0))
        scale *= m * m
        cos_scale = yarn_mscale(factor, float(rope.get('mscale') or 0)) / m
    else:
        cos_scale = 1.0
    position = np.arange(n, dtype=np.float64)
    angle = position[:, None] * rope_frequencies(rope, rope_dim)[None, :]
    cos = jnp.asarray(np.cos(angle) * cos_scale, jnp.float32)
    sin = jnp.asarray(np.sin(angle) * cos_scale, jnp.float32)
    beta = float(rope.get('llama_4_scaling_beta') or 0)
    original = float(rope.get('original_max_position_embeddings', 1))
    q_scale = jnp.asarray(1.0 + beta * np.log1p(np.floor(position
                                                         / original)),
                          jnp.float32)
    x = f32(weights.embed[jnp.asarray(ids)])
    for layer in weights.layers:
        q = queries(x, layer.attn_norm, layer.wq_a, layer.q_norm, layer.wq_b,
                    heads=heads, eps=eps)
        cbar, kr = latents(x, layer.attn_norm, layer.wkv_a, layer.kv_norm,
                           cos, sin, kv_lora=kv_lora, eps=eps,
                           interleaved=interleaved)
        x = x + attention(q, cbar, kr, layer.wkv_b, layer.wo, cos, sin,
                          q_scale, heads=heads, nope=nope, n_valid=n_valid,
                          scale=scale, interleaved=interleaved)
        del q, cbar, kr
        x = moe(config, layer, x)
    if logit_positions is None:
        rows = x[first_logit:n_valid]
    else:
        rows = x[jnp.asarray(np.asarray(logit_positions, np.int64))]
    return head_forward(rows, weights.final_norm, weights.head, eps=eps)
