"""The plain reference of the ``minicpm-sala-9b-l16`` configuration: the full
forward pass in float32 ``jax.numpy``, matrix products at
``jax.default_matmul_precision("highest")``, no cache, no kernels, no
batching.  It shares no code with the program's model
(``code2vec_tpu/models/hybrid_decoder.py``, ``code2vec_tpu/ops/``): it is
given the same bfloat16-rounded weights, cast up, and the ids of one whole
sequence (for a session: every prompt and every generated token in order).

The equations, from ``config.json`` of
https://huggingface.co/openbmb/MiniCPM-SALA (``model_type``
``minicpm_sala``) and, for what that file leaves out, from the family's
public code; every such size is listed under ``assumed`` in the
configuration's file.  ``L`` is the PUBLISHED depth (32) also when fewer
layers are run, ``l`` a layer's published index::

    h_0 = E[id] * scale_emb                                   (12)
    h   = h + Mixer_l(RMSNorm(h)) * scale_depth / sqrt(L)     (1.4 / sqrt 32)
    h   = h + W_down(silu(W_gate x) * W_up x) * scale_depth / sqrt(L)
    logits = W_head RMSNorm(h_L) / (hidden_size / dim_model_base)   (/ 16)
    RMSNorm(x) = x / sqrt(mean(x^2) + 1e-6) * g

``lightning-attn``: ``q, k, v = W x`` as 32 heads of 128; q and k per-head
RMSNorm with a gain of 128 entries; q and k rotated (theta 10,000,
rotate-half over all 128 dimensions, absolute position)::

    S_t = gamma_h S_{t-1} + k_t^T v_t        o_t = q_t S_t / sqrt(128)
    gamma_h = exp(-2^(-8 (h + 1) / 32) (1 - l / (L - 1) + 1e-5))

then per-head RMSNorm of ``o``, ``o * sigmoid(W_g x)``, ``W_o``.  Here the
recurrence is what it says: a scan over positions.

``minicpm4`` (InfLLM-V2): ``q`` as 32 heads, ``k, v`` as 2; per-head RMSNorm
of q and k; no rotary embedding.  A query at position ``i`` with
``i + 1 <= dense_len`` (8,192) attends causally to every key, scale
``1/sqrt(128)``.  Beyond it, for each key/value head ``g`` (16 query heads)::

    kbar_j  = mean(k[16 j .. 16 j + 31])        for every j with 16 j + 31 <= i
    p_h     = softmax_j(q_h . kbar_j / sqrt(128))
    P_g     = sum_{h in g} p_h
    score_b = max(P_g[4 b - 1 .. 4 b + 3])       block b = positions 64 b ..
    score_b = +inf   for b = 0 (init_blocks 1) and for every block that
                     overlaps the last 2,048 positions (window_size)
    chosen  = the 64 highest-scoring blocks among those the query can see
              (every one where fewer are visible), ties to the lower index

and ``o`` is causal softmax attention over the positions of the chosen
blocks, the same set for the 16 heads of ``g``; then
``W_o (o * sigmoid(W_g x))``.

**A departure, noted as one**: the family's code takes the dense branch by
the length of the call, which makes an early position's output depend on
how long the sequence later grows.  A served session has no such length, so
the branch is taken by the query's own visible length ``i + 1``: the one
causal reading, under which chunks, turns and this full pass agree.  No
length-dependent scaling of the logits in the layers without RoPE (the
config has no key for one).

So that a pass over some 37,000 positions fits beside 10 GB of weights it
goes layer by layer and product by product, the feed-forward a block of
tokens at a time and the sparse layer a block of queries at a time; every
size is read from the configuration, so the CPU tests run the same code at
a tiny size.  (The same pass with one thing wrong, for the readings the
check's tolerance is set against, is ``chipbench/controls_minicpm_sala.py``'s:
nothing here knows of it.)
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64
TOKEN_BLOCK = 2048

#: the family's public ``sparse_config`` (openbmb/MiniCPM4.1-8B): the
#: catalog's row of MiniCPM-SALA has no such key
SPARSE_CONFIG = {'kernel_size': 32, 'kernel_stride': 16, 'block_size': 64,
                 'window_size': 2048, 'topk': 64, 'init_blocks': 1,
                 'dense_len': 8192}


class LayerWeights(NamedTuple):
    kind: str               # 'lightning-attn' | 'minicpm4'
    index: int              # the published index of the layer
    attn_norm: jax.Array    # [hidden]
    wq: jax.Array           # [hidden, heads * d]
    wk: jax.Array           # [hidden, kv_heads * d]
    wv: jax.Array
    wg: jax.Array           # [hidden, heads * d]: the output gate
    wo: jax.Array           # [heads * d, hidden]
    q_norm: jax.Array       # [d]
    k_norm: jax.Array
    o_norm: Optional[jax.Array]     # [d], lightning layers only
    mlp_norm: jax.Array
    w_gate: jax.Array       # [hidden, intermediate]
    w_up: jax.Array
    w_down: jax.Array       # [intermediate, hidden]


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


def rotate(x, cos, sin):
    """x [n, heads, d]; pairs (i, i + d / 2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=('heads', 'eps'))
def project(x, norm, w, head_norm, *, heads, eps):
    """``RMSNorm(x) W`` as heads, each then RMS-normed where ``head_norm``
    is given."""
    with jax.default_matmul_precision('highest'):
        out = (rms_norm(x, norm, eps) @ f32(w)).reshape(x.shape[0], heads, -1)
        if head_norm is not None:
            out = rms_norm(out, head_norm, eps)
        return out


@jax.jit
def recurrence(q, k, v, gamma):
    """``S_t = gamma S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``, a
    position at a time; q, k, v [n, heads, d], gamma [heads]."""
    with jax.default_matmul_precision('highest'):
        heads, d = q.shape[1], q.shape[2]

        def one(state, qkv):
            qt, kt, vt = qkv
            state = gamma[:, None, None] * state \
                + kt[:, :, None] * vt[:, None, :]
            return state, jnp.einsum('hd,hde->he', qt, state) / math.sqrt(d)
        _, out = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                              (q, k, v))
        return out


@functools.partial(jax.jit, static_argnames=(
    'n_valid', 'kernel_size', 'kernel_stride', 'block_size', 'window_size',
    'topk', 'init_blocks', 'dense_len'))
def sparse_layer_attention(q, k, v, *, n_valid, kernel_size, kernel_stride,
                           block_size, window_size, topk, init_blocks,
                           dense_len):
    """q [n, heads, d], k and v [n, kv_heads, d]; a block of queries at a
    time.  Returns [n, heads * d]."""
    with jax.default_matmul_precision('highest'):
        n, heads, d = q.shape
        kv_heads = k.shape[1]
        group = heads // kv_heads
        scale = 1.0 / math.sqrt(d)
        n_pooled = max((n - kernel_size) // kernel_stride + 1, 1)
        window = kernel_stride * jnp.arange(n_pooled)[:, None] \
            + jnp.arange(kernel_size)[None, :]
        kbar = jnp.mean(k[jnp.minimum(window, n - 1)], axis=1)  # [J, kv, d]
        last_of = kernel_stride * jnp.arange(n_pooled) + kernel_size - 1
        n_blocks = -(-n // block_size)
        per_block = block_size // kernel_stride
        # the pooled keys that overlap block b: r b - 1 .. r b + r - 1
        reads = per_block * jnp.arange(n_blocks)[:, None] - 1 \
            + jnp.arange(per_block + 1)[None, :]
        reads_ok = (reads >= 0) & (reads < n_pooled)
        reads = jnp.clip(reads, 0, n_pooled - 1)
        key_at = jnp.arange(n)
        block_of_key = key_at // block_size
        b = jnp.arange(n_blocks)

        def block(start):
            at = start + jnp.arange(QUERY_BLOCK)                # positions
            qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=0)
            qb = qb.reshape(QUERY_BLOCK, kv_heads, group, d)
            # stage 1: which blocks
            whole = last_of[None, :] <= at[:, None]             # [q, J]
            s1 = jnp.einsum('qkgd,jkd->qkgj', qb, kbar) * scale
            s1 = jnp.where(whole[:, None, None, :], s1, -1e30)
            p = jax.nn.softmax(s1, axis=-1)
            p = jnp.where(whole[:, None, None, :], p, 0.0)
            mass = jnp.sum(p, axis=2)                           # [q, kv, J]
            seen = whole[:, None, reads] & reads_ok[None, None]
            score = jnp.max(jnp.where(seen, mass[:, :, reads], -1.0),
                            axis=-1)                            # [q, kv, B]
            visible = b[None, :] <= (at // block_size)[:, None]
            first_in_window = jnp.maximum(at - window_size + 1, 0) \
                // block_size
            forced = (b[None, :] < init_blocks) | \
                (b[None, :] >= first_in_window[:, None])
            score = jnp.where(forced[:, None, :], jnp.inf, score)
            score = jnp.where(visible[:, None, :], score, -jnp.inf)
            # the topk highest, ties to the lower index: a stable sort
            order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
            chosen = jnp.zeros(score.shape, bool)
            chosen = jax.vmap(jax.vmap(lambda c, o: c.at[o].set(True)))(
                chosen, order) & visible[:, None, :]
            sparse = (at + 1 > dense_len)[:, None, None]
            reads_block = jnp.where(sparse, chosen, visible[:, None, :])
            # stage 2: causal attention over the chosen blocks' positions
            mask = reads_block[:, :, block_of_key] & \
                (key_at[None, None, :] <= at[:, None, None]) & \
                (key_at[None, None, :] < n_valid)
            s2 = jnp.einsum('qkgd,skd->qkgs', qb, k) * scale
            s2 = jnp.where(mask[:, :, None, :], s2, -1e30)
            w = jax.nn.softmax(s2, axis=-1)
            out = jnp.einsum('qkgs,skd->qkgd', w, v)
            return out.reshape(QUERY_BLOCK, heads * d)

        starts = jnp.arange(0, n, QUERY_BLOCK)
        return jax.lax.map(block, starts).reshape(n, heads * d)


@functools.partial(jax.jit, static_argnames=('eps', 'depth_scale'))
def mix_out(x, o, o_norm, normed_gate, wo, *, eps, depth_scale):
    """``x + W_o (norm(o) * gate) * depth_scale``; o [n, heads, d]."""
    with jax.default_matmul_precision('highest'):
        if o_norm is not None:
            o = rms_norm(o, o_norm, eps)
        o = o.reshape(o.shape[0], -1) * normed_gate
        return x + (o @ f32(wo)) * depth_scale


@functools.partial(jax.jit, static_argnames=('eps',))
def gate_of(x, norm, wg, *, eps):
    with jax.default_matmul_precision('highest'):
        return jax.nn.sigmoid(rms_norm(x, norm, eps) @ f32(wg))


@functools.partial(jax.jit, static_argnames=('eps', 'depth_scale'))
def feed_forward(x, norm, w_gate, w_up, w_down, *, eps, depth_scale):
    with jax.default_matmul_precision('highest'):
        h = rms_norm(x, norm, eps)
        inner = jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))
        return x + (inner @ f32(w_down)) * depth_scale


@functools.partial(jax.jit, static_argnames=('eps', 'scale'))
def head_forward(x, final_norm, head, *, eps, scale):
    with jax.default_matmul_precision('highest'):
        return (rms_norm(x, final_norm, eps) @ f32(head)) * scale


def forward(config: dict, weights: Weights, token_ids, first_logit: int = 0,
            logit_positions=None):
    """Logits float32 of the whole sequence ``token_ids``: at positions
    ``first_logit ..`` or, where given, at ``logit_positions``."""
    ids = np.asarray(token_ids, np.int64)
    n_valid = int(ids.shape[0])
    n = -(-n_valid // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.pad(ids, (0, n - n_valid))
    eps = float(config['rms_norm_eps'])
    published = len(config['mixer_types'])
    depth_scale = float(config['scale_depth']) / math.sqrt(published)
    sparse = dict(SPARSE_CONFIG, **config.get('sparse_config', {}))
    d_l = int(config['lightning_head_dim'])
    heads_l = int(config['lightning_nh'])
    freq = float(config['rope_theta']) ** (
        -2.0 * np.arange(d_l // 2, dtype=np.float64) / d_l)
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = f32(weights.embed[jnp.asarray(ids)]) * float(config['scale_emb'])
    for layer in weights.layers:
        if layer.kind == 'lightning-attn':
            q = rotate(project(x, layer.attn_norm, layer.wq, layer.q_norm,
                               heads=heads_l, eps=eps), cos, sin)
            k = rotate(project(x, layer.attn_norm, layer.wk, layer.k_norm,
                               heads=heads_l, eps=eps), cos, sin)
            v = project(x, layer.attn_norm, layer.wv, None, heads=heads_l,
                        eps=eps)
            slope = 2.0 ** (-8.0 * (np.arange(heads_l) + 1.0) / heads_l)
            gamma = np.exp(-slope * (1.0 - layer.index / (published - 1)
                                     + 1e-5))
            o = recurrence(q, k, v, jnp.asarray(gamma, jnp.float32))
            del q, k, v
        else:
            heads = int(config['num_attention_heads'])
            kv_heads = int(config['num_key_value_heads'])
            q = project(x, layer.attn_norm, layer.wq, layer.q_norm,
                        heads=heads, eps=eps)
            k = project(x, layer.attn_norm, layer.wk, layer.k_norm,
                        heads=kv_heads, eps=eps)
            v = project(x, layer.attn_norm, layer.wv, None, heads=kv_heads,
                        eps=eps)
            o = sparse_layer_attention(
                q, k, v, n_valid=n_valid,
                **{key: int(value) for key, value in sparse.items()})
            o = o.reshape(n, heads, -1)
            del q, k, v
        gate = gate_of(x, layer.attn_norm, layer.wg, eps=eps)
        x = mix_out(x, o, layer.o_norm, gate, layer.wo, eps=eps,
                    depth_scale=depth_scale)
        del o, gate
        x = jnp.concatenate([
            feed_forward(x[start:start + TOKEN_BLOCK], layer.mlp_norm,
                         layer.w_gate, layer.w_up, layer.w_down, eps=eps,
                         depth_scale=depth_scale)
            for start in range(0, n, TOKEN_BLOCK)])
    if logit_positions is None:
        rows = x[first_logit:n_valid]
    else:
        rows = x[jnp.asarray(np.asarray(logit_positions, np.int64))]
    return head_forward(
        rows, weights.final_norm, weights.head, eps=eps,
        scale=float(config['dim_model_base']) / float(config['hidden_size']))
