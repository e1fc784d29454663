"""Linear attention with a per-head decay (the Lightning Attention
family): the recurrence

    S_t = gamma_h S_{t-1} + k_t^T v_t        o_t = q_t S_t / sqrt(d)

with one ``d x d`` float32 state a head, in the two forms serving needs.

- **prefill** (``chunk_scan``): a chunk of a prompt in blocks of
  ``BLOCK`` positions.  Inside a block the products are those of causal
  attention with the decay as the mask, ``(q k^T * D) v`` with
  ``D[t, u] = gamma^(t - u)`` for ``u <= t``; between blocks the state is
  carried, ``q_t (gamma^(t + 1) S_in)`` added to a position's output and
  ``S_out = gamma^B S_in + sum_u gamma^(B - 1 - u) k_u^T v_u``.  The decay
  is written as ``exp(difference of cumulative logs)``, never as a ratio of
  two powers, so a fast-decaying head neither overflows nor divides by
  zero.  Padding positions (``valid`` 0) neither decay the state nor add
  to it, so the state after a padded chunk is the state after its real
  tokens, whatever bucket carried them.
- **decode** (``decode_update``): one token a sequence, the recurrence as
  written.

The state stays float32 between blocks, chunks and turns; every product
that reads or writes it is computed at full float32 precision (on a TPU a
float32 product otherwise rounds its operands to bfloat16, which would be a
bfloat16 state by another name).  The products of a block's own ``q``,
``k`` and ``v`` take the operands as they come (bfloat16 on the chip) and
accumulate in float32.  Plain ``jax.numpy``: the same code runs in the CPU
tests and compiles for the chip, where each form's operations carry its
``jax.named_scope`` (the caller's) in the compiled program's metadata.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: positions a block of the chunked scan holds
BLOCK = 128


def decay_rates(num_heads: int, layer_index: int, num_layers: int
                ) -> np.ndarray:
    """``-log(gamma_h)`` of every head of the layer at (published) index
    ``layer_index`` of ``num_layers``: the family's ALiBi-style slopes
    ``2^(-8 (h + 1) / heads)`` times the layer factor
    ``1 - l / (L - 1) + 1e-5``."""
    slopes = 2.0 ** (-8.0 * (np.arange(num_heads, dtype=np.float64) + 1)
                     / num_heads)
    return slopes * (1.0 - layer_index / (num_layers - 1) + 1e-5)


def _exact(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision='highest')


def _mixed(spec: str, a, b):
    """Operands as they come, float32 accumulation; float32 operands at
    full precision."""
    if a.dtype == jnp.float32 or b.dtype == jnp.float32:
        return _exact(spec, a, b)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def chunk_scan(q, k, v, rates, valid, state, block: int = BLOCK):
    """``q, k, v`` [tokens, heads, d] of ONE sequence in order, ``rates``
    [heads] float32 (``-log gamma``), ``valid`` [tokens] (0 at padding),
    ``state`` [heads, d, d] float32 before the first token.  Returns the
    outputs [tokens, heads, d] float32 and the state after the last valid
    token."""
    tokens, heads, d = q.shape
    blocks = -(-tokens // block)
    pad = blocks * block - tokens
    if pad:
        q, k, v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        valid = jnp.pad(valid, (0, pad))
    shape = (blocks, block, heads, d)
    q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
    live = (valid > 0).astype(jnp.float32).reshape(blocks, block)
    rates = rates.astype(jnp.float32)
    lower = jnp.tril(jnp.ones((block, block), jnp.float32))
    scale = 1.0 / math.sqrt(d)

    def one(carry, xs):
        qb, kb, vb, lb = xs
        # valid positions up to and including each one: the decay's clock
        clock = jnp.cumsum(lb)
        # D[h, t, u] = gamma_h^(clock_t - clock_u) for u <= t, valid u
        gap = clock[:, None] - clock[None, :]
        decay = jnp.exp(-rates[:, None, None] * gap[None]) \
            * (lower * lb[None, :])[None]
        scores = _mixed('thd,uhd->htu', qb, kb) * decay
        intra = _mixed('htu,uhd->thd', scores.astype(vb.dtype), vb)
        carried = jnp.exp(-rates[None, :] * clock[:, None])     # [t, h]
        inter = _exact('thd,hde->the', qb, carry) * carried[:, :, None]
        left = jnp.exp(-rates[None, :] * (clock[-1] - clock)[:, None]) \
            * lb[:, None]                                       # [u, h]
        added = _exact('uhd,uhe->hde',
                       kb.astype(jnp.float32) * left[:, :, None], vb)
        carry = carry * jnp.exp(-rates * clock[-1])[:, None, None] + added
        return carry, (intra + inter) * scale

    state, out = jax.lax.scan(one, state.astype(jnp.float32),
                              (q, k, v, live))
    return out.reshape(blocks * block, heads, d)[:tokens], state


def decode_update(q, k, v, rates, valid, states):
    """One token for each of ``rows`` sequences: ``q, k, v`` [rows, heads,
    d], ``states`` [rows, heads, d, d] float32.  A row with ``valid`` 0
    leaves its state as it was.  Returns outputs [rows, heads, d] float32
    and the new states."""
    d = q.shape[-1]
    live = (valid > 0).astype(jnp.float32)[:, None]
    gamma = jnp.exp(-rates.astype(jnp.float32)[None, :] * live)  # [r, h]
    outer = k.astype(jnp.float32)[..., :, None] \
        * v.astype(jnp.float32)[..., None, :]
    states = states * gamma[:, :, None, None] \
        + outer * live[:, :, None, None]
    out = _exact('rhd,rhde->rhe', q, states) / math.sqrt(d)
    return out, states
