"""code2vec's forward pass, loss and top-k, plainly.

Written from the paper's equations (Alon et al., "code2vec: Learning
Distributed Representations of Code", POPL 2019, section 4.2) in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``: no
kernel, no packing, no batching tricks, and independent of the program's
``models/functional.py`` and ``ops/``. For a method with path-contexts
``(x_s, p, x_t)_i``::

    c_i     = [ value_vocab[x_s] ; path_vocab[p] ; value_vocab[x_t] ]
    c~_i    = tanh(W c_i)
    alpha_i = exp(c~_i . a) / sum_j exp(c~_j . a)
    v       = sum_i alpha_i c~_i
    q(y)    = exp(v . tags_vocab[y]) / sum_y' exp(v . tags_vocab[y'])
    loss    = - log q(label), averaged over the batch

Departures, each forced by the data and not by speed: a context slot that
holds no context takes no part in the softmax over contexts, and rows of the
tag table beyond the vocabulary (the program pads tables to a multiple of
128) take no part in the softmax over tags.

It also parses ``.c2v`` lines by itself, with the vocabulary's dictionaries
and nothing of the program's reader: the first ``max_contexts`` contexts of a
line, an unknown word to the vocabulary's OOV index.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Tables(NamedTuple):
    """The five parameter arrays, float32, and the number of real tags."""
    value_vocab: jax.Array    # (tokens, d)
    path_vocab: jax.Array     # (paths, d)
    tags_vocab: jax.Array     # (tags, D)
    w: jax.Array              # (3d, D)
    a: jax.Array              # (D, 1)
    n_tags: int


class Parsed(NamedTuple):
    source: np.ndarray    # (B, C) int32
    path: np.ndarray
    target: np.ndarray
    valid: np.ndarray     # (B, C) bool: the slot holds a context
    label: np.ndarray     # (B,) int32
    contexts: list        # per line, its (source, path, target) strings


def parse_lines(lines: Sequence[str], token_index: Dict[str, int],
                path_index: Dict[str, int], tag_index: Dict[str, int],
                oov: Dict[str, int], max_contexts: int) -> Parsed:
    n = len(lines)
    source = np.zeros((n, max_contexts), np.int32)
    path = np.zeros((n, max_contexts), np.int32)
    target = np.zeros((n, max_contexts), np.int32)
    valid = np.zeros((n, max_contexts), bool)
    label = np.zeros((n,), np.int32)
    contexts = []
    for row, line in enumerate(lines):
        name, *parts = line.rstrip('\n').split(' ')
        label[row] = tag_index.get(name, oov['tag'])
        kept = []
        for slot, part in enumerate(parts[:max_contexts]):
            if not part:
                continue
            s, p, t = part.split(',')
            source[row, slot] = token_index.get(s, oov['token'])
            path[row, slot] = path_index.get(p, oov['path'])
            target[row, slot] = token_index.get(t, oov['token'])
            valid[row, slot] = True
            kept.append((s, p, t))
        contexts.append(kept)
    return Parsed(source, path, target, valid, label, contexts)


def forward(tables: Tables, source, path, target, valid):
    """(code vectors (B, D), attention (B, C), logits (B, tags))."""
    with jax.default_matmul_precision('highest'):
        c = jnp.concatenate([tables.value_vocab[source],
                             tables.path_vocab[path],
                             tables.value_vocab[target]], axis=-1)
        c_tilde = jnp.tanh(c @ tables.w)
        score = (c_tilde @ tables.a)[..., 0]
        score = jnp.where(valid, score, -jnp.inf)
        score = score - jnp.max(score, axis=1, keepdims=True)
        weight = jnp.where(valid, jnp.exp(score), 0.0)
        alpha = weight / jnp.sum(weight, axis=1, keepdims=True)
        v = jnp.sum(alpha[..., None] * c_tilde, axis=1)
        logits = v @ tables.tags_vocab.T
        real = jnp.arange(logits.shape[1]) < tables.n_tags
        return v, alpha, jnp.where(real[None, :], logits, -jnp.inf)


def log_q(logits):
    top = jnp.max(logits, axis=1, keepdims=True)
    return logits - top - jnp.log(
        jnp.sum(jnp.exp(logits - top), axis=1, keepdims=True))


def loss(tables: Tables, source, path, target, valid, label):
    """Mean cross-entropy of the batch."""
    _, _, logits = forward(tables, source, path, target, valid)
    picked = jnp.take_along_axis(log_q(logits), label[:, None], axis=1)
    return -jnp.mean(picked)


def top_k(logits, k: int):
    """(values, indices) of the k largest logits of each row, and the
    scores the program reports for them: a softmax over those k."""
    values, indices = jax.lax.top_k(logits, k)
    shifted = jnp.exp(values - values[:, :1])
    return values, indices, shifted / jnp.sum(shifted, axis=1, keepdims=True)
