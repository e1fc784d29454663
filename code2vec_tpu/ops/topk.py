"""Cross-shard top-k merge for the column-sharded target softmax.

The reference's top-k runs on a single device over the full 261K-way score
matrix (tensorflow_model.py:299-302). With the target table column-sharded
over the ``model`` mesh axis, the naive jit lowering all-gathers the full
logits (B × V floats over ICI) before a replicated top-k. This shard_map
kernel does the standard two-stage merge instead:

  1. each shard computes a LOCAL top-k over its V/m logit columns;
  2. only the k candidates per shard (values + globalized indices) are
     all-gathered — k·m ≪ V/m traffic (k=10, m=8, V=261K: ~80 floats vs
     ~32K per example);
  3. a final top-k over the m·k candidates yields the exact global result.
     Tie-breaking is by LOWEST GLOBAL INDEX, matching single-device
     ``lax.top_k``: shards own ascending index ranges, each shard's
     candidates are emitted in (value desc, index asc) order, and the
     merge's ``lax.top_k`` picks the leftmost of equal values — which is
     always the lowest global index (tested in tests/test_topk_merge.py).

The same merge shape serves the embedding index (code2vec_tpu/index/):
``sharded_top_k`` is axis-general (the index's store shards over the
*data* axis where the softmax shards over *model*), and the
``padded_local_topk`` / ``merge_topk_host`` pair implements the
host-side streamed merge across store shards, where a shard may hold
FEWER than k rows (k > n_shard pads with −inf/−1 sentinels that the
merge drops).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from code2vec_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

# Index sentinel for padded top-k slots (k > n): value is -inf, index is
# -1 — never a valid row, and np.take-safe (wraps to the last row, whose
# score the merge has already discarded).
PAD_INDEX = -1


def grouped_top_k(x: jax.Array, k: int, group_size: int = 2048
                  ) -> Tuple[jax.Array, jax.Array]:
    """EXACT top-k over the last axis via a two-stage group merge.

    Stage 1 takes top-k within each ``group_size`` slice of the vocab
    axis; stage 2 takes top-k over the groups*k candidates. Every global
    top-k element is necessarily in its group's top-k, so the result is
    exact — and tie-breaking matches ``lax.top_k`` (lowest index wins):
    within a group by lax.top_k itself, across groups because candidates
    are ordered by group and groups cover ascending index ranges.

    Motivation: one monolithic top-k over a (B, 261K) logits matrix makes
    the selection network as wide as the vocab; two narrow stages map
    better onto the VPU. Whether that wins on a given chip is measured,
    not assumed; callers opt in explicitly.

    MEASURED VERDICT (2026-07-29, v5e-class chip, PERF.md): 119.3 ms vs
    lax.top_k's 24.8 ms at (1024, 261K), k=10 — XLA's monolithic top-k
    wins 4.8×; nothing routes here in production. Retained as a tested,
    documented negative result.
    """
    v = x.shape[-1]
    # cap like sharded_top_k: lax.top_k rejects k > axis length
    k = min(k, v)
    if v <= group_size or k >= group_size:
        return jax.lax.top_k(x, k)
    lead = x.shape[:-1]
    groups = -(-v // group_size)
    pad = groups * group_size - v
    if pad:
        pad_widths = [(0, 0)] * len(lead) + [(0, pad)]
        x = jnp.pad(x, pad_widths, constant_values=-jnp.inf)
    grouped = x.reshape(*lead, groups, group_size)
    group_values, group_indices = jax.lax.top_k(grouped, k)  # (..., G, k)
    base = (jnp.arange(groups, dtype=group_indices.dtype)
            * group_size)[:, None]
    cand_values = group_values.reshape(*lead, groups * k)
    cand_indices = (group_indices + base).reshape(*lead, groups * k)
    final_values, positions = jax.lax.top_k(cand_values, k)
    final_indices = jnp.take_along_axis(cand_indices, positions, axis=-1)
    return final_values, final_indices


def sharded_top_k(logits: jax.Array, k: int, mesh: Mesh,
                  shard_axis: str = MODEL_AXIS,
                  batch_axis: str = DATA_AXIS
                  ) -> Tuple[jax.Array, jax.Array]:
    """Top-k over the last axis of ``logits`` laid out
    ``P(batch_axis, shard_axis)`` on ``mesh``. Returns (values, indices),
    both ``P(batch_axis, None)``.

    The default axes are the softmax layout (batch over ``data``, vocab
    columns over ``model``); the embedding index calls it with
    ``shard_axis=DATA_AXIS, batch_axis=None`` — queries replicated, store
    rows (the score columns) sharded over the data axis
    (code2vec_tpu/index/exact.py).

    Falls back to ``lax.top_k`` when the shard axis is trivial.
    ``k`` may exceed the per-shard width V/m (as long as k <= V): each
    shard then contributes all of its columns as candidates.
    """
    shard_size = mesh.shape[shard_axis]
    k = min(k, logits.shape[-1])
    if shard_size == 1:
        return jax.lax.top_k(logits, k)

    def local_merge(local_logits):
        # local_logits: (B/d, V/m) on each (batch, shard) shard
        local_k = min(k, local_logits.shape[-1])
        local_values, local_indices = jax.lax.top_k(local_logits, local_k)
        shard = jax.lax.axis_index(shard_axis)
        global_indices = local_indices + shard * local_logits.shape[-1]
        # gather local_k candidates per shard along the shard axis
        all_values = jax.lax.all_gather(local_values, shard_axis)
        all_indices = jax.lax.all_gather(global_indices, shard_axis)
        # (m, B/d, local_k) -> (B/d, m*local_k); m*local_k >= k always
        all_values = jnp.moveaxis(all_values, 0, 1).reshape(
            local_values.shape[0], -1)
        all_indices = jnp.moveaxis(all_indices, 0, 1).reshape(
            local_values.shape[0], -1)
        merged_values, positions = jax.lax.top_k(all_values, k)
        merged_indices = jnp.take_along_axis(all_indices, positions, axis=1)
        return merged_values, merged_indices

    # check_vma=False: outputs ARE replicated along the shard axis (post
    # all_gather + identical merge on every shard) but the static checker
    # can't prove it
    return jax.shard_map(local_merge, mesh=mesh,
                         in_specs=(P(batch_axis, shard_axis),),
                         out_specs=(P(batch_axis), P(batch_axis)),
                         check_vma=False)(logits)


def padded_local_topk(x: jax.Array, k: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """``lax.top_k`` over the last axis where ``k`` MAY exceed the axis
    length: the result is padded to exactly ``k`` slots with ``-inf``
    values and ``PAD_INDEX`` indices, so per-shard candidate lists from
    unevenly-sized shards stack rectangularly and ``merge_topk_host``
    can drop the sentinels. Traceable (static shapes only)."""
    n = x.shape[-1]
    local_k = min(k, n)
    values, indices = jax.lax.top_k(x, local_k)
    if local_k < k:
        pad_widths = [(0, 0)] * (x.ndim - 1) + [(0, k - local_k)]
        values = jnp.pad(values, pad_widths, constant_values=-jnp.inf)
        indices = jnp.pad(indices, pad_widths,
                          constant_values=PAD_INDEX)
    return values, indices


def merge_topk_host(values: np.ndarray, indices: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side EXACT merge of per-shard top-k candidates.

    ``values``/``indices`` are ``(..., m)`` numpy arrays of candidate
    scores and GLOBAL row indices — typically the concatenation of each
    shard's ``padded_local_topk`` output with per-shard offsets already
    applied. Sentinel slots (``-inf`` value / ``PAD_INDEX``) sort past
    every real candidate and are returned only when fewer than ``k``
    real candidates exist in total.

    Deterministic: ties break by LOWEST index (``np.lexsort`` with the
    index as the secondary key), matching ``lax.top_k`` single-device
    semantics — property-tested against ``np.argsort`` in
    tests/test_topk_merge.py."""
    values = np.asarray(values)
    indices = np.asarray(indices)
    if values.shape != indices.shape:
        raise ValueError('values %r and indices %r must agree in shape'
                         % (values.shape, indices.shape))
    k = min(k, values.shape[-1])
    # primary key: value DESC; secondary: index ASC (lexsort's last key
    # is primary). -(-inf) = +inf sorts sentinels last.
    order = np.lexsort((indices, -values), axis=-1)[..., :k]
    return (np.take_along_axis(values, order, axis=-1),
            np.take_along_axis(indices, order, axis=-1))
