"""The operations and bytes the serving steps of a linear-attention and
block-sparse decoder need, from what each step carried
(``ServingEngine.lm_step_log()``) and the model's config.json.

What the algorithm requires, whatever implements it: padding rows, blocks a
query did not choose and the masked part of a block count nothing.  A
multiply-add is two operations.  Bytes are the compulsory HBM traffic of
each kernel taken alone: the cached state it must read (and write) once and
the activations it must read and write (bfloat16, 2 bytes; a recurrent
state float32, 4 bytes).

- ``linear_prefill`` / ``linear_decode`` (a lightning layer, ``H`` heads of
  ``d``): the recurrence ``S = gamma S + k^T v``, ``o = q S`` is two
  products of ``d x d`` a token and head and the decay, ``5 d^2``
  operations; a sequence's state (``H d^2`` float32) is read and written
  once a step, whatever the number of its tokens; q, k, v in and o out.
- ``sparse_select`` (a sparse layer, stage 1): a query at position ``i``
  scores every pooled key it can see (``(i + 1) // stride - 1`` of them,
  ``kernel = 2 stride``) with every query head, ``2 d`` operations a score;
  a sequence's pooled rows (one of ``kv_heads x d`` every ``stride``
  positions) are read once a step.  A query within ``dense_len`` selects
  nothing.
- ``sparse_attention`` (stage 2): a query attends to the positions of its
  chosen blocks that are not after it: ``min(visible, topk)`` blocks, the
  last of them its own, filled to ``i mod block + 1``; ``4 d`` operations a
  key and query head.  A query within ``dense_len`` attends to all
  ``i + 1`` keys.  The keys and values of a sequence are read at most once
  a step: a decode row reads its chosen blocks, a chunk's queries between
  them at most the sequence.
- the rest of a step (the fused q/k/v/gate product, the output product, the
  feed-forward, the head over the rows whose logits are wanted) counts in
  the step's total, which ``lmhybrid.step_mfu`` sets against the device
  time of the step programs.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from chipbench.reference_minicpm_sala import SPARSE_CONFIG

BF16, F32 = 2, 4
KERNELS = ('linear_prefill', 'linear_decode', 'sparse_select',
           'sparse_attention')


def kinds(config: dict):
    """(lightning layers, sparse layers) that are run."""
    first = int(config.get('first_hidden_layer', 0))
    run = config['mixer_types'][first:first
                                + int(config['num_hidden_layers'])]
    return run.count('lightning-attn'), run.count('minicpm4')


def sparse_config(config: dict) -> dict:
    return dict(SPARSE_CONFIG, **config.get('sparse_config', {}))


def linear_work(config: dict, tokens: int, sequences: int
                ) -> Dict[str, float]:
    """One lightning layer over ``tokens`` tokens of ``sequences``
    sequences."""
    heads, d = int(config['lightning_nh']), int(config['lightning_head_dim'])
    return {
        'flops': float(5 * d * d * heads * tokens),
        'hbm_bytes': float(2 * F32 * heads * d * d * sequences
                           + 4 * BF16 * heads * d * tokens)}


def select_work(config: dict, positions: np.ndarray) -> Dict[str, float]:
    """One sparse layer's stage 1 for queries at ``positions`` of ONE
    sequence."""
    sparse = sparse_config(config)
    d, heads = int(config['head_dim']), int(config['num_attention_heads'])
    kv_heads = int(config['num_key_value_heads'])
    at = positions[positions + 1 > sparse['dense_len']].astype(np.float64)
    if not at.size:
        return {'flops': 0.0, 'hbm_bytes': 0.0}
    stride = sparse['kernel_stride']
    pooled = np.maximum((at + 1) // stride - sparse['kernel_size'] // stride
                        + 1, 0)
    rows = (at.max() + 1) // stride
    return {
        'flops': float(2 * d * heads * pooled.sum()),
        'hbm_bytes': float(BF16 * (kv_heads * d * rows
                                   + heads * d * at.size))}


def attention_keys(config: dict, positions: np.ndarray) -> np.ndarray:
    """Keys each query at ``positions`` attends to."""
    sparse = sparse_config(config)
    at = positions.astype(np.int64)
    block = sparse['block_size']
    chosen = np.minimum(at // block + 1, sparse['topk'])
    beyond = (chosen - 1) * block + at % block + 1
    return np.where(at + 1 > sparse['dense_len'], beyond, at + 1)


def attention_work(config: dict, positions: np.ndarray
                   ) -> Dict[str, float]:
    """One sparse layer's stage 2 (or dense branch) for queries at
    ``positions`` of ONE sequence."""
    d, heads = int(config['head_dim']), int(config['num_attention_heads'])
    kv_heads = int(config['num_key_value_heads'])
    if not positions.size:
        return {'flops': 0.0, 'hbm_bytes': 0.0}
    keys = attention_keys(config, positions).astype(np.float64)
    read = min(keys.sum(), float(positions.max()) + 1)
    return {
        'flops': float(4 * d * heads * keys.sum()),
        'hbm_bytes': float(BF16 * (2 * kv_heads * d * read
                                   + 2 * heads * d * positions.size))}


def dense_flops(config: dict, tokens: int, outputs: int) -> float:
    """A step's products outside the four kernels, every layer and the
    head."""
    h, ff = int(config['hidden_size']), int(config['intermediate_size'])
    lightning, sparse = kinds(config)
    wide_l = int(config['lightning_nh']) * int(config['lightning_head_dim'])
    wide = int(config['num_attention_heads']) * int(config['head_dim'])
    kv = int(config['num_key_value_heads']) * int(config['head_dim'])
    layer_l = 2 * tokens * h * (4 * wide_l + wide_l + 3 * ff)
    layer_s = 2 * tokens * h * (2 * wide + 2 * kv + wide + 3 * ff)
    return float(lightning * layer_l + sparse * layer_s
                 + 2 * outputs * h * int(config['vocab_size']))


def _add(total: Dict[str, float], part: Dict[str, float],
         times: float = 1.0) -> None:
    for key, value in part.items():
        total[key] = total.get(key, 0.0) + times * value


def step_work(config: dict, step: dict) -> Dict[str, Dict[str, float]]:
    """{kernel: {'flops', 'hbm_bytes'}} of one logged step, every layer of
    the kernel's kind, and ``'step'``: {'flops'} of the whole step."""
    lightning, sparse = kinds(config)
    work = {name: {'flops': 0.0, 'hbm_bytes': 0.0} for name in KERNELS}
    chunk = int(step['chunk_tokens'])
    decode = np.asarray(step['decode_positions'], np.int64)
    if decode.size:
        _add(work['linear_decode'],
             linear_work(config, decode.size, decode.size), lightning)
        for p in decode:
            one = np.asarray([p])
            _add(work['sparse_select'], select_work(config, one), sparse)
            _add(work['sparse_attention'], attention_work(config, one),
                 sparse)
    if chunk:
        at = int(step['chunk_first']) + np.arange(chunk)
        _add(work['linear_prefill'], linear_work(config, chunk, 1),
             lightning)
        _add(work['sparse_select'], select_work(config, at), sparse)
        _add(work['sparse_attention'], attention_work(config, at), sparse)
    outputs = decode.size + (1 if chunk else 0)
    work['step'] = {'flops': dense_flops(config, chunk + decode.size,
                                         outputs)
                    + sum(work[name]['flops'] for name in KERNELS)}
    return work


def total_work(config: dict, steps: Iterable[dict]
               ) -> Dict[str, Dict[str, float]]:
    total: Dict[str, Dict[str, float]] = {}
    for step in steps:
        for name, part in step_work(config, step).items():
            _add(total.setdefault(name, {}), part)
    return total
