"""The operations and bytes a decoder's serving steps need, from what each
step carried (``ServingEngine.lm_step_log()``) and the model's config.json.

What the algorithm requires, not what a kernel does: padding rows, keys
outside a window and the masked half of a causal block count nothing.  A
multiply-add is two operations.  Bytes are the compulsory HBM traffic of
each kernel taken alone: the weights or cached keys it must read once and
the activations it must read and write (bfloat16, 2 bytes).

- attention of one query at position ``p``: keys ``p + 1`` in a full layer,
  ``min(p + 1, window)`` in a sliding one; ``4 x head_dim x query heads``
  operations a key (scores and the weighted sum).  The keys and values of a
  sequence are read once a step for all of its queries (a chunk's queries
  share them): a chunk at ``first .. first + n`` reads ``first + n`` keys
  in a full layer, ``min(first, window - 1) + n`` in a sliding one.
- the expert layer: ``top_k`` experts a token, three products of
  ``hidden x width`` each; the weights of the experts the step actually
  touched (``experts_touched``) are read once.
- the rest of a step (the q/k/v and output projections, the router, the
  head over the rows whose logits are wanted) counts in the step's total,
  which ``lm.step_mfu`` sets against the device time of the step programs.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

BF16 = 2
KERNELS = ('window_attention', 'full_attention', 'decode_attention',
           'experts')


def _kinds(config: dict):
    layers = int(config['num_hidden_layers'])
    kinds = config['layer_types'][:layers]
    return kinds.count('sliding_attention'), kinds.count('full_attention')


def attention_work(config: dict, positions: np.ndarray, kv_read: int,
                   window: bool) -> Dict[str, float]:
    """One layer's attention over queries at ``positions`` of ONE
    sequence, ``kv_read`` of whose keys the step reads."""
    d, heads = int(config['head_dim']), int(config['num_attention_heads'])
    kv_heads = int(config['num_key_value_heads'])
    keys = positions.astype(np.float64) + 1
    if window:
        keys = np.minimum(keys, float(config['sliding_window']))
    queries = positions.shape[0]
    return {
        'flops': float(4 * d * heads * keys.sum()),
        'hbm_bytes': float(BF16 * (2 * kv_heads * d * kv_read
                                   + 2 * queries * heads * d)),
    }


def experts_work(config: dict, tokens: int, touched: float
                 ) -> Dict[str, float]:
    """One layer's expert products for ``tokens`` tokens that reached
    ``touched`` distinct experts."""
    h, width = int(config['hidden_size']), int(config['moe_intermediate_size'])
    top_k = int(config['num_experts_per_tok'])
    rows = tokens * top_k
    return {
        'flops': float(2 * 3 * h * width * rows),
        'hbm_bytes': float(BF16 * (touched * 3 * h * width
                                   + rows * (2 * h + 3 * width))),
    }


def dense_flops(config: dict, tokens: int, outputs: int) -> float:
    """A step's products outside the two kernels, every layer and the
    head: q/k/v, the output projection, the router, the logits."""
    h, d = int(config['hidden_size']), int(config['head_dim'])
    heads, kv_heads = (int(config['num_attention_heads']),
                       int(config['num_key_value_heads']))
    layer = 2 * tokens * h * (2 * heads * d + 2 * kv_heads * d
                              + int(config['num_experts']))
    return float(int(config['num_hidden_layers']) * layer
                 + 2 * outputs * h * int(config['vocab_size']))


def _add(total: Dict[str, float], part: Dict[str, float],
         times: float = 1.0) -> None:
    for key, value in part.items():
        total[key] = total.get(key, 0.0) + times * value


def step_work(config: dict, step: dict) -> Dict[str, Dict[str, float]]:
    """{kernel: {'flops', 'hbm_bytes'}} of one logged step, every layer of
    the kernel's kind, and ``'step'``: {'flops'} of the whole step.  A
    step with a chunk charges its attention (decode rows included: they
    ride the same call) to ``window_attention``/``full_attention``; one
    without to ``decode_attention``."""
    sliding, full = _kinds(config)
    window = int(config['sliding_window'])
    work = {name: {'flops': 0.0, 'hbm_bytes': 0.0} for name in KERNELS}
    chunk = int(step['chunk_tokens'])
    decode = np.asarray(step['decode_positions'], np.int64)
    names = (('window_attention', 'full_attention') if chunk
             else ('decode_attention', 'decode_attention'))
    for p in decode:
        one = np.asarray([p])
        _add(work[names[0]], attention_work(
            config, one, min(int(p) + 1, window), True), sliding)
        _add(work[names[1]], attention_work(
            config, one, int(p) + 1, False), full)
    if chunk:
        first = int(step['chunk_first'])
        at = first + np.arange(chunk)
        _add(work['window_attention'], attention_work(
            config, at, min(first, window - 1) + chunk, True), sliding)
        _add(work['full_attention'], attention_work(
            config, at, first + chunk, False), full)
    tokens = chunk + decode.shape[0]
    for touched in np.asarray(step['experts_touched'], np.float64):
        _add(work['experts'], experts_work(config, tokens, touched))
    outputs = decode.shape[0] + (1 if chunk else 0)
    work['step'] = {'flops': dense_flops(config, tokens, outputs)
                    + sum(work[name]['flops'] for name in KERNELS)}
    return work


def total_work(config: dict, steps: Iterable[dict]
               ) -> Dict[str, Dict[str, float]]:
    total: Dict[str, Dict[str, float]] = {}
    for step in steps:
        for name, part in step_work(config, step).items():
            _add(total.setdefault(name, {}), part)
    return total


def least_seconds(work: Dict[str, float], peaks: dict) -> Dict[str, object]:
    """The least time a chip with these peaks could take over ``work``, and
    which of its limits sets it."""
    bounds = {'compute': work['flops'] / peaks['flops_per_s_bf16'],
              'hbm': work['hbm_bytes'] / peaks['hbm_bytes_per_s']}
    bound = max(bounds, key=bounds.get)
    return {'seconds': bounds[bound], 'bound': bound, 'bounds': bounds}
