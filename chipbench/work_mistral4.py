"""The operations and bytes the serving steps of a latent-attention decoder
with a share of the experts need, from what each step carried
(``ServingEngine.lm_step_log()``) and the model's config.json.

What the algorithm requires, whatever implements it: padding rows, idle
decode rows, keys after a query and choices of experts not held count
nothing.  A multiply-add is two operations.  Bytes are the compulsory HBM
traffic of each kernel taken alone: the cached latents or weights it must
read once and the activations it must read and write (bfloat16, 2 bytes;
float32 outputs, 4 bytes).  ``H`` heads, latent width ``W = kv_lora +
rope``.

- ``latent_decode`` (the scope ``lm/latent_decode`` a layer: the absorbed
  query, the kernel, the latent output's up-projection): a decode row at
  position ``p`` reads its ``p + 1`` latents (``W`` values each) ONCE for
  all heads; ``2 H W`` operations a key for the scores and ``2 H kv_lora``
  for the weighted sum; the absorption ``2 H nope kv_lora`` and the
  up-projection ``2 H kv_lora v`` a row; ``W_kvb`` is read once a step.
  HBM-bound: the latents.
- ``latent_prefill`` (``lm/latent_prefill``: a chunk's expanded attention):
  the chunk's history of ``n`` positions (every position before it and
  its own) is read and up-projected once, ``2 n kv_lora H (nope + v)``;
  a query at ``p`` attends to ``p + 1`` keys with every head,
  ``2 H (nope + rope)`` for the scores and ``2 H v`` for the sum a key.
  Compute-bound.
- ``experts`` (``lm/experts``: the grouped products of the held experts):
  three products of ``hidden x width`` a choice that fell on a held
  expert; the weights of the held experts the step touched are read once.
- the rest of a step (the attention's projections, the router, the shared
  expert, the head over the rows whose logits are wanted) counts in the
  step's total, which ``lmlatent.step_mfu`` sets against the device time
  of the step programs.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

BF16, F32 = 2, 4
KERNELS = ('latent_decode', 'latent_prefill', 'experts')


def _sizes(config: dict):
    return (int(config['num_attention_heads']), int(config['kv_lora_rank']),
            int(config['qk_nope_head_dim']), int(config['qk_rope_head_dim']),
            int(config['v_head_dim']))


def decode_work(config: dict, positions: np.ndarray) -> Dict[str, float]:
    """One layer's absorbed attention for decode rows at ``positions``."""
    heads, kv_lora, nope, rope, v = _sizes(config)
    width = kv_lora + rope
    if not positions.size:
        return {'flops': 0.0, 'hbm_bytes': 0.0}
    keys = float((positions.astype(np.float64) + 1).sum())
    rows = float(positions.size)
    return {
        'flops': 2 * heads * (width + kv_lora) * keys
        + 2 * heads * kv_lora * (nope + v) * rows,
        'hbm_bytes': BF16 * width * keys
        + BF16 * kv_lora * heads * (nope + v)
        + rows * heads * (BF16 * (nope + rope) + F32 * v)}


def prefill_work(config: dict, first: int, taken: int) -> Dict[str, float]:
    """One layer's expanded attention for a chunk of ``taken`` queries at
    positions ``first ..`` of one sequence."""
    heads, kv_lora, nope, rope, v = _sizes(config)
    history = first + taken
    keys = float(taken) * first + taken * (taken + 1) / 2.0
    return {
        'flops': 2.0 * history * kv_lora * heads * (nope + v)
        + 2.0 * heads * (nope + rope + v) * keys,
        'hbm_bytes': BF16 * (kv_lora + rope) * history
        + BF16 * kv_lora * heads * (nope + v)
        + taken * heads * (BF16 * (nope + rope) + F32 * v)}


def experts_work(config: dict, choices: float, touched: float
                 ) -> Dict[str, float]:
    """The held experts' products for ``choices`` held choices that
    reached ``touched`` distinct experts (both summed over layers)."""
    h, width = int(config['hidden_size']), int(config['moe_intermediate_size'])
    return {'flops': 2.0 * 3 * h * width * choices,
            'hbm_bytes': BF16 * (touched * 3 * h * width
                                 + choices * (2 * h + 3 * width))}


def dense_flops(config: dict, tokens: int, outputs: int) -> float:
    """A step's products outside the three kernels, every layer and the
    head: the attention's projections, the router, the shared expert."""
    heads, kv_lora, nope, rope, v = _sizes(config)
    h = int(config['hidden_size'])
    q_lora = int(config['q_lora_rank'])
    shared = int(config['n_shared_experts']) \
        * int(config['moe_intermediate_size'])
    routed = int(config.get('n_routed_experts_published',
                            config['n_routed_experts']))
    layer = 2 * tokens * (h * (q_lora + kv_lora + rope)
                          + q_lora * heads * (nope + rope)
                          + heads * v * h + h * routed + 3 * h * shared)
    return float(int(config['num_hidden_layers']) * layer
                 + 2 * outputs * h * int(config['vocab_size']))


def _add(total: Dict[str, float], part: Dict[str, float],
         times: float = 1.0) -> None:
    for key, value in part.items():
        total[key] = total.get(key, 0.0) + times * value


def step_work(config: dict, step: dict) -> Dict[str, Dict[str, float]]:
    """{kernel: {'flops', 'hbm_bytes'}} of one logged step, every layer,
    and ``'step'``: {'flops'} of the whole step."""
    layers = int(config['num_hidden_layers'])
    work = {name: {'flops': 0.0, 'hbm_bytes': 0.0} for name in KERNELS}
    chunk = int(step['chunk_tokens'])
    decode = np.asarray(step['decode_positions'], np.int64)
    _add(work['latent_decode'], decode_work(config, decode), layers)
    if chunk:
        _add(work['latent_prefill'],
             prefill_work(config, int(step['chunk_first']), chunk), layers)
    _add(work['experts'], experts_work(
        config, float(step['held_choices']),
        float(np.asarray(step['experts_touched'], np.float64).sum())))
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
