"""The operations and bytes code2vec needs, from shapes alone.

Not what a compiler emits: what the algorithm requires, so that a custom
call counts what it computes and a recomputation counts nothing. The
reference is the model of ``reference.py`` trained with dense Adam, as the
configuration states it. A multiply-add is two operations.

FLOPs count the matrix products and, at one operation each, the
elementwise work that scales with a large shape. Bytes are the compulsory
HBM traffic: each parameter, moment and gradient byte the step must read or
write once, the embedding rows gathered and scattered, the target table read
by each product that uses it, and the packed index stream. Activations and
logits are not compulsory, because a fused implementation keeps them on the
chip; that makes the bound a floor, and a share of it honest but low.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

F32 = 4
DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}


class Shapes(NamedTuple):
    """What a step's cost depends on. Vocabulary sizes are the allocated
    table rows (the program pads them to a multiple of 128)."""
    token_rows: int
    path_rows: int
    target_rows: int
    token_dim: int
    path_dim: int
    code_dim: int
    mu_bytes: int = 2
    nu_bytes: int = 2

    @property
    def context_dim(self) -> int:
        return 2 * self.token_dim + self.path_dim

    @property
    def parameters(self) -> int:
        return (self.token_rows * self.token_dim
                + self.path_rows * self.path_dim
                + self.target_rows * self.code_dim
                + self.context_dim * self.code_dim + self.code_dim)


def shapes_from(sizes: Dict[str, int], config) -> Shapes:
    """From the backend's ``sizes`` (allocated rows) and its ``Config``."""
    return Shapes(
        token_rows=sizes['token_vocab_size'],
        path_rows=sizes['path_vocab_size'],
        target_rows=sizes['target_vocab_size'],
        token_dim=sizes['token_dim'], path_dim=sizes['path_dim'],
        code_dim=sizes['code_dim'],
        mu_bytes=DTYPE_BYTES[config.ADAM_MU_DTYPE],
        nu_bytes=DTYPE_BYTES[config.ADAM_NU_DTYPE])


def _forward_flops(s: Shapes, examples: int, contexts: float) -> float:
    encode = contexts * (
        2 * s.context_dim * s.code_dim    # tanh(W c)
        + s.code_dim                      # tanh
        + 2 * s.code_dim                  # attention score
        + 3                               # softmax over the contexts
        + 2 * s.code_dim)                 # weighted sum
    logits = examples * 2 * s.code_dim * s.target_rows
    return float(encode + logits)


def train_step(s: Shapes, examples: int, contexts: float,
               chips: int = 1) -> Dict[str, float]:
    """One optimizer step, as one chip sees it: ``examples`` methods
    holding ``contexts`` valid contexts in all on that chip. Forward,
    backward (twice the forward's products), cross-entropy, the
    scatter-adds, the Adam walk; on ``chips`` > 1 (data parallel, state
    mirrored) also the dense gradient's all-reduce, of which a ring sends
    and receives 2 (n - 1) / n of the gradient on each chip."""
    forward = _forward_flops(s, examples, contexts)
    cross_entropy = 3.0 * examples * s.target_rows
    scatter = float(contexts * s.context_dim)
    adam = 12.0 * s.parameters
    flops = 3.0 * forward + 2.0 * cross_entropy + scatter + adam
    row_bytes = s.context_dim * F32
    hbm = (
        contexts * 12                       # the (source, path, target) stream
        + contexts * row_bytes              # gather the rows
        + contexts * row_bytes * 2          # scatter-add: read and write
        + 2 * s.target_rows * s.code_dim * F32     # logits product, fwd + bwd
        + s.parameters * F32                # write the dense gradient
        # Adam: read parameter, gradient and both moments; write parameter
        # and both moments
        + s.parameters * (3 * F32 + 2 * s.mu_bytes + 2 * s.nu_bytes))
    return {'flops': flops, 'hbm_bytes': float(hbm),
            'collective_bytes':
                2.0 * (chips - 1) / chips * s.parameters * F32}


def least_seconds(work: Dict[str, float], peaks: dict) -> Dict[str, object]:
    """The least time a chip with these peaks could take, and which of its
    limits sets it."""
    bounds = {
        'compute': work['flops'] / peaks['flops_per_s_bf16'],
        'hbm': work['hbm_bytes'] / peaks['hbm_bytes_per_s'],
        'ici': work['collective_bytes'] / peaks['ici_bytes_per_s'],
    }
    bound = max(bounds, key=bounds.get)
    return {'seconds': bounds[bound], 'bound': bound, 'bounds': bounds}
