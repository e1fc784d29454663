"""``work.train_step``'s operations and bytes, split by part of the step.

No new count. Every term here is a term of ``chipbench/work.py::
train_step``, taken from the same functions and constants, and the four
parts sum to its ``flops`` and ``hbm_bytes`` exactly
(``chipbench/tests/test_step_scopes.py``): whatever a later PR corrects in
``work.py`` it corrects here by the same edit, or that test fails. A part's
floor is ``work.least_seconds`` of its share.

- ``encode``: 3 x the encode forward's FLOPs; the index stream and the
  gathered rows.
- ``table_grad``: the scatter's FLOPs; the scatter-add's read and write, and
  the token and path tables' dense float32 gradient write.
- ``logits_ce``: 3 x the logits product + 2 x CE; the target table read by
  both products, and the target and dense parameters' gradient write.
- ``adam``: 12 FLOPs and ``3 F32 + 2 mu + 2 nu`` bytes a parameter.

``collective_bytes`` belongs to no part: ``mesh.collective_ms_per_step``
times the collectives, and a part's ``ici`` bound is 0.

Shapes come from the legend beside a capture (the ``.json`` the trainer
writes with each program's text: the abstract parameters and optimizer
state), because a reader's ``run`` carries neither the model nor the padded
table rows.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from chipbench import work
from chipbench.work import F32, Shapes

PARTS = ('encode', 'table_grad', 'logits_ce', 'adam')
_TABLES = ('token_embedding', 'path_embedding', 'target_embedding')


def shapes_of(about: dict) -> Optional[Shapes]:
    """``work.Shapes`` from a legend's ``.json``; None where it does not
    name the three tables and their moments."""
    def leaf(tree: str, *words: str) -> Optional[dict]:
        for path, found in about.get(tree, {}).items():
            if all(re.search(r'\b%s\b' % word, path) for word in words):
                return found
        return None

    tables = [leaf('params', name) for name in _TABLES]
    mu = leaf('opt_state', 'mu', _TABLES[0])
    nu = leaf('opt_state', 'nu', _TABLES[0])
    if None in tables or mu is None or nu is None:
        return None
    token, path, target = (table['shape'] for table in tables)
    return Shapes(token_rows=token[0], path_rows=path[0],
                  target_rows=target[0], token_dim=token[1],
                  path_dim=path[1], code_dim=target[1],
                  mu_bytes=work.DTYPE_BYTES[mu['dtype']],
                  nu_bytes=work.DTYPE_BYTES[nu['dtype']])


def train_step_parts(s: Shapes, examples: int, contexts: float,
                     chips: int = 1) -> Dict[str, Dict[str, float]]:
    """{part: {'flops', 'hbm_bytes', 'collective_bytes'}} of one optimizer
    step as one chip sees it; the arguments are ``work.train_step``'s."""
    del chips   # the parts' work is a chip's own; the collective is none's
    logits_forward = float(examples * 2 * s.code_dim * s.target_rows)
    encode_forward = work._forward_flops(s, examples, contexts) \
        - logits_forward
    cross_entropy = 3.0 * examples * s.target_rows
    row_bytes = s.context_dim * F32
    table_parameters = (s.token_rows * s.token_dim
                        + s.path_rows * s.path_dim)
    parts = {
        'encode': (3.0 * encode_forward,
                   contexts * 12 + contexts * row_bytes),
        'table_grad': (float(contexts * s.context_dim),
                       contexts * row_bytes * 2 + table_parameters * F32),
        'logits_ce': (3.0 * logits_forward + 2.0 * cross_entropy,
                      2 * s.target_rows * s.code_dim * F32
                      + (s.parameters - table_parameters) * F32),
        'adam': (12.0 * s.parameters,
                 s.parameters * (3 * F32 + 2 * s.mu_bytes
                                 + 2 * s.nu_bytes)),
    }
    return {part: {'flops': float(flops), 'hbm_bytes': float(hbm),
                   'collective_bytes': 0.0}
            for part, (flops, hbm) in parts.items()}
