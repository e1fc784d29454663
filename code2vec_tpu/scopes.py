"""Names for the parts of the step programs.

A profile's device events carry the compiled program's instruction names
(``fusion.28``), which the compiler renumbers whenever a shape or a pass
changes. The program's text keeps, for every instruction, the name stack it
was traced under (``op_name="jit(train_step)/jvp(c2v_encode)/..."``), so a
part that is wrapped in a ``jax.named_scope`` where it is written can be
found by name in every later trace: the trainer writes the text beside each
capture (``telemetry/trace.py::ProgramLegend``), and
``chipbench/reduce/step_scopes.py`` joins the two. A scope is metadata: the
lowered program is the same with and without it
(``tests/test_step_scopes.py``), and JAX's persistent-cache key ignores it.

==================  ====================================================
``c2v_encode``      the encoder: gather, transform, attention, weighted
                    sum; in the train step also its recompute and the
                    gradients of ``transform`` / ``attention``
``c2v_table_grad``  the token and path tables' gradients (inside the
                    encoder's backward: the innermost scope names the part)
``c2v_logits``      ``compute_logits`` and both of its backward products
``c2v_ce``          the cross-entropy (the fused kernel too), its backward
``c2v_adam``        ``optimizer.update`` + ``apply_updates`` and the casts
                    that feed them
``c2v_topk``        ``take_top_k`` of the eval and predict programs
==================  ====================================================

Autodiff wraps the names it derives: ``jvp(c2v_encode)`` is the forward,
``transpose(jvp(c2v_encode))`` what the backward derives from it, so a
reader looks for the name inside those wrappers and not for a whole path
component. A ``jax.custom_vjp``'s backward gets no name from autodiff and
opens its scope itself (``ops/pallas_ragged.py``).
"""
from __future__ import annotations

import functools

import jax

SCOPES = ('c2v_encode', 'c2v_table_grad', 'c2v_logits', 'c2v_ce',
          'c2v_adam', 'c2v_topk')


def scoped(name: str):
    """Decorator: every call of the function runs under
    ``jax.named_scope(name)``. A context of its own a call, where the
    decorator form of ``jax.named_scope`` shares one between the threads
    that trace the function."""
    if name not in SCOPES:
        raise ValueError('%r is not in the scope catalog %s'
                         % (name, SCOPES))

    def wrap(fn):
        @functools.wraps(fn)
        def in_scope(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return in_scope
    return wrap
