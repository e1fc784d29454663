"""Shared plumbing for the Pallas TPU kernel modules.

One definition of where a kernel may run, used by all three kernels
(``ops/pallas_encode.py``, ``ops/pallas_ce.py``, ``ops/pallas_ragged.py``)
so the routing discipline cannot drift between them:

- kernel-or-twin is decided ONCE by the caller that owns the mesh
  (``training/trainer.py``), from the platform of the mesh's devices
  (``parallel/mesh.py::mesh_platform``); a failed backend init
  propagates, it never selects the twin;
- a kernel that is asked for runs compiled (Mosaic) or not at all: off a
  TPU it raises :class:`KernelRequiresTPU`. The Pallas interpreter is
  something only a test (or a labelled CPU rehearsal) turns on, per call
  with ``interpret=True`` or for a block with :func:`interpret_kernels`.
"""
from __future__ import annotations

import contextlib
import contextvars

from code2vec_tpu.parallel.mesh import mesh_platform

_INTERPRET = contextvars.ContextVar('c2v_pallas_interpret', default=False)


class KernelRequiresTPU(RuntimeError):
    """A Pallas kernel was forced on where no TPU runs it."""


@contextlib.contextmanager
def interpret_kernels():
    """Tests and labelled CPU rehearsals only: every Pallas kernel TRACED
    inside the block runs in the interpreter (jit caches do not key on
    this, so enter it before a program's first call)."""
    token = _INTERPRET.set(True)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def resolve_interpret(interpret: bool, what: str, mesh=None) -> bool:
    """The ``interpret`` flag a ``pallas_call`` gets: True only where a
    test asked for it; otherwise False, after checking that the program
    runs on a TPU."""
    if interpret or _INTERPRET.get():
        return True
    platform = mesh_platform(mesh)
    if platform != 'tpu':
        raise KernelRequiresTPU(
            '%s is a Pallas TPU kernel and the devices are %r: it runs '
            'compiled on a TPU or, in tests, under interpret=True / '
            'interpret_kernels() — it does not fall back.'
            % (what, platform))
    return False
