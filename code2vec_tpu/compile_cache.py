"""Where JAX keeps compiled programs between processes.

The serving ladder alone is 69 programs at default settings
(serving/engine.py ``warmup``), each with its own Mosaic compile, so every
entry point that compiles calls :func:`configure` before its first
compile: ``cli.main``, ``benchlib``'s trainer builders, ``chip_smoke.py``,
the serving-mesh worker main and ``scripts/mesh_worker.py``.

The directory is part of the cache key, so it must not move:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing in code — the place is chosen from outside;
- unset: one fixed directory inside the checkout (git-ignored), never a
  temp name, pid or timestamp.
"""
from __future__ import annotations

import os

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'

#: the in-checkout default, ``<repo>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory. Idempotent; touches no
    backend."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax
    jax.config.update('jax_compilation_cache_dir', DEFAULT_DIR)
    return DEFAULT_DIR
