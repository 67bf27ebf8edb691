"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable` once, before their first compile.  Compiled programs are
then found again by the next process that compiles the same program
with the same directory:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
  other directory is set in code;
* otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed — never built from a temp name, a
  pid or the time — because it is part of what a later run must match.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/compile_cache.py``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
