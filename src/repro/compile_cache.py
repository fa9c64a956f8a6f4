"""JAX persistent compilation cache for the command-line entry points.

A cold run compiles one engine executable per batch bucket; the persistent
cache lets the next process on the same machine load them instead.  Only
entry points call :func:`enable` (``chip_smoke.py``, ``benchmarks/run.py``
and the ``__main__`` of ``dse``, ``search``, ``sim_service`` and
``telemetry``); importing the library never touches JAX's configuration.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing.
* unset: the cache goes to the fixed ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is part of every cache key, so it is never
  derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
