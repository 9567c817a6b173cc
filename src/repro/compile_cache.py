"""Persistent JAX compilation cache for the command-line entry points.

:func:`enable` is called by the entry points (``python -m repro.noc``,
``python -m benchmarks.run``, ``chip_smoke.py``), never at library import:
a library user keeps control of JAX's configuration.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  configured.
* Unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (git-ignored). The path is fixed — never built from a temp name, a pid
  or the time — because a cache whose directory moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

#: in-checkout default (``src/repro/compile_cache.py`` -> ``<root>/.jax_cache``)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
