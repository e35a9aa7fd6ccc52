"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.learn``,
``repro.launch.serve``, ``benchmarks/run.py``) call ``configure()`` once,
before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing changes.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed — the cache directory is part of what a later run
  must find again, so it is never built from a temp name, a process id or
  the time.
"""

from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` — this file lives at ``<checkout>/src/repro/``.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
