"""Where compiled programs persist between runs.

JAX keeps compiled executables in a persistent cache once it is given a
directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here sets another directory.  Otherwise the entry points
(``launch.serve``, ``launch.train``, ``launch.tune``, ``chip_smoke.py``) call
:func:`enable` and the cache lives at one fixed path inside the checkout,
``<repo>/.cache/jax`` (gitignored).  The path is fixed because it is part of
what a later run must find again: a temp name, pid or time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/launch/compile_cache.py -> <repo>/.cache
CACHE_ROOT = Path(__file__).resolve().parents[3] / ".cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``CACHE_ROOT/jax``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CACHE_ROOT / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
