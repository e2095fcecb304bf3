"""Where JAX keeps its persistent compilation cache for this program.

``enable()`` is called by the program's entry points (``chip_smoke.py`` and
the benchmarks), never on ``import repro``: importing the package leaves
JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout: the directory is part of what a later run must
# find again, so it never depends on the time, the pid or a temp name
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that variable
    itself, so nothing is set here and that directory is returned.
    Otherwise the cache goes to ``DEFAULT_DIR`` (``.jax_cache/`` at the root
    of the checkout).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
