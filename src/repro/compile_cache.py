"""Where JAX keeps its persistent compilation cache for this program, and
how many programs the process has compiled or loaded from it.

``enable()`` is called by the program's entry points (``chip_smoke.py`` and
the benchmarks), never on ``import repro``: importing the package leaves
JAX's configuration alone. ``count_compiles()`` registers the one
``jax.monitoring`` listener that feeds the ``jax.compiles`` counter every
``repro.obs`` span diffs; the modules that fetch device results call it on
import, and it changes no configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

# a program compiled, or loaded from the persistent cache: the two events
# a benchmark counts as compilations
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

_counting = False

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


def count_compiles() -> None:
    """Register, once per process, the ``jax.monitoring`` listener that
    counts every compilation (or persistent-cache load) of any jit on the
    ``jax.compiles`` counter."""
    global _counting
    if _counting:
        return
    _counting = True
    import jax

    from repro import obs
    compiles = obs.counter("jax.compiles")

    def _on(name, secs, **kw):
        if name in COMPILE_EVENTS:
            compiles.inc()

    jax.monitoring.register_event_duration_secs_listener(_on)
