"""Host-device transfers on the DSE path, counted.

``fetch`` is the per-array ``np.asarray(x, dtype)`` the DSE path already
did at each site (characterize, expand, scoring, replay), plus one
increment of the always-on ``device.fetches`` counter, so every
``repro.obs`` span reports how many arrays its body brought to the host
(its ``fetches`` arg). ``put`` is the other direction: one host array sent
to the device (the encoded config list), counted on ``device.puts`` (the
spans' ``puts`` arg). Importing this module also registers the compile
listener (``repro.compile_cache.count_compiles``) that feeds the spans'
``compiles`` arg.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs

_C_FETCHES = obs.counter("device.fetches")
_C_PUTS = obs.counter("device.puts")

compile_cache.count_compiles()


def fetch(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counted as one device fetch."""
    _C_FETCHES.inc()
    return np.asarray(x, dtype)


def put(x):
    """``jnp.asarray(x)`` of a host array, counted as one device put."""
    _C_PUTS.inc()
    return jnp.asarray(x)
