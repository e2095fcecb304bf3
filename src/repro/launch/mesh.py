"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
Mesh construction goes through ``repro.compat.make_mesh``, which sets one
axis type for every axis.
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
