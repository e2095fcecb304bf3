"""Mesh and sharding helpers shared by the sharded paths and the models.

Written against the installed jax (0.9). Every mesh this repo builds goes
through ``make_mesh``, so the axis type of every mesh axis is set in one
place.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              kind: str = "auto", devices=None):
    """``jax.make_mesh`` with every axis of type ``kind`` ("auto" /
    "explicit" / "manual", a ``jax.sharding.AxisType`` member name)."""
    types = (getattr(jax.sharding.AxisType, kind.capitalize()),) \
        * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=types, devices=devices)


def current_mesh():
    """The physical mesh activated by ``with mesh:``, or None.

    This is the one private-API touchpoint (``thread_resources`` has no
    public accessor); isolating it here keeps the model code free of
    ``jax._src`` imports."""
    try:
        from jax._src import mesh as mesh_lib
        env_mesh = mesh_lib.thread_resources.env.physical_mesh
    except Exception:
        return None
    if env_mesh is None or getattr(env_mesh, "empty", True):
        return None
    return env_mesh


# ---------------------------------------------------------------------------
# sharding constructors (checkpoint restore & dry-run placement)
# ---------------------------------------------------------------------------


def named_sharding(mesh, *spec):
    """NamedSharding from PartitionSpec parts (or a ready PartitionSpec)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if len(spec) == 1 and isinstance(spec[0], PartitionSpec):
        return NamedSharding(mesh, spec[0])
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated_like(mesh, tree):
    """Pytree of fully-replicated NamedShardings matching ``tree``'s leaves
    (the reshard-on-restore default when no explicit shardings are given)."""
    sh = named_sharding(mesh)
    return jax.tree.map(lambda _: sh, tree)
