"""Device-parallel evaluation of batched grids (1D and 2D sharding).

``shard_leading`` runs a batched pure function with its first argument's
leading axis split across every visible device via ``repro.compat.make_mesh``
+ ``jax.shard_map``; remaining arguments are replicated. The grid is
padded to a device-count multiple and un-padded on the way out, so callers
never see the device count. On a 1-device host it degrades to a plain call —
the result is bit-identical either way (same kernel, same math, only the
placement differs), which is what lets the hetero composition tests assert
sharded == single-device.

``shard2d`` generalizes this to a 2D device mesh for doubly-batched work
(e.g. compositions × operating corners): the first argument's leading axis
shards over one mesh axis and the second argument's over the other, with the
device count factorized between them. Same contract: padded in, un-padded
out, bit-identical to the unsharded call.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.compat import make_mesh

GRID_AXIS = "grid"
CORNER_AXIS = "corner"

# multi-device dispatches (repro.obs registry); single-device calls take the
# plain-call fast path and are deliberately not counted as "sharded"
_C_SHARD = obs.counter("parallel.shard_calls")


def pad_to_multiple(x, multiple: int):
    """Pad ``x``'s leading axis up to a multiple of ``multiple`` by repeating
    its first row (values are discarded by the caller's un-pad slice).

    Returns ``(padded, original_length)``."""
    n = x.shape[0]
    if multiple <= 1 or n % multiple == 0:
        return x, n
    pad = multiple - n % multiple
    fill = jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])
    return jnp.concatenate([x, fill], axis=0), n


def shard_leading(fn, x, *rest, devices: Optional[Sequence] = None,
                  axis_name: str = GRID_AXIS):
    """Evaluate ``fn(x, *rest)`` with ``x``'s leading axis sharded.

    ``fn``     pure, shape-polymorphic over the leading axis of ``x``; every
               output leaf must carry that leading axis.
    ``x``      the grid array, shape ``(J, ...)``.
    ``rest``   broadcast (replicated) arguments — arrays or pytrees.
    ``devices`` defaults to ``jax.devices()``; with one device the call is a
               plain ``fn(x, *rest)``.

    Returns ``fn``'s output with every leaf un-padded back to length ``J``.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n_dev = len(devs)
    if n_dev <= 1:
        return fn(x, *rest)
    with obs.span("parallel.shard", mesh="1d", n_dev=n_dev):
        _C_SHARD.inc()
        mesh = make_mesh((n_dev,), (axis_name,), devices=devs)
        xp, n = pad_to_multiple(jnp.asarray(x), n_dev)
        sharded = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(axis_name),) + (P(),) * len(rest),
            out_specs=P(axis_name), check_vma=False)
        out = sharded(xp, *rest)
        return jax.tree.map(lambda leaf: leaf[:n], out)


def _factor_devices(n_dev: int, minor_n: int) -> Tuple[int, int]:
    """Split ``n_dev`` into ``(major_ways, minor_ways)``: the minor axis gets
    the largest divisor of ``n_dev`` not exceeding its extent ``minor_n`` (no
    point cutting a 2-corner axis 8 ways), the major axis the rest."""
    minor_ways = max(d for d in range(1, n_dev + 1)
                     if n_dev % d == 0 and d <= max(minor_n, 1))
    return n_dev // minor_ways, minor_ways


def shard2d(fn, x, y, *rest, devices: Optional[Sequence] = None,
            axis_names: Tuple[str, str] = (GRID_AXIS, CORNER_AXIS)):
    """Evaluate ``fn(x, y, *rest)`` on a 2D device mesh.

    ``fn``     pure; shape-polymorphic over the leading axis of every ``x``
               leaf and of every ``y`` leaf; every output leaf must carry
               ``(y_leading, x_leading)`` as its first two axes.
    ``x``      array or pytree whose leaves share leading extent ``J`` —
               sharded over mesh axis ``axis_names[0]``.
    ``y``      array or pytree whose leaves share leading extent ``C`` —
               sharded over mesh axis ``axis_names[1]``.
    ``rest``   broadcast (replicated) arguments.
    ``devices`` defaults to ``jax.devices()``; the device count factorizes
               across the two axes (minor ``y`` axis first, capped at ``C``);
               with one device the call is a plain ``fn(x, y, *rest)``.

    Both leading axes are padded to mesh-shape multiples and un-padded on the
    way out, so results are bit-identical to the unsharded call.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n_dev = len(devs)
    if n_dev <= 1:
        return fn(x, y, *rest)
    with obs.span("parallel.shard", mesh="2d", n_dev=n_dev):
        _C_SHARD.inc()
        n_x = jax.tree.leaves(x)[0].shape[0]
        n_y = jax.tree.leaves(y)[0].shape[0]
        ways_x, ways_y = _factor_devices(n_dev, n_y)
        ax_x, ax_y = axis_names
        mesh = make_mesh((ways_x, ways_y), (ax_x, ax_y), devices=devs)
        xp = jax.tree.map(
            lambda leaf: pad_to_multiple(jnp.asarray(leaf), ways_x)[0], x)
        yp = jax.tree.map(
            lambda leaf: pad_to_multiple(jnp.asarray(leaf), ways_y)[0], y)
        sharded = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(ax_x), P(ax_y)) + (P(),) * len(rest),
            out_specs=P(ax_y, ax_x), check_vma=False)
        out = sharded(xp, yp, *rest)
        return jax.tree.map(lambda leaf: leaf[:n_y, :n_x], out)
