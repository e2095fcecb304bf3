"""Host-device transfers on the DSE path, counted.

``fetch`` is the per-array ``np.asarray(x, dtype)`` the DSE path does at
the sites that need one array (scoring, replay); ``fetch_tree`` brings a
whole pytree of same-dtype device arrays (a characterization's metric
columns) to the host in one transfer. Each call of either is one
increment of the always-on ``device.fetches`` counter, so every
``repro.obs`` span reports how many host transfers its body made (its
``fetches`` arg), however many arrays each carried. ``put`` is the other
direction: one host array sent to the device (the encoded config list),
counted on ``device.puts`` (the spans' ``puts`` arg). Importing this
module also registers the compile listener
(``repro.compile_cache.count_compiles``) that feeds the spans'
``compiles`` arg.
"""
from __future__ import annotations

import math

import jax
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


# ravel + concatenate: moves bits, computes nothing, so every leaf comes
# back bit-identical to its own np.asarray; one program per leaf-shape list
_join = jax.jit(lambda leaves: jnp.concatenate([jnp.ravel(x)
                                                for x in leaves]))


def fetch_tree(tree):
    """The pytree ``tree`` of same-dtype device arrays as numpy arrays,
    brought to the host in one transfer (one jitted join of the raveled
    leaves, one ``np.asarray``, a host split by the leaves' shapes),
    counted as one device fetch. Mixed dtypes raise ``TypeError``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dtypes = sorted({str(x.dtype) for x in leaves})
    if len(dtypes) > 1:
        raise TypeError(f"fetch_tree joins leaves of one dtype, got "
                        f"{dtypes}")
    _C_FETCHES.inc()
    flat = np.asarray(_join(leaves))
    ends = np.cumsum([math.prod(x.shape) for x in leaves])
    parts = np.split(flat, ends[:-1])
    return jax.tree_util.tree_unflatten(
        treedef, [p.reshape(x.shape) for p, x in zip(parts, leaves)])


def put(x):
    """``jnp.asarray(x)`` of a host array, counted as one device put."""
    _C_PUTS.inc()
    return jnp.asarray(x)
