"""Batched system-level scoring of memory compositions (pure jnp).

One *composition* assigns a DesignTable row to every (level, bucket) slot of
a task. This module prices whole compositions: the chosen macro is tiled to
the slot's capacity share, and per-composition system metrics are reduced
over the slots —

``area_um2``        Σ tiles · macro area                          [µm²]
``p_static_w``      Σ tiles · (leakage + refresh) power           [W]
``p_dyn_w``         Σ read energy · required read frequency       [W]
``p_w``             p_static_w + p_dyn_w                          [W]
``bw_margin``       min over slots of f_op / f_required           [ratio]
``capacity_bits``   Σ tiles · macro bits                          [bits]
``overprovision``   capacity_bits / Σ required bits               [ratio]

Everything is a gather + reduction over a ``(J, S)`` index matrix (J
compositions × S slots), evaluated in ONE jit so a multi-thousand-row
composition grid costs a single device dispatch. The same kernel runs
sharded over the grid axis via ``repro.parallel.grid.shard_leading`` when
``sharded=True`` — results are identical, only placement changes.

Slots carrying the infeasible sentinel (``config_idx < 0``) price at +inf
area/power so they sort last and are flagged infeasible by the caller.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import backend
from repro.parallel.grid import shard2d, shard_leading

# DesignTable metric columns the scorer gathers from
METRIC_COLS = ("area_um2", "bits", "p_leak_w", "p_refresh_w", "e_read_j",
               "f_op_hz")

# output metric names, in the order score_kernel returns them
SYSTEM_METRICS = ("area_um2", "p_static_w", "p_dyn_w", "p_w", "bw_margin",
                  "capacity_bits", "overprovision")


@dataclass(frozen=True)
class SystemBudget:
    """Chip-level envelopes applied to WHOLE compositions.

    Unlike per-slot caps, these constrain the reduced system metrics the
    scorer returns: ``area_um2`` is the total system area ceiling [µm²],
    ``power_w`` the total (static + dynamic) power ceiling [W], and
    ``bw_margin_min`` the minimum acceptable bandwidth margin (min over
    slots of f_op / f_required, a ratio — 1.0 means every slot must at
    least meet its required read frequency). ``None`` disables a rail.

    Compositions violating any active rail are marked infeasible and sort
    after every feasible one; each active rail pins its per-slot
    extremal row into the candidate grid (argmin area / argmin power /
    argmax f_op) so ``n_feasible == 0`` on an untruncated grid proves the
    budget is genuinely unmeetable rather than a cap artifact.
    """
    area_um2: Optional[float] = None
    power_w: Optional[float] = None
    bw_margin_min: Optional[float] = None

    @property
    def active(self) -> bool:
        return (self.area_um2 is not None or self.power_w is not None
                or self.bw_margin_min is not None)

    def ensure_orders(self) -> Tuple[str, ...]:
        """Candidate-pin keys for the active rails (see
        ``repro.hetero.candidates.bucket_candidates``)."""
        return tuple(k for k, v in (("area", self.area_um2),
                                    ("power", self.power_w),
                                    ("bandwidth", self.bw_margin_min))
                     if v is not None)

    def feasible(self, scores: Mapping[str, np.ndarray]) -> np.ndarray:
        """Boolean mask over scored compositions passing every active rail
        (``scores`` keyed by SYSTEM_METRICS, each ``(J,)``)."""
        mask = np.ones(np.asarray(scores["area_um2"]).shape[0], bool)
        if self.area_um2 is not None:
            mask &= np.asarray(scores["area_um2"]) <= self.area_um2
        if self.power_w is not None:
            mask &= np.asarray(scores["p_w"]) <= self.power_w
        if self.bw_margin_min is not None:
            mask &= np.asarray(scores["bw_margin"]) >= self.bw_margin_min
        return mask


# how many batched composition evaluations this process has run (a compose()
# cache hit leaves the counter unchanged — tests use it the same way they use
# api.characterize_call_count for the DesignTable cache); lives on the
# repro.obs metrics registry, read through the thin alias below
_C_EVALS = obs.counter("hetero.compose_evals")


def composition_eval_count() -> int:
    """Number of batched composition scoring sweeps executed so far
    (backed by the ``hetero.compose_evals`` obs counter)."""
    return _C_EVALS.value


def slot_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the trailing slot axis, one slot at a time in slot order
    (jnp or numpy arrays alike).

    A ``jnp.sum`` reduction may be reassociated, and on TPU its order
    follows the array's layout, which depends on how many compositions are
    in the call: the same composition then prices differently in a sharded,
    batched or single-composition call. A left fold of elementwise adds
    fixes the order, so every such call agrees bit for bit."""
    acc = x[..., 0]
    for s in range(1, x.shape[-1]):
        acc = acc + x[..., s]
    return acc


def score_kernel(idx: jnp.ndarray, cols: Dict[str, jnp.ndarray],
                 cap_bits: jnp.ndarray, f_req: jnp.ndarray
                 ) -> Dict[str, jnp.ndarray]:
    """Score a composition grid. Pure jnp; safe under jit and shard_map.

    ``idx``       (J, S) int32 row indices into the table (-1 = sentinel).
    ``cols``      metric columns (each ``(n_configs,)``), METRIC_COLS keys.
    ``cap_bits``  (S,) required capacity per slot [bits].
    ``f_req``     (S,) required read frequency per slot [Hz].

    Returns a dict of ``(J,)`` float32 arrays keyed by SYSTEM_METRICS.
    """
    bad = idx < 0
    safe = jnp.maximum(idx, 0)

    def take(name):
        return jnp.take(cols[name], safe, axis=0)        # (J, S)

    bits = jnp.maximum(take("bits"), 1.0)
    tiles = jnp.ceil(cap_bits[None, :] / bits)           # macros per slot
    inf = jnp.float32(jnp.inf)

    area_um2 = slot_sum(jnp.where(bad, inf, tiles * take("area_um2")))
    p_static_w = slot_sum(
        jnp.where(bad, inf, tiles * (take("p_leak_w") + take("p_refresh_w"))))
    p_dyn_w = slot_sum(jnp.where(bad, inf, take("e_read_j") * f_req[None, :]))
    bw_margin = jnp.min(
        jnp.where(bad, 0.0,
                  take("f_op_hz") / jnp.maximum(f_req[None, :], 1.0)), axis=1)
    capacity_bits = slot_sum(jnp.where(bad, 0.0, tiles * bits))
    overprov = capacity_bits / jnp.maximum(slot_sum(cap_bits), 1.0)
    return {
        "area_um2": area_um2,
        "p_static_w": p_static_w,
        "p_dyn_w": p_dyn_w,
        "p_w": p_static_w + p_dyn_w,
        "bw_margin": bw_margin,
        "capacity_bits": capacity_bits,
        "overprovision": overprov,
    }


_score_jit = jax.jit(score_kernel)


def _score_interpret(idx, cols, cap_bits, f_req) -> Dict[str, np.ndarray]:
    """Pure-numpy float32 mirror of ``score_kernel`` — the oracle the
    registry-level interpret-vs-xla divergence sweep
    (``tests/test_backend_divergence.py``) drives against the jit path.
    Slots are summed by the same ``slot_sum`` left fold (``np.sum`` sums
    pairwise from 8 slots on, a different order)."""
    idx = np.asarray(idx)
    bad = idx < 0
    safe = np.maximum(idx, 0)

    def take(name):
        return np.asarray(cols[name], np.float32)[safe]          # (J, S)

    slot_cap_bits = np.asarray(cap_bits, np.float32)
    slot_f_req_hz = np.asarray(f_req, np.float32)
    bits = np.maximum(take("bits"), np.float32(1.0))
    tiles = np.ceil(slot_cap_bits[None, :] / bits)
    inf = np.float32(np.inf)
    area_um2 = slot_sum(np.where(bad, inf, tiles * take("area_um2")))
    p_static_w = slot_sum(
        np.where(bad, inf, tiles * (take("p_leak_w") + take("p_refresh_w"))))
    p_dyn_w = slot_sum(
        np.where(bad, inf, take("e_read_j") * slot_f_req_hz[None, :]))
    bw_margin = np.min(
        np.where(bad, np.float32(0.0),
                 take("f_op_hz") / np.maximum(slot_f_req_hz[None, :],
                                              np.float32(1.0))), axis=1)
    capacity_bits = slot_sum(np.where(bad, np.float32(0.0), tiles * bits))
    overprov = capacity_bits / np.maximum(
        slot_sum(slot_cap_bits), np.float32(1.0))
    return {
        "area_um2": area_um2,
        "p_static_w": p_static_w,
        "p_dyn_w": p_dyn_w,
        "p_w": (p_static_w + p_dyn_w).astype(np.float32),
        "bw_margin": bw_margin.astype(np.float32),
        "capacity_bits": capacity_bits,
        "overprovision": overprov.astype(np.float32),
    }


# the composition scorer is a registered dispatch point like every other
# compute hot-spot: "xla" is the jit kernel score_grid runs, "interpret" the
# numpy oracle above, and the divergence sweep proves them against each other
backend.register("compose_score", xla=_score_jit, interpret=_score_interpret)


def _score_corners_kernel(idx: jnp.ndarray, cols: Dict[str, jnp.ndarray],
                          cap_bits: jnp.ndarray, f_req: jnp.ndarray
                          ) -> Dict[str, jnp.ndarray]:
    """``score_kernel`` vmapped over corner-stacked metric columns: ``cols``
    leaves are ``(C, n_configs)`` and every output leaf is ``(C, J)``."""
    return jax.vmap(score_kernel, in_axes=(None, 0, None, None))(
        idx, cols, cap_bits, f_req)


_score_corners_jit = jax.jit(_score_corners_kernel)


def tiles_for(metrics: Mapping[str, np.ndarray], idx: np.ndarray,
              cap_bits: np.ndarray) -> np.ndarray:
    """Macros needed per slot — numpy mirror of the kernel's tiling rule,
    in float32 like the kernel so the reported tile counts can never
    disagree with the metrics priced from them."""
    bits = np.maximum(np.asarray(metrics["bits"], np.float32)[
        np.maximum(idx, 0)], np.float32(1.0))
    slot_cap_bits = np.asarray(cap_bits, np.float32)
    return np.where(idx < 0, 0,
                    np.ceil(slot_cap_bits[None, :] / bits)).astype(np.int64)


def score_grid(metrics: Mapping[str, np.ndarray], idx: np.ndarray,
               cap_bits: Sequence[float], f_req: Sequence[float],
               *, sharded: bool = False,
               devices: Optional[Sequence] = None) -> Dict[str, np.ndarray]:
    """Score ``(J, S)`` composition grid ``idx`` against table ``metrics``.

    ``sharded=True`` splits the grid's J axis across every visible device
    (``repro.compat`` mesh + shard_map); single-device hosts fall back to the
    plain jit call with identical results. Returns numpy ``(J,)`` arrays
    keyed by SYSTEM_METRICS.
    """
    cols = {k: jnp.asarray(np.asarray(metrics[k]), jnp.float32)
            for k in METRIC_COLS}
    idx_dev = jnp.asarray(np.asarray(idx), jnp.int32)
    slot_cap_bits = jnp.asarray(np.asarray(cap_bits), jnp.float32)
    slot_f_req_hz = jnp.asarray(np.asarray(f_req), jnp.float32)
    from repro.analysis import sanitize
    with obs.span("hetero.score", probe=_score_jit,
                  J=int(idx_dev.shape[0]), S=int(idx_dev.shape[1]),
                  sharded=sharded):
        if sharded:
            # shard_map composes badly with checkify's error plumbing; the
            # sanitizer covers the single-device path, which computes the same
            # values
            out = shard_leading(_score_jit, idx_dev, cols, slot_cap_bits,
                                slot_f_req_hz, devices=devices)
        else:
            out = sanitize.maybe_wrap(_score_jit)(
                idx_dev, cols, slot_cap_bits, slot_f_req_hz)
    _C_EVALS.inc()
    return {k: np.asarray(v) for k, v in out.items()}


def score_grid_corners(corner_metrics: Sequence[Mapping[str, np.ndarray]],
                       idx: np.ndarray, cap_bits: Sequence[float],
                       f_req: Sequence[float], *, sharded: bool = False,
                       devices: Optional[Sequence] = None
                       ) -> Dict[str, np.ndarray]:
    """Score one ``(J, S)`` grid under ``C`` operating-corner column sets in
    a single dispatch (``corner_metrics`` is one metric mapping per corner,
    e.g. ``[table.corner_metrics(c) for c in table.corner_labels]``).

    ``sharded=True`` spreads the work over a 2D (compositions × corners)
    device mesh (``repro.parallel.grid.shard2d``); results are bit-identical
    to the single-device path. Returns ``(C, J)`` numpy arrays keyed by
    SYSTEM_METRICS.
    """
    cols = {k: jnp.asarray(np.stack([np.asarray(m[k])
                                     for m in corner_metrics]), jnp.float32)
            for k in METRIC_COLS}
    idx_dev = jnp.asarray(np.asarray(idx), jnp.int32)
    slot_cap_bits = jnp.asarray(np.asarray(cap_bits), jnp.float32)
    slot_f_req_hz = jnp.asarray(np.asarray(f_req), jnp.float32)
    from repro.analysis import sanitize
    with obs.span("hetero.score", probe=_score_corners_jit,
                  J=int(idx_dev.shape[0]), S=int(idx_dev.shape[1]),
                  corners=len(corner_metrics), sharded=sharded):
        if sharded:
            # same caveat as score_grid: shard_map composes badly with
            # checkify, and the single-device path computes identical values
            out = shard2d(_score_corners_jit, idx_dev, cols, slot_cap_bits,
                          slot_f_req_hz, devices=devices)
        else:
            out = sanitize.maybe_wrap(_score_corners_jit)(
                idx_dev, cols, slot_cap_bits, slot_f_req_hz)
    _C_EVALS.inc()
    return {k: np.asarray(v) for k, v in out.items()}
