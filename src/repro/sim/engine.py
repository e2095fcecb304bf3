"""Batched trace replay: a ``jax.lax.scan`` over time bins, vmapped over the
full (J compositions × S slots) grid.

For every composition (one DesignTable row per slot) and every time bin of a
``repro.sim.trace.Trace``, the engine models what the analytic scorer
averages away:

- **port collisions**: demand reads/writes, scheduled refresh ops
  (``repro.sim.refresh``), and expiry rewrites all contend for the slot's
  aggregate port capacity ``tiles × f_op_hz × t_bin``; a bin whose total op
  count exceeds it stretches (service time ``t_bin × max(1, utilization)``),
  and the overlap of refresh with demand traffic is reported as
  ``collisions``.
- **dynamic access energy**: ``reads × e_read_j + write_ops × e_write_j``,
  with write bits converted to port accesses by each macro's own word width.
- **refresh energy**: every live word rewritten once per scheduled interval,
  ``(e_read_j + e_write_j)`` per op — only for slots whose data must outlive
  the cell's retention.
- **retention-expiry rewrites**: with refresh *disabled*, the same slots
  lose data at rate ``1/retention_s`` and must rewrite it (at
  ``rewrite_overhead × e_write_j`` per access — the overhead covers the
  upstream re-fetch).
- **occupancy / age**: live data ages with time and is rejuvenated by
  writes; the peak age is reported so callers can see how close a
  composition sails to its retention wall.

Everything per-bin is float32 elementwise arithmetic + per-slot reductions,
so the whole grid runs as ONE ``jit(vmap(scan))`` dispatch. The grid kernel
is registered with ``repro.kernels.backend`` as op ``"sim_replay"``:

  "xla"        the vmapped scan (default everywhere; there is no TPU-only
               path, so TPU hosts fall back here too)
  "interpret"  a per-composition Python loop over the same jitted
               single-composition scan — the bit-exactness oracle the tests
               compare against
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.hetero.system import slot_sum
from repro.kernels import backend as _backend
from repro.sim import refresh as refresh_mod
from repro.sim.trace import Trace

# metric columns the engine gathers from a DesignTable, plus the axis-derived
# "word_bits" column (``table["word_size"]``) the caller must add
SIM_COLS = ("bits", "word_bits", "e_read_j", "e_write_j", "f_op_hz",
            "p_leak_w", "retention_s")

# per-composition outputs, in the order the report/caching layers persist
SIM_METRICS = ("e_dyn_j", "e_refresh_j", "e_rewrite_j", "e_leak_j",
               "e_total_j", "t_sim_s", "t_wall_s", "stall_frac",
               "collisions", "util_peak", "age_peak_s", "p_avg_w")

# how many batched trace replays this process has run (a cached
# simulate/rerank leaves it unchanged — same proof pattern as
# api.characterize_call_count / hetero.composition_eval_count); lives on
# the repro.obs metrics registry, read through the thin alias below
_C_REPLAYS = obs.counter("sim.replay_calls")

# temperature-drift Arrhenius baseline: the solver's nominal die temperature
# and activation ratio Ea/kB [K] (Ea = 0.5 eV, matching core.corners)
_T_NOMINAL_K = 300.0
_EA_OVER_KB_K = 0.5 / 8.617333262e-5


def sim_eval_count() -> int:
    """Number of batched trace-replay sweeps executed so far
    (backed by the ``sim.replay_calls`` obs counter)."""
    return _C_REPLAYS.value


@dataclass(frozen=True)
class SimPolicy:
    """How traces are built, replayed, and used to re-rank.

    ``phases``           which phase traces to replay (``repro.sim.trace``
                         envelopes); energies/times sum across phases.
    ``duration_s``       replayed window per phase [s].
    ``n_bins``           time bins per phase.
    ``refresh``          True: schedule refresh at ``refresh_margin ×
                         retention_s``; False: let data expire and pay
                         retention-expiry rewrites instead.
    ``refresh_margin``   interval safety factor on the solver's retention.
    ``rewrite_overhead`` energy multiplier per expiry-rewrite access (the
                         upstream re-fetch the write implies).
    ``objective``        simulated re-rank key: "energy" (total J),
                         "latency" (simulated time incl. stalls), or "edp"
                         (energy × delay). The analytic top-K prune itself
                         is ``ComposePolicy.top_k`` — the re-rank replays
                         exactly the compositions the analytic report
                         materialized.
    ``corner``           operating-corner label (e.g. "hot") whose
                         ``retention_s@<corner>`` column drives refresh
                         intervals, expiry rewrites, and the retention wall
                         — requires a corner-batched DesignTable; None uses
                         the base ``retention_s``.
    ``adaptive_refresh`` True: a per-bank refresh controller that adapts the
                         effective interval to the observed traffic phase —
                         demand writes rejuvenate the words they touch, so
                         each bin's scheduled refresh ops are scaled by
                         ``1 - turnover`` (the fraction of live data the
                         bin's writes already rewrote). Write-heavy phases
                         therefore stretch the refresh duty; read-mostly
                         phases pay the full schedule.
    ``temp_drift_k``     linear die-temperature drift [K] across each phase's
                         replay window (300 K at t=0 → 300+drift at the end).
                         Retention follows the solver's Arrhenius law
                         (Ea=0.5 eV, as ``core.corners``) bin by bin inside
                         the scan, shrinking refresh intervals and
                         accelerating expiry rewrites as the die heats.
                         0.0 (default) replays at constant temperature,
                         bit-identical to the pre-drift engine.
    """
    phases: Tuple[str, ...] = ("prefill", "decode")
    duration_s: float = 1e-3
    n_bins: int = 32
    refresh: bool = True
    refresh_margin: float = refresh_mod.DEFAULT_REFRESH_MARGIN
    rewrite_overhead: float = 2.0
    objective: str = "energy"
    corner: Optional[str] = None
    adaptive_refresh: bool = False
    temp_drift_k: float = 0.0

    def __post_init__(self):
        if self.objective not in ("energy", "latency", "edp"):
            raise ValueError(f"unknown sim objective {self.objective!r}; "
                             f"choose from ('energy', 'latency', 'edp')")
        unknown = set(self.phases) - {"prefill", "decode", "train_step"}
        if unknown:
            raise ValueError(f"unknown phases {sorted(unknown)}")
        refresh_mod._check_margin(self.refresh_margin)
        drift = float(self.temp_drift_k)
        if not np.isfinite(drift) or _T_NOMINAL_K + drift <= 0.0:
            raise ValueError(
                f"temp_drift_k must be finite and keep the die above 0 K "
                f"(baseline {_T_NOMINAL_K:g} K), got {self.temp_drift_k!r}")


# ---------------------------------------------------------------------------
# the scan kernel
# ---------------------------------------------------------------------------


def _sim_phase_one(params, slot, xs, consts):
    """Replay one phase against ONE composition. Pure jnp; float32.

    ``params``  dict of (S,) per-slot macro columns (gathered table rows).
    ``slot``    dict of (S,) slot requirement vectors (cap_bits, lifetime_s).
    ``xs``      (t_bin (T,), reads (T, S), write_bits (T, S), occ (T, S)).
    ``consts``  (5,) f32: [refresh_on, rewrite_overhead, adaptive_on,
                temp_drift_k, t_total_s].
    Returns a dict of scalar outputs keyed by SIM_METRICS.

    Temperature drift and the adaptive controller live INSIDE the scan: each
    bin scales retention by the Arrhenius factor of the current die
    temperature (linear 300 K → 300+drift ramp over ``t_total_s``) before
    deriving refresh need, interval, and expiry rate; the adaptive controller
    then skips the fraction of scheduled refreshes the bin's own writes
    already performed. Both collapse to exact multiplications by 1.0 when
    disabled, keeping the base replay bit-identical.
    """
    p, s = params, slot
    eps = jnp.float32(1e-30)
    refresh_on, overhead = consts[0], consts[1]
    adaptive_on, drift_k, t_total = consts[2], consts[3], consts[4]
    num_words = p["bits"] / p["word_bits"]
    interval = p["interval_s"]
    cap_rate = p["tiles"] * p["f_op_hz"]             # port ops/s per slot

    def step(carry, x):
        age, e_dyn, e_ref, e_rew, t_sim, coll, upk, apk, t_acc = carry
        t_bin, reads, wbits, occ = x
        # die temperature at this bin; retention Arrhenius scale vs 300 K
        # (drift 0 -> exponent exactly 0 -> rs exactly 1.0)
        t_now = _T_NOMINAL_K + drift_k * (t_acc / jnp.maximum(t_total, eps))
        rs = jnp.exp(_EA_OVER_KB_K * (1.0 / t_now - 1.0 / _T_NOMINAL_K))
        ret = p["retention_s"] * rs
        need = refresh_mod.needs_refresh(
            ret, s["lifetime_s"]).astype(jnp.float32)
        wops = wbits / p["word_bits"]
        turn = jnp.clip(wbits / jnp.maximum(occ * s["cap_bits"], eps),
                        0.0, 1.0)
        # adaptive controller: writes are refreshes of the words they touch,
        # so skip that fraction of the schedule (adaptive_on gates to 1.0)
        refr = ((1.0 - adaptive_on * turn) * refresh_on * need
                * refresh_mod.refresh_ops(
                    p["tiles"] * num_words, interval * rs, occ, t_bin))
        rewr = ((1.0 - refresh_on) * need * occ * s["cap_bits"] * t_bin
                / jnp.maximum(ret, eps) / p["word_bits"])
        cap_ops = jnp.maximum(cap_rate * t_bin, eps)
        util = (reads + wops + refr + rewr) / cap_ops
        age = (age + t_bin) * (1.0 - turn)
        carry = (
            age,
            e_dyn + slot_sum(reads * p["e_read_j"] + wops * p["e_write_j"]),
            e_ref + slot_sum(refr * (p["e_read_j"] + p["e_write_j"])),
            e_rew + slot_sum(rewr * p["e_write_j"]) * overhead,
            t_sim + t_bin * jnp.maximum(jnp.max(util), 1.0),
            coll + slot_sum(refr * jnp.minimum((reads + wops) / cap_ops,
                                               1.0)),
            jnp.maximum(upk, jnp.max(util)),
            jnp.maximum(apk, jnp.max(age)),
            t_acc + t_bin,
        )
        return carry, None

    S = p["bits"].shape[0]
    zero = jnp.float32(0.0)
    carry0 = (jnp.zeros((S,), jnp.float32),) + (zero,) * 8
    (age, e_dyn, e_ref, e_rew, t_sim, coll, upk, apk, _), _ = jax.lax.scan(
        step, carry0, xs)
    t_wall = jnp.sum(xs[0])
    e_leak = slot_sum(p["p_leak_w"] * p["tiles"]) * t_sim
    e_total = e_dyn + e_ref + e_rew + e_leak
    return {
        "e_dyn_j": e_dyn, "e_refresh_j": e_ref, "e_rewrite_j": e_rew,
        "e_leak_j": e_leak, "e_total_j": e_total,
        "t_sim_s": t_sim, "t_wall_s": t_wall,
        "stall_frac": (t_sim - t_wall) / jnp.maximum(t_wall, eps),
        "collisions": coll, "util_peak": upk, "age_peak_s": apk,
        "p_avg_w": e_total / jnp.maximum(t_sim, eps),
    }


_sim_grid_xla = jax.jit(jax.vmap(_sim_phase_one, in_axes=(0, None, None,
                                                          None)))
_sim_one_jit = jax.jit(_sim_phase_one)


def _sim_grid_interpret(params, slot, xs, consts):
    """Per-composition Python loop over the same jitted scan — the oracle the
    vmapped path must match bit-for-bit."""
    J = next(iter(params.values())).shape[0]
    rows = [_sim_one_jit({k: v[j] for k, v in params.items()},
                         slot, xs, consts) for j in range(J)]
    return {m: jnp.stack([r[m] for r in rows]) for m in SIM_METRICS}


_backend.register("sim_replay", xla=_sim_grid_xla,
                  interpret=_sim_grid_interpret)


# ---------------------------------------------------------------------------
# public batched entry
# ---------------------------------------------------------------------------


def _gather_params(cols: Mapping[str, np.ndarray], idx: np.ndarray,
                   cap_bits: np.ndarray,
                   policy: SimPolicy) -> Dict[str, jnp.ndarray]:
    if policy.corner is not None:
        # schedule refresh / expiry off the named corner's retention column
        cols = {**cols,
                "retention_s": refresh_mod.retention_column(
                    cols, policy.corner)}
    safe = jnp.maximum(jnp.asarray(np.asarray(idx), jnp.int32), 0)
    missing = [c for c in SIM_COLS if c not in cols]
    if missing:
        raise KeyError(f"sim cols missing {missing}; callers gather "
                       f"DesignTable metrics + word_bits=table['word_size']")
    p = {c: jnp.take(jnp.asarray(np.asarray(cols[c]), jnp.float32), safe,
                     axis=0) for c in SIM_COLS}
    bits = jnp.maximum(p["bits"], 1.0)
    cap = jnp.asarray(np.asarray(cap_bits), jnp.float32)
    p["tiles"] = jnp.ceil(cap[None, :] / bits)       # scorer's tiling rule
    p["interval_s"] = jnp.asarray(
        refresh_mod.refresh_interval_s(p["retention_s"],
                                       policy.refresh_margin), jnp.float32)
    return p


def simulate_traces(cols: Mapping[str, np.ndarray], idx: np.ndarray,
                    traces: Sequence[Trace],
                    policy: Optional[SimPolicy] = None,
                    backend: Optional[str] = None) -> Dict[str, object]:
    """Replay ``traces`` against every composition of ``idx``.

    ``cols``    DesignTable metric columns + ``word_bits`` (each
                ``(n_configs,)``) — see ``SIM_COLS``.
    ``idx``     (J, S) int32 row indices (-1 = infeasible sentinel; such
                compositions price at +inf energy/time like the analytic
                scorer).
    ``traces``  one ``Trace`` per phase, identical slot order as ``idx``
                columns.
    ``backend`` kernel backend override ("xla" | "interpret"); default via
                ``repro.kernels.backend.resolve_backend``.

    Returns ``{metric: (J,) float64}`` over ``SIM_METRICS`` — energies,
    times, and collisions summed across phases, peaks maxed — plus
    ``"phases"``: the same per-phase dicts keyed by phase name.
    """
    if not traces:
        raise ValueError("simulate_traces() needs at least one Trace")
    policy = policy or SimPolicy()
    idx = np.asarray(idx)
    S = idx.shape[1]
    if any(t.n_slots != S for t in traces):
        raise ValueError(f"trace slot counts {[t.n_slots for t in traces]} "
                         f"!= grid slot count {S}")
    t0 = traces[0]
    params = _gather_params(cols, idx, t0.cap_bits, policy)
    slot = {"cap_bits": jnp.asarray(t0.cap_bits, jnp.float32),
            "lifetime_s": jnp.asarray(t0.lifetime_s, jnp.float32)}
    from repro.analysis import sanitize
    impl = sanitize.maybe_wrap(_backend.get_impl("sim_replay", backend))

    per_phase: Dict[str, Dict[str, np.ndarray]] = {}
    bad = np.any(idx < 0, axis=1)
    with obs.span("sim.replay", J=int(idx.shape[0]), S=int(S),
                  phases=len(traces)):
        for tr in traces:
            # the drift ramp spans each phase's own replay window
            consts = jnp.asarray(
                [1.0 if policy.refresh else 0.0, policy.rewrite_overhead,
                 1.0 if policy.adaptive_refresh else 0.0,
                 policy.temp_drift_k, float(np.sum(tr.t_bin_s))],
                jnp.float32)
            xs = (jnp.asarray(tr.t_bin_s, jnp.float32),
                  jnp.asarray(tr.reads.T, jnp.float32),
                  jnp.asarray(tr.write_bits.T, jnp.float32),
                  jnp.asarray(tr.occupancy.T, jnp.float32))
            with obs.span("sim.replay_phase", probe=_sim_grid_xla,
                          phase=tr.phase):
                out = impl(params, slot, xs, consts)
            per_phase[tr.phase] = _mask_sentinels(
                {m: np.asarray(out[m], np.float64) for m in SIM_METRICS}, bad)
    _C_REPLAYS.inc()

    combined = _mask_sentinels(_combine_phases(per_phase), bad)
    combined["phases"] = per_phase
    return combined


def _mask_sentinels(metrics: Dict[str, np.ndarray],
                    bad: np.ndarray) -> Dict[str, np.ndarray]:
    """Price compositions with any sentinel slot (clamped to table row 0 by
    the gather) at +inf energy/time, zero diagnostics — the analytic
    scorer's contract, applied to combined AND per-phase outputs."""
    if not bad.any():
        return metrics
    for m in ("e_dyn_j", "e_refresh_j", "e_rewrite_j", "e_leak_j",
              "e_total_j", "t_sim_s", "p_avg_w"):
        metrics[m] = np.where(bad, np.inf, metrics[m])
    for m in ("collisions", "util_peak", "age_peak_s", "stall_frac"):
        metrics[m] = np.where(bad, 0.0, metrics[m])
    return metrics


def _combine_phases(per_phase: Mapping[str, Mapping[str, np.ndarray]]
                    ) -> Dict[str, np.ndarray]:
    """Sum energies/times/collisions across phases, max the peaks, and
    re-derive the ratio metrics from the combined totals."""
    phases = list(per_phase.values())
    out: Dict[str, np.ndarray] = {}
    for m in ("e_dyn_j", "e_refresh_j", "e_rewrite_j", "e_leak_j",
              "e_total_j", "t_sim_s", "t_wall_s", "collisions"):
        out[m] = np.sum([ph[m] for ph in phases], axis=0)
    for m in ("util_peak", "age_peak_s"):
        out[m] = np.max([ph[m] for ph in phases], axis=0)
    # sentinel rows hold inf sums: inf-inf / inf/inf transiently produce
    # nans here that _mask_sentinels overwrites — keep numpy quiet about it
    with np.errstate(invalid="ignore"):
        out["stall_frac"] = ((out["t_sim_s"] - out["t_wall_s"])
                             / np.maximum(out["t_wall_s"], 1e-30))
        out["p_avg_w"] = out["e_total_j"] / np.maximum(out["t_sim_s"], 1e-30)
    return out
