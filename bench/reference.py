"""Plain reference of a DSE query, independent of the program under test.

It restates, from the published model, what one query computes: the macro
characterization of every configuration of the design space at an
operating point (analytic device, periphery and RK4 retention models), the
per-(level, bucket) candidate lists, the whole-composition pricing, the
ranking under the compose policy, the (vdd, refresh-margin) sweep blocks,
and the trace replay that re-ranks the analytic top-K. It imports nothing
of the program.

Every function takes a numeric backend ``nb``: ``NB64`` (numpy, float64)
is the reference; ``bf16()`` (jax.numpy, bfloat16) is the control, the same
computation one precision below the program's float32. Discrete steps
(feasibility, sorting, list building) run in numpy on the values the
backend produced.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# numeric backends
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Backend:
    xp: object          # numpy or jax.numpy
    dtype: object

    def c(self, x):
        """An array of this backend's dtype."""
        return self.xp.asarray(x, self.dtype)

    def where(self, cond, a, b):
        return self.xp.where(cond, self.c(a), self.c(b))

    def host(self, x) -> np.ndarray:
        """Values as float64 numpy (for the discrete steps)."""
        return np.asarray(np.asarray(x).astype(np.float32), np.float64) \
            if self.xp is not np else np.asarray(x, np.float64)


NB64 = Backend(np, np.float64)


def bf16() -> Backend:
    import jax.numpy as jnp
    return Backend(jnp, jnp.bfloat16)


# ---------------------------------------------------------------------------
# technology constants (40 nm-class process, the model's calibration)
# ---------------------------------------------------------------------------

VDD, VDD_BOOST, TEMP_K, UT = 1.1, 1.6, 300.0, 0.02585
C_GATE, C_JUNC, C_WIRE, R_WIRE = 1.0e-15, 0.8e-15, 0.20e-15, 2.0
GATE_AREA, DFF_AREA, LS_AREA = 0.9, 4.2, 5.5
SA_AREA, SA_AREA_CM, WRITE_DRV_AREA = 9.0, 12.0, 3.0
PREDIS_AREA, PRECH_AREA, CTRL_AREA = 1.1, 1.6, 120.0
DELAY_STAGE_AREA, RING_PITCH = 2.2, 1.8
T_GATE, T_DFF_CQ, T_SETUP = 15e-12, 45e-12, 30e-12
T_SA, T_SA_CM, T_MUX, T_WL_DRV, DELAY_STAGE = 40e-12, 28e-12, 12e-12, \
    28e-12, 60e-12
V_SENSE, V_SENSE_SRAM = 0.10, 0.08
E_SA, E_DFF, ACTIVITY = 8e-15, 4e-15, 0.5
INV_LEAK, INV_CIN = 60e-12, 1.5e-15
KB_EV, EA_LEAK_EV = 8.617333262e-5, 0.5

# devices: vt [V], subthreshold swing [mV/dec], I_on target [A/um] at
# VDD/VDD, DIBL [V/V], off floor [A/um], gate leak [A/um]
DEVICES = {
    "si_nmos": (0.45, 88.0, 600e-6, 0.08, 1e-12, 2e-12),
    "si_nmos_hvt": (0.62, 85.0, 420e-6, 0.06, 1e-12, 2e-12),
    "si_pmos": (0.45, 92.0, 300e-6, 0.08, 1e-12, 2e-14),
    "ito_os": (0.47, 65.0, 110e-6, 0.02, 1e-19, 0.0),
    "ito_os_hvt": (0.72, 65.0, 70e-6, 0.02, 1e-19, 0.0),
    "igzo_os": (0.55, 70.0, 30e-6, 0.02, 1e-19, 0.0),
}


def _cell(kind, w, h, w_write, w_read, c_sn, wdev, rdev, dual, leaks):
    return dict(kind=kind, cell_w=w, cell_h=h, w_write=w_write,
                w_read=w_read, c_sn=c_sn, wdev=wdev, rdev=rdev, dual=dual,
                leaks=leaks)


_C_SISI = 0.15 * C_GATE + 0.12 * C_JUNC + 0.35e-15
_C_OSSI = 0.15 * C_GATE + 0.10 * C_JUNC + 0.35e-15
_C_OSOS = 0.12 * C_GATE + 0.10 * C_JUNC + 0.30e-15
BITCELLS = {
    "sram6t": _cell(0, 0.55, 0.44, 0.12, 0.15, 0.0, "si_nmos", "si_nmos",
                    0, 2),
    "gc_sisi": _cell(1, 0.380, 0.44, 0.12, 0.15, _C_SISI, "si_nmos",
                     "si_pmos", 1, 0),
    "gc_sisi_hvt": _cell(1, 0.380, 0.44, 0.12, 0.15, _C_SISI, "si_nmos_hvt",
                         "si_pmos", 1, 0),
    "gc_ossi": _cell(2, 0.220, 0.385, 0.10, 0.15, _C_OSSI, "ito_os",
                     "si_pmos", 1, 0),
    "gc_ossi_hvt": _cell(2, 0.220, 0.385, 0.10, 0.15, _C_OSSI, "ito_os_hvt",
                         "si_pmos", 1, 0),
    "gc_osos": _cell(3, 0.190, 0.38, 0.10, 0.12, _C_OSOS, "ito_os",
                     "igzo_os", 1, 0),
    "gc_osos_hvt": _cell(3, 0.190, 0.38, 0.10, 0.12, _C_OSOS, "ito_os_hvt",
                         "igzo_os", 1, 0),
}
FAMILY = {"sram6t": "sram", "gc_sisi": "si-si", "gc_sisi_hvt": "si-si",
          "gc_ossi": "os-si", "gc_ossi_hvt": "os-si", "gc_osos": "os-os",
          "gc_osos_hvt": "os-os"}
DISPLAY = {"os-si": "OS-Si GCRAM", "si-si": "Si-Si GCRAM", "sram": "SRAM",
           "os-os": "OS-OS GCRAM"}

CHAR_COLUMNS = ("area_um2", "area_array_um2", "f_read_hz", "f_write_hz",
                "f_op_hz", "bandwidth_bits_s", "bandwidth_total_bits_s",
                "t_read_s", "t_write_s", "e_read_j", "e_write_j", "p_dyn_w",
                "p_leak_w", "p_refresh_w", "retention_s", "rows", "cols",
                "mux", "bits")


# ---------------------------------------------------------------------------
# operating point and devices
# ---------------------------------------------------------------------------


def tech_at(nb: Backend, vdd: float, temp_k: float) -> Dict[str, object]:
    """Corner-dependent parameters at (vdd [V], temp_k [K])."""
    v, t = nb.c(vdd), nb.c(temp_k)
    vr = v / VDD
    return dict(vdd=v, vdd_boost=VDD_BOOST * vr, ut=UT * (t / TEMP_K),
                leak_scale=nb.xp.exp(EA_LEAK_EV / KB_EV
                                     * (1.0 / TEMP_K - 1.0 / t)),
                drive_scale=(t / TEMP_K) ** -1.5, v_sense=V_SENSE * vr,
                v_sense_sram=V_SENSE_SRAM * vr)


def _softplus_sq(nb, u):
    sp = nb.xp.logaddexp(nb.c(0.0), u / 2.0)
    return sp * sp


def _drain_current(nb, dev, vgs, vds, w, tp):
    """EKV-style drain current [A] of ``dev`` (a dict of arrays)."""
    xp = nb.xp
    vt_eff = dev["vt"] - dev["eta"] * vds
    nut = dev["n"] * tp["ut"]
    i_ch = dev["ispec"] * (_softplus_sq(nb, (vgs - vt_eff) / nut)
                           - _softplus_sq(nb, (vgs - vt_eff - dev["n"] * vds)
                                          / nut))
    i_ch = xp.maximum(i_ch, 0.0) * tp["drive_scale"]
    floor = dev["i_floor"] * tp["leak_scale"] * nb.where(vds > 0, 1.0, 0.0)
    return (i_ch + floor) * w


def device(nb: Backend, name: str) -> Dict[str, object]:
    """Device parameters, with the spec current calibrated so that
    I_on(VDD, VDD) equals the target at the 300 K calibration point."""
    vt, ss_mv, i_on, eta, i_floor, j_gate = DEVICES[name]
    n = nb.c(ss_mv) * 1e-3 / (UT * math.log(10.0))
    probe = dict(vt=nb.c(vt), n=n, ispec=nb.c(1.0), eta=nb.c(eta),
                 i_floor=nb.c(0.0))
    nominal = tech_at(nb, VDD, TEMP_K)
    scale = _drain_current(nb, probe, nb.c(VDD), nb.c(VDD), nb.c(1.0),
                           nominal)
    return dict(vt=nb.c(vt), n=n, ispec=nb.c(i_on) / scale, eta=nb.c(eta),
                i_floor=nb.c(i_floor), j_gate=nb.c(j_gate))


def _stack(nb, dicts: List[Dict[str, object]]) -> Dict[str, object]:
    return {k: nb.xp.stack([nb.c(d[k]) for d in dicts]) for k in dicts[0]}


def _ceil_log2(nb, x):
    mant, ex = nb.xp.frexp(x)
    return (ex - (mant == 0.5)).astype(nb.dtype)


# ---------------------------------------------------------------------------
# characterization
# ---------------------------------------------------------------------------


def characterize(nb: Backend, configs: Sequence[Dict[str, object]],
                 vdd: float = VDD, temp_k: float = TEMP_K
                 ) -> Dict[str, np.ndarray]:
    """PPA and retention of every configuration at (vdd, temp_k).

    ``configs``: dicts with mem_type, word_size, num_words, banks,
    level_shift, sa_current_mode, mux (0 = squarest power-of-two mux).
    Returns float64 numpy columns named as ``CHAR_COLUMNS``.
    """
    xp = nb.xp
    tp = tech_at(nb, vdd, temp_k)
    vdd_, boost = tp["vdd"], tp["vdd_boost"]
    devs = {name: device(nb, name) for name in DEVICES}
    cells = [BITCELLS[c["mem_type"]] for c in configs]
    wdev = _stack(nb, [devs[c["wdev"]] for c in cells])
    rdev = _stack(nb, [devs[c["rdev"]] for c in cells])
    cell = {k: nb.c([c[k] for c in cells])
            for k in ("kind", "cell_w", "cell_h", "w_write", "w_read",
                      "c_sn", "dual", "leaks")}
    col = {k: nb.c([float(c[k]) for c in configs])
           for k in ("word_size", "num_words", "banks", "level_shift",
                     "sa_current_mode", "mux")}
    wz, nw, banks = col["word_size"], col["num_words"], col["banks"]
    ls, sa_cm = col["level_shift"], col["sa_current_mode"]
    is_gc = nb.where(cell["kind"] > 0, 1.0, 0.0).astype(nb.dtype)
    dual = cell["dual"]

    # geometry: the squarest power-of-two column mux, in [1, 8]
    nw_bank = nw / banks
    half_log2 = xp.log2(nw_bank / xp.maximum(wz, 1.0)) / 2.0
    auto = xp.clip(2.0 ** xp.round(xp.maximum(half_log2, 0.0)), 1.0, 8.0)
    m = xp.minimum(nb.where(col["mux"] > 0, col["mux"], auto), nw_bank)
    rows = xp.maximum(nw_bank / m, 1.0)
    cols = wz * m

    def wordline(w_access):
        return (cols * (w_access * C_GATE + cell["cell_w"] * C_WIRE),
                cols * cell["cell_w"] * R_WIRE)

    def bitline(w_drain):
        return (rows * (w_drain * C_JUNC + cell["cell_h"] * C_WIRE),
                rows * cell["cell_h"] * R_WIRE)

    def wl_driver(c_load, r_wire, supply):
        w_drv = xp.maximum(c_load / (8.0 * INV_CIN), 1.0)
        return (0.8 + 0.35 * w_drv, T_WL_DRV + 0.4 * r_wire * c_load,
                (c_load + w_drv * INV_CIN) * supply ** 2, w_drv * INV_LEAK)

    def delay_chain(t_crit):
        n = xp.ceil(t_crit / DELAY_STAGE) + 1.0
        return n * DELAY_STAGE, n * 1.0e-15 * vdd_ ** 2, n * 0.8 * INV_LEAK

    def i_on(dev, w):
        return _drain_current(nb, dev, vdd_, vdd_, w, tp)

    si_nmos = devs["si_nmos"]

    def write_driver(c_bl):
        w_drv = xp.maximum(c_bl / (10.0 * INV_CIN), 1.0)
        return (WRITE_DRV_AREA + 0.3 * w_drv,
                20e-12 + c_bl * vdd_ / i_on(si_nmos, w_drv),
                c_bl * vdd_ ** 2 * 0.5, w_drv * INV_LEAK)

    # stored '1' level: degraded by the write device's VT unless boosted
    v_high = nb.where(cell["kind"] > 0,
                      nb.where(ls > 0, vdd_, vdd_ - wdev["vt"]), vdd_)

    # decoder
    n_addr_row = _ceil_log2(nb, xp.maximum(rows, 2.0))
    dec_area = rows * GATE_AREA + n_addr_row * 4.0 * GATE_AREA
    t_dec = (2.0 + xp.ceil(n_addr_row / 3.0)) * T_GATE
    e_dec = (n_addr_row * 4.0 + 2.0) * 1.2e-15 * vdd_ ** 2
    l_dec = (rows + n_addr_row * 4.0) * 0.5 * INV_LEAK

    # column mux
    is_mux = nb.where(m > 1, 1.0, 0.0).astype(nb.dtype)
    mux_stages = _ceil_log2(nb, xp.maximum(m, 1.0))
    t_mux = mux_stages * T_MUX
    e_mux = mux_stages * 0.8e-15 * vdd_ ** 2
    l_mux = 0.2 * INV_LEAK * is_mux

    # ---- area (no term of it depends on the operating point)
    arr_area = cols * cell["cell_w"] * (rows * cell["cell_h"] * 1.04)
    c_wl_w, r_wl_w = wordline(cell["w_write"])
    drv_area = wl_driver(c_wl_w, r_wl_w, vdd_)[0]
    a_row = (dec_area + rows * drv_area) * (1.0 + dual) \
        + ls * rows * LS_AREA * is_gc
    c_bl_r, r_bl_r = bitline(cell["w_read"])
    wd_area = write_driver(c_bl_r)[0]
    a_col = (wz * nb.where(sa_cm > 0, SA_AREA_CM, SA_AREA) + wz * wd_area
             + cols * 0.9 * is_mux
             + cols * nb.where(is_gc > 0, PREDIS_AREA, PRECH_AREA))
    n_addr = _ceil_log2(nb, xp.maximum(nw, 2.0))
    a_dff = (2 * wz + n_addr * (1.0 + dual)) * DFF_AREA
    a_ctrl = CTRL_AREA * (1.0 + 0.5 * dual)
    core = (arr_area + a_row + a_col + a_dff + a_ctrl) * banks \
        + nb.where(banks > 1, 40.0, 0.0) * banks
    area = core + 4.0 * xp.sqrt(core) * RING_PITCH * (2.0 + ls * is_gc)

    # ---- read path
    c_wl_r, r_wl_r = wordline(cell["w_read"])
    _, t_wl, e_wl, l_wl = wl_driver(c_wl_r, r_wl_r, vdd_)
    i0 = _drain_current(nb, rdev, vdd_, 0.5 * vdd_, cell["w_read"], tp)
    i1 = _drain_current(nb, rdev, vdd_ - v_high, 0.5 * vdd_, cell["w_read"],
                        tp)
    i_rd_gc = xp.maximum(i0 - i1, 0.05 * i0)
    i_rd_sram = 0.8 * i_on(wdev, cell["w_write"])
    t_bl = nb.where(is_gc > 0,
                    c_bl_r * tp["v_sense"] / xp.maximum(i_rd_gc, 1e-9),
                    c_bl_r * tp["v_sense_sram"] / xp.maximum(i_rd_sram,
                                                             1e-9))
    e_sa_v = E_SA * (vdd_ ** 2 / VDD ** 2)
    t_sa = nb.where(sa_cm > 0, T_SA_CM, T_SA)
    e_sa = nb.where(sa_cm > 0, e_sa_v * 1.6, e_sa_v)
    l_sa = 3 * INV_LEAK                   # the voltage-mode SA's leak
    t_read = (T_DFF_CQ + t_dec + t_wl + 0.7 * r_bl_r * c_bl_r + t_bl
              + t_mux + t_sa + T_SETUP)
    t_read_cyc, _, l_dc = delay_chain(t_read)

    # ---- write path
    _, t_wwl, e_wwl, l_wwl = wl_driver(c_wl_w, r_wl_w, boost)
    e_ls = 2.5e-15 * boost ** 2 / vdd_ ** 2
    l_ls = 2 * INV_LEAK
    t_wwl = t_wwl + ls * 18e-12 * is_gc
    c_wbl, _ = bitline(cell["w_write"])
    _, t_wd, e_wd, l_wd = write_driver(c_wbl)
    v_wwl = nb.where(ls > 0, boost, vdd_)
    i_w = _drain_current(nb, wdev, v_wwl - 0.9 * v_high,
                         xp.maximum(vdd_ - 0.9 * v_high, 0.1),
                         cell["w_write"], tp)
    t_sn = nb.where(is_gc > 0,
                    cell["c_sn"] * v_high / xp.maximum(i_w, 1e-9), 30e-12)
    t_write = T_DFF_CQ + t_dec + t_wwl + t_wd + t_sn + T_SETUP
    t_write_cyc, _, _ = delay_chain(t_write)

    # ---- frequency and bandwidth (dual-port gain cells read concurrently;
    # an SRAM port is shared and loses 30% of its reads to writes)
    f_read, f_write = 1.0 / t_read_cyc, 1.0 / t_write_cyc
    f_sram = 1.0 / xp.maximum(t_read_cyc, t_write_cyc)
    f_op = nb.where(is_gc > 0, xp.minimum(f_read, f_write), f_sram)
    bw = nb.where(is_gc > 0, wz * f_read, wz * f_sram * 0.7)
    bw_total = nb.where(is_gc > 0, wz * (f_read + f_write * dual),
                        wz * f_sram * 0.7)

    # ---- energy and power
    e_read = (e_dec + e_wl + c_wl_r * vdd_ ** 2
              + c_bl_r * vdd_ * tp["v_sense"] * cols / xp.maximum(m, 1.0)
              + wz * e_sa + e_mux + 2 * wz * E_DFF)
    e_write = (e_dec + e_wwl + e_wd * wz + ls * e_ls * is_gc
               + c_wbl * vdd_ ** 2 * wz * 0.5 + wz * E_DFF
               + ls * is_gc * (c_wl_w * (boost ** 2 - vdd_ ** 2)))
    p_dyn = (e_read + e_write * 0.5) * f_op * ACTIVITY
    i_cell_leak = cell["leaks"] * _drain_current(nb, wdev, nb.c(0.0), vdd_,
                                                 nb.c(0.15), tp)
    bits = wz * nw
    i_periph = (l_dec * (1 + dual) + l_wl + l_wwl + wz * (l_sa + l_wd)
                + l_mux * cols + l_dc + ls * l_ls * rows * is_gc
                + 25 * INV_LEAK) * banks
    p_leak = bits * i_cell_leak * vdd_ + i_periph * vdd_

    # ---- retention and refresh
    ret = nb.where(is_gc > 0,
                   retention_time(nb, cell, wdev, rdev, v_high, tp), 1e12)
    p_refresh = nb.where(is_gc > 0, (e_read + e_write) * nw
                         / xp.maximum(ret, 1e-9), 0.0)

    out = {
        "area_um2": area, "area_array_um2": arr_area * banks,
        "f_read_hz": nb.where(is_gc > 0, f_read, f_sram),
        "f_write_hz": nb.where(is_gc > 0, f_write, f_sram),
        "f_op_hz": f_op, "bandwidth_bits_s": bw,
        "bandwidth_total_bits_s": bw_total,
        "t_read_s": t_read, "t_write_s": t_write,
        "e_read_j": e_read, "e_write_j": e_write,
        "p_dyn_w": p_dyn, "p_leak_w": p_leak, "p_refresh_w": p_refresh,
        "retention_s": ret, "rows": rows, "cols": cols, "mux": m,
        "bits": bits,
    }
    return {k: nb.host(xp.broadcast_to(v, wz.shape)) for k, v in out.items()}


N_RET_STEPS = 480                      # 30 points per decade, 1 ns .. 1e7 s


def retention_time(nb: Backend, cell, wdev, rdev, v0, tp):
    """Seconds until a stored '1' droops below the read-margin threshold:
    C_SN dV/dt = -(I_sub(write device, vgs = 0, vds = V) + I_gate(read
    device, V)), by RK4 on the log grid, with log-linear interpolation of
    the crossing."""
    xp = nb.xp
    ts = xp.logspace(-9.0, 7.0, N_RET_STEPS + 1).astype(nb.dtype)
    c_sn = xp.maximum(cell["c_sn"], 1e-18)

    def dvdt(v):
        v = xp.maximum(v, 0.0)
        i_sub = _drain_current(nb, wdev, nb.c(0.0), v, cell["w_write"], tp)
        i_gate = rdev["j_gate"] * tp["leak_scale"] * cell["w_read"] \
            * (v / tp["vdd"])
        return -(i_sub + i_gate) / c_sn

    v = v0
    vs = [v0]
    for i in range(N_RET_STEPS):
        dt = ts[i + 1] - ts[i]
        k1 = dvdt(v)
        k2 = dvdt(v + 0.5 * dt * k1)
        k3 = dvdt(v + 0.5 * dt * k2)
        k4 = dvdt(v + dt * k3)
        v_new = v + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
        vs.append(v_new)
        v = xp.clip(v_new, 0.0, 2.0)
    vs = xp.stack(vs, axis=1)                              # (N, T + 1)

    # threshold: the lowest SN level at which the read device conducts at
    # most a tenth of its stored-'0' current, on a 256-point grid
    grid = xp.linspace(0.0, 1.0, 256).astype(nb.dtype)[None, :] * tp["vdd"]
    col = {k: rdev[k][:, None] for k in ("vt", "n", "ispec", "eta",
                                          "i_floor")}
    w = cell["w_read"][:, None]
    i_read = _drain_current(nb, col, tp["vdd"] - grid, tp["vdd"], w, tp)
    i_on0 = _drain_current(nb, col, tp["vdd"], tp["vdd"], w, tp)
    ok = i_read <= 0.1 * i_on0
    v_min = xp.take_along_axis(
        xp.broadcast_to(grid, ok.shape), xp.argmax(ok, axis=1)[:, None],
        axis=1)[:, 0]

    crossed = vs < v_min[:, None]
    idx = xp.argmax(crossed, axis=1)
    i0 = xp.maximum(idx - 1, 0)
    t0, t1 = ts[i0], ts[idx]
    v_hi = xp.take_along_axis(vs, i0[:, None], axis=1)[:, 0]
    v_lo = xp.take_along_axis(vs, idx[:, None], axis=1)[:, 0]
    frac = xp.clip((v_hi - v_min) / xp.maximum(v_hi - v_lo, 1e-9), 0.0, 1.0)
    t_cross = xp.exp(xp.log(t0) + frac * (xp.log(t1) - xp.log(t0)))
    return nb.where(xp.any(crossed, axis=1), t_cross, ts[-1])


# ---------------------------------------------------------------------------
# composition: candidates, pricing, ranking
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Slot:
    level: str
    capacity_bits: float
    f_hz: float
    lifetime_s: float


def task_slots(task: Dict[str, object]) -> List[Slot]:
    """Slots in level order, buckets in order. ``task["levels"]`` is a list
    of [name, capacity_kib, [[frac, f_hz, lifetime_s], ...]]."""
    out = []
    for name, cap_kib, buckets in task["levels"]:
        for frac, f_hz, life in buckets:
            out.append(Slot(name, cap_kib * 8 * 1024 * frac, f_hz, life))
    return out


def _slot_contrib(table, slot: Slot):
    tiles = np.ceil(slot.capacity_bits / np.maximum(table["bits"], 1.0))
    power = table["p_leak_w"] + table["p_refresh_w"]
    return tiles * table["area_um2"], tiles * power \
        + table["e_read_j"] * slot.f_hz


def candidates(table, families: np.ndarray, slot: Slot, policy) -> List:
    """Candidate rows of one slot: [(row, preference rank), ...] in list
    order, or [(-1, len(preference))] when no row is feasible."""
    pref = policy["preference"]
    ok = (table["f_op_hz"] >= slot.f_hz) & (table["retention_s"]
                                             >= slot.lifetime_s)
    power = table["p_leak_w"] + table["p_refresh_w"]
    out = []
    for rank, fam in enumerate(pref):
        rows = np.where(ok & (families == fam))[0]
        rows = rows[np.lexsort((table["area_um2"][rows], power[rows]))]
        if policy["candidate_mode"] == "per_family_best":
            rows = rows[:1]
        out.extend((int(r), rank) for r in rows)
    if out and policy["objective"] == "power":
        sys_area, sys_power = _slot_contrib(table, slot)
        out.sort(key=lambda c: (sys_power[c[0]], sys_area[c[0]]))
    elif policy["objective"] != "preference":
        raise NotImplementedError(policy["objective"])
    out = out[:policy["max_candidates_per_bucket"]]
    return out or [(-1, len(pref))]


def trim(lists: List[List], max_compositions: int) -> List[List]:
    """Drop the last candidate of the longest list until the product fits."""
    lists = [list(c) for c in lists]
    while math.prod(len(c) for c in lists) > max_compositions:
        s = max(range(len(lists)), key=lambda s: (len(lists[s]), -s))
        if len(lists[s]) <= 1:
            break
        lists[s].pop()
    return lists


SYSTEM_METRICS = ("area_um2", "p_static_w", "p_dyn_w", "p_w", "bw_margin",
                  "capacity_bits", "overprovision")


def price(nb: Backend, table, slots: Sequence[Slot], idx: np.ndarray):
    """System metrics of compositions ``idx`` (J, S) (-1: no candidate)."""
    xp = nb.xp
    bad = idx < 0
    safe = np.maximum(idx, 0)

    def take(name):
        return nb.c(table[name][safe])

    cap = nb.c([s.capacity_bits for s in slots])[None, :]
    f_req = nb.c([s.f_hz for s in slots])[None, :]
    bits = xp.maximum(take("bits"), 1.0)
    tiles = xp.ceil(cap / bits)
    inf = nb.c(np.inf)
    bad_ = xp.asarray(bad)
    area = xp.sum(nb.where(bad_, inf, tiles * take("area_um2")), axis=1)
    p_static = xp.sum(nb.where(bad_, inf, tiles * (take("p_leak_w")
                                                    + take("p_refresh_w"))),
                      axis=1)
    p_dyn = xp.sum(nb.where(bad_, inf, take("e_read_j") * f_req), axis=1)
    bw = xp.min(nb.where(bad_, 0.0, take("f_op_hz") / xp.maximum(f_req, 1.0)),
                axis=1)
    capacity = xp.sum(nb.where(bad_, 0.0, tiles * bits), axis=1)
    over = capacity / xp.maximum(xp.sum(cap), 1.0)
    out = dict(area_um2=area, p_static_w=p_static, p_dyn_w=p_dyn,
               p_w=p_static + p_dyn, bw_margin=bw, capacity_bits=capacity,
               overprovision=over)
    res = {k: nb.host(v) for k, v in out.items()}
    res["tiles"] = np.where(bad, 0, nb.host(tiles)).astype(np.int64)
    return res


def rank_keys(objective: str, m, rank_sum, feasible) -> Tuple:
    """Ranking keys, most significant first."""
    big = np.finfo(np.float64).max
    area = np.nan_to_num(m["area_um2"], posinf=big)
    infeas = (~feasible).astype(np.int64)
    if objective == "preference":
        return (infeas, rank_sum, np.nan_to_num(m["p_static_w"], posinf=big),
                area)
    if objective == "power":
        return (infeas, np.nan_to_num(m["p_w"], posinf=big), area)
    raise NotImplementedError(objective)


# ---------------------------------------------------------------------------
# trace replay
# ---------------------------------------------------------------------------

SIM_METRICS = ("e_dyn_j", "e_refresh_j", "e_rewrite_j", "e_leak_j",
               "e_total_j", "t_sim_s", "t_wall_s", "collisions", "util_peak",
               "age_peak_s")


def phase_trace(slots: Sequence[Slot], phase: str, duration_s: float,
                n_bins: int):
    """(t_bin (T,), reads (S, T), write_bits (S, T), occupancy (S, T))."""
    cap = np.array([s.capacity_bits for s in slots])
    f_req = np.array([s.f_hz for s in slots])
    life = np.array([s.lifetime_s for s in slots])
    T = n_bins
    t_bin = np.full(T, duration_s / T)
    x = (np.arange(T) + 0.5) / T
    long_lived = (life >= duration_s)[:, None]
    occ = np.ones((len(slots), T))
    env = np.ones((len(slots), T))
    if phase == "prefill":          # long-lived data fills, reads ramp
        occ = np.where(long_lived, x + 0.5 / T, occ)
        env = np.where(long_lived, 2.0 * x + 1.0 / T, env)
    elif phase == "train_step":     # short-lived residuals rise and fall
        tri = np.where(x < 0.5, 2.0 * x, 2.0 * (1.0 - x)) + 0.5 / T
        occ = np.where(long_lived, occ, tri)
        env = np.where(long_lived, env, np.where(x < 0.5, 0.8, 1.2))
    elif phase != "decode":
        raise ValueError(phase)
    occ = np.clip(occ, 0.0, 1.0)
    env = env / env.mean(axis=1, keepdims=True)
    reads = f_req[:, None] * t_bin[None, :] * env
    turnover = occ * cap[:, None] * t_bin[None, :] / life[:, None]
    d_occ = np.diff(occ, axis=1, prepend=occ[:, :1])
    fills = np.maximum(d_occ, 0.0) * cap[:, None]
    return t_bin, reads, turnover + fills, occ


def replay(nb: Backend, table, word_bits: np.ndarray, slots: Sequence[Slot],
           idx: np.ndarray, sim) -> Dict[str, np.ndarray]:
    """Replay each phase against compositions ``idx`` (K, S); energies,
    times and collisions add over phases, peaks take the maximum."""
    xp = nb.xp
    if not sim["refresh"] or sim["adaptive_refresh"] or sim["temp_drift_k"]:
        raise NotImplementedError("only scheduled, fixed-temperature refresh")
    safe = np.maximum(idx, 0)

    def take(col):
        return nb.c(col[safe])

    bits, wbits_col = take(table["bits"]), take(word_bits)
    e_read, e_write = take(table["e_read_j"]), take(table["e_write_j"])
    f_op, p_leak = take(table["f_op_hz"]), take(table["p_leak_w"])
    ret = take(table["retention_s"])
    cap = nb.c([s.capacity_bits for s in slots])[None, :]
    life = nb.c([s.lifetime_s for s in slots])[None, :]
    tiles = xp.ceil(cap / xp.maximum(bits, 1.0))
    words = bits / wbits_col
    interval = sim["refresh_margin"] * ret
    need = nb.where(ret < life, 1.0, 0.0).astype(nb.dtype)
    eps = 1e-30
    total = {m: 0.0 for m in SIM_METRICS}
    for phase in sim["phases"]:
        t_bin, reads, wbits, occ = phase_trace(slots, phase,
                                               sim["duration_s"],
                                               sim["n_bins"])
        K = idx.shape[0]
        age = nb.c(np.zeros(idx.shape))
        acc = {m: nb.c(np.zeros(K)) for m in ("e_dyn_j", "e_refresh_j",
                                               "t_sim_s", "collisions",
                                               "util_peak", "age_peak_s")}
        for t in range(len(t_bin)):
            tb = nb.c(t_bin[t])
            r, wb, oc = (nb.c(a[:, t])[None, :] for a in (reads, wbits, occ))
            wops = wb / wbits_col
            turn = xp.clip(wb / xp.maximum(oc * cap, eps), 0.0, 1.0)
            refr = need * (oc * tiles * words * tb / interval)
            cap_ops = xp.maximum(tiles * f_op * tb, eps)
            util = (r + wops + refr) / cap_ops
            age = (age + tb) * (1.0 - turn)
            acc["e_dyn_j"] = acc["e_dyn_j"] + xp.sum(r * e_read
                                                     + wops * e_write, axis=1)
            acc["e_refresh_j"] = acc["e_refresh_j"] + xp.sum(
                refr * (e_read + e_write), axis=1)
            acc["t_sim_s"] = acc["t_sim_s"] + tb * xp.maximum(
                xp.max(util, axis=1), 1.0)
            acc["collisions"] = acc["collisions"] + xp.sum(
                refr * xp.minimum((r + wops) / cap_ops, 1.0), axis=1)
            acc["util_peak"] = xp.maximum(acc["util_peak"],
                                          xp.max(util, axis=1))
            acc["age_peak_s"] = xp.maximum(acc["age_peak_s"],
                                           xp.max(age, axis=1))
        e_leak = xp.sum(p_leak * tiles, axis=1) * acc["t_sim_s"]
        ph = {m: nb.host(v) for m, v in acc.items()}
        ph["e_leak_j"] = nb.host(e_leak)
        ph["e_rewrite_j"] = np.zeros(K)
        ph["t_wall_s"] = np.full(K, float(t_bin.sum()))
        ph["e_total_j"] = (ph["e_dyn_j"] + ph["e_refresh_j"]
                           + ph["e_rewrite_j"] + ph["e_leak_j"])
        for m in SIM_METRICS:
            if m in ("util_peak", "age_peak_s"):
                total[m] = np.maximum(total[m], ph[m])
            else:
                total[m] = total[m] + ph[m]
    bad = np.any(idx < 0, axis=1)
    for m in SIM_METRICS:
        if m != "t_wall_s":
            total[m] = np.where(bad, np.inf if m.startswith(("e_", "t_sim"))
                                else 0.0, total[m])
    return total


# ---------------------------------------------------------------------------
# one query
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Query:
    """One DSE query: the design space, one task, the compose policy (its
    ``search`` names the engine) and, with ``refine="simulate"``, the
    replay policy."""
    configs: Tuple[Dict[str, object], ...]
    task: Dict[str, object]
    policy: Dict[str, object]
    refine: Optional[str] = None
    sim: Optional[Dict[str, object]] = None


@dataclass
class Context:
    """The reference's view of a query's search space: the base table, the
    metric columns of every (operating point, margin) block stacked in
    block order (row ``block * n + config``), and the slots."""
    query: Query
    base: Dict[str, np.ndarray]
    table: Dict[str, np.ndarray]
    families: np.ndarray
    word_bits: np.ndarray
    points: List
    slots: List[Slot]


@dataclass
class Answer:
    """A query's result in the form both the program's report and the
    reference reduce to: the base table, the ranked compositions as rows of
    ``Context.table`` per slot (-1: no candidate), their tiles and metrics
    (system metrics, and ``sim_*`` replay metrics after a re-rank), and the
    level labels of the best composition."""
    table: Dict[str, np.ndarray]
    ranked: np.ndarray
    tiles: np.ndarray
    metrics: List[Dict[str, float]]
    labels: Dict[str, str]


def sweep_points(policy) -> List[Tuple[Optional[Tuple[float, float]],
                                       Optional[float]]]:
    """The (operating point, refresh margin) blocks: the base point and
    analytic refresh first, then every swept point at every margin."""
    vdds = [None] + [tuple(p) for p in policy.get("vdd_sweep", [])]
    margins = [None] + list(policy.get("refresh_margin_sweep", []))
    return [(v, m) for v in vdds for m in margins]


def context(nb: Backend, q: Query) -> Context:
    configs = list(q.configs)
    base = characterize(nb, configs)
    points = sweep_points(q.policy)
    at_op, blocks = {}, []
    for op, margin in points:
        if op is None:
            tab = dict(base)
        else:
            if op not in at_op:
                at_op[op] = characterize(nb, configs, vdd=op[0],
                                         temp_k=op[1])
            tab = dict(at_op[op])
        if margin is not None:          # 1/margin as many refreshes
            tab["p_refresh_w"] = tab["p_refresh_w"] / margin
        blocks.append(tab)
    fam = np.array([FAMILY[c["mem_type"]] for c in configs])
    wb = np.array([float(c["word_size"]) for c in configs])
    return Context(query=q, base=base,
                   table={k: np.concatenate([b[k] for b in blocks])
                          for k in CHAR_COLUMNS},
                   families=np.concatenate([fam] * len(points)),
                   word_bits=np.concatenate([wb] * len(points)),
                   points=points, slots=task_slots(q.task))


def pref_rank_sum(ctx: Context, idx: np.ndarray) -> np.ndarray:
    pref = list(ctx.query.policy["preference"])
    rank = np.array([pref.index(f) if f in pref else len(pref)
                     for f in ctx.families] + [len(pref)])
    return rank[np.where(idx < 0, len(ctx.families), idx)].sum(axis=1)


def evaluate(nb: Backend, ctx: Context, idx: np.ndarray):
    """Price compositions ``idx`` (K, S) of ``ctx``: (metrics per
    composition, tiles (K, S), ranking keys per composition)."""
    q = ctx.query
    m = price(nb, ctx.table, ctx.slots, idx)
    metrics = [{k: float(m[k][j]) for k in SYSTEM_METRICS}
               for j in range(len(idx))]
    feas = np.all(idx >= 0, axis=1)
    rank_sum = pref_rank_sum(ctx, idx)
    keys = rank_keys(q.policy["objective"], m, rank_sum, feas)
    if q.refine == "simulate":
        rep = replay(nb, ctx.table, ctx.word_bits, ctx.slots, idx, q.sim)
        for j, mm in enumerate(metrics):
            mm.update({f"sim_{k}": float(rep[k][j]) for k in SIM_METRICS})
        keys = replay_keys(q.policy["objective"], q.sim, m, rep, rank_sum,
                           feas)
    return metrics, m["tiles"], [tuple(k[j] for k in keys)
                                 for j in range(len(idx))]


def replay_keys(objective, sim, m, rep, rank_sum, feas) -> Tuple:
    """Re-rank keys after a replay, most significant first: the replayed
    energy takes the place of the analytic power, its time breaks ties."""
    big = np.finfo(np.float64).max
    if sim["objective"] != "energy":
        raise NotImplementedError(sim["objective"])
    e = np.nan_to_num(rep["e_total_j"], posinf=big)
    t = np.nan_to_num(rep["t_sim_s"], posinf=big)
    area = np.nan_to_num(m["area_um2"], posinf=big)
    infeas = (~feas).astype(np.int64)
    if objective == "preference":
        return (infeas, rank_sum, e, t, area)
    if objective == "power":
        return (infeas, e, t, area)
    raise NotImplementedError(objective)


def _lexorder(keys: Tuple, pos: np.ndarray) -> np.ndarray:
    """Order by the keys (most significant first), then by list position
    with slot 0 most significant."""
    ties = tuple(pos[:, s] for s in reversed(range(pos.shape[1])))
    return np.lexsort(ties + tuple(reversed(keys)))


def label(families: Sequence[Optional[str]]) -> str:
    seen = []
    for f in families:
        if f and f not in seen:
            seen.append(f)
    return " + ".join(DISPLAY[f] for f in seen) if seen else "infeasible"


def answer(nb: Backend, q: Query) -> Tuple[Answer, Context]:
    """The reference's answer to one query.

    The ranked list is the top-k of the candidate product: of the policy's
    trimmed grid for ``search="exhaustive"``, of the whole product for
    ``"branch_and_bound"``, which a lossless search must reproduce; "auto"
    is branch-and-bound where the product exceeds ``search_threshold``. Under
    the "power" objective each slot's list is sorted by the slot's share of
    the system power, which the composition's power sums; a composition
    that uses a slot's (k+1)-th candidate or later is then beaten by the k
    compositions that swap it for one of the first k, so only the first k
    candidates of each slot can reach the top k, and only those are priced.
    """
    ctx = context(nb, q)
    pol = q.policy
    top_k = pol["top_k"]
    lists = [candidates(ctx.table, ctx.families, s, pol) for s in ctx.slots]
    search = pol["search"]
    if search == "auto":
        search = "branch_and_bound" if math.prod(len(c) for c in lists) \
            > pol["search_threshold"] else "exhaustive"
    if search == "exhaustive":
        lists = trim(lists, pol["max_compositions"])
    if pol["objective"] == "power":
        lists = [c[:top_k] for c in lists]
    counts = [len(c) for c in lists]
    pos = np.stack(np.unravel_index(np.arange(math.prod(counts)), counts), 1)
    idx = np.stack([np.array([c[0] for c in cl])[pos[:, s]]
                    for s, cl in enumerate(lists)], axis=1)
    m = price(nb, ctx.table, ctx.slots, idx)
    keys = rank_keys(pol["objective"], m, pref_rank_sum(ctx, idx),
                     np.all(idx >= 0, axis=1))
    idx = idx[_lexorder(keys, pos)[:top_k]]
    metrics, tiles, keys = evaluate(nb, ctx, idx)
    if q.refine == "simulate":
        order = np.lexsort(tuple(reversed(tuple(zip(*keys)))))
        idx, tiles = idx[order], tiles[order]
        metrics = [metrics[j] for j in order]
    labels: Dict[str, List] = {}
    for s, slot in enumerate(ctx.slots):
        r = idx[0, s]
        labels.setdefault(slot.level, []).append(
            None if r < 0 else ctx.families[r])
    return Answer(table=ctx.base, ranked=idx, tiles=tiles, metrics=metrics,
                  labels={k: label(v) for k, v in labels.items()}), ctx
