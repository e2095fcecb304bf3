"""Transient retention solver: storage-node decay of a stored '1'.

   C_SN * dV/dt = -[ I_sub(write dev, vgs=0, vds=V) + I_gate(read dev, V) ]

integrated with RK4 on a log-spaced grid (1 ns .. 1e7 s, 30 pts/decade) —
the SPICE transient the paper runs per configuration. Retention time is the
crossing of V below V0 - RETENTION_DV_FRAC*VDD (read-margin criterion).

The pure-jnp scan here is the oracle for the Pallas kernel in
``repro.kernels.retention_kernel`` (same grid, same RK4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import bitcells, corners, devices, tech

T_START, T_END, PTS_PER_DECADE = 1e-9, 1e7, 30
# plain math, not jnp: computing this with jnp.log10 dispatched device work
# (and possibly platform init) at import time for a compile-time constant
N_STEPS = int(PTS_PER_DECADE * (math.log10(T_END) - math.log10(T_START)))  # 480


def time_grid():
    return jnp.logspace(jnp.log10(T_START), jnp.log10(T_END), N_STEPS + 1)


def leak_current(cell: bitcells.BitcellParams, v_sn, tp=None):
    """Total leakage pulling the stored '1' down [A] (WBL held at 0V worst
    case: write-device subthreshold + DIBL, plus read-device gate leak).
    ``tp`` = operating corner: subthreshold leakage grows with the thermal
    voltage and the Arrhenius floor, gate leak with ``leak_scale``."""
    tp = corners.resolve(tp)
    wdev = devices.take_device(bitcells.DEVICE_STACK,
                               cell.write_dev.astype(jnp.int32))
    rdev = devices.take_device(bitcells.DEVICE_STACK,
                               cell.read_dev.astype(jnp.int32))
    i_sub_a = devices.mosfet_id(wdev, 0.0, v_sn, cell.w_write, tp)
    i_gate_a = rdev.j_gate * tp.leak_scale * cell.w_read * (v_sn / tp.vdd)
    return i_sub_a + i_gate_a


def decay_curve(cell: bitcells.BitcellParams, v0, tp=None):
    """V_SN(t) on the log grid via RK4. Returns (ts, vs)."""
    tp = corners.resolve(tp)
    ts = time_grid()

    def f(v):
        return -leak_current(cell, jnp.maximum(v, 0.0), tp) / jnp.maximum(
            cell.c_sn, 1e-18)

    def step(v, dt):
        k1 = f(v)
        k2 = f(v + 0.5 * dt * k1)
        k3 = f(v + 0.5 * dt * k2)
        k4 = f(v + dt * k3)
        v_new_v = v + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
        return jnp.clip(v_new_v, 0.0, 2.0), v_new_v

    dts = jnp.diff(ts)
    _, vs = jax.lax.scan(step, jnp.asarray(v0, jnp.float32), dts)
    return ts, jnp.concatenate([jnp.asarray([v0], jnp.float32), vs])


def read_margin_threshold(cell: bitcells.BitcellParams,
                          false_read_ratio: float = 0.1, tp=None):
    """Absolute SN voltage below which a stored '1' starts to conduct the
    (PMOS, gate=SN) read device at > ratio x the stored-'0' current — i.e.
    the point where the '1' reads as '0'.

    This absolute criterion is what makes the WWL level shifter *improve*
    retention (paper Fig 9c): it raises the stored level from VDD-VT to VDD,
    widening the droop window to the same threshold."""
    tp = corners.resolve(tp)
    rdev = devices.take_device(bitcells.DEVICE_STACK,
                               cell.read_dev.astype(jnp.int32))
    grid = jnp.linspace(0.0, tp.vdd, 256)
    # |vgs| of the read device when SN sits at v: VDD - v
    i_read_a = devices.mosfet_id(rdev, tp.vdd - grid, tp.vdd, cell.w_read, tp)
    i_on0_a = devices.mosfet_id(rdev, tp.vdd, tp.vdd, cell.w_read, tp)
    ok = i_read_a <= false_read_ratio * i_on0_a          # high-enough SN region
    # lowest v on the grid that is still a safe '1'
    idx = jnp.argmax(ok)                             # first True
    return grid[idx]


def retention_time(cell: bitcells.BitcellParams, level_shift=0, tp=None):
    """Seconds until the stored '1' droops below the read-margin threshold.
    ``tp`` = operating corner: hotter corners leak harder (shorter
    retention), higher vdd stores a higher level (longer retention)."""
    tp = corners.resolve(tp)
    v0 = bitcells.sn_high_level(cell, level_shift, tp)
    ts, vs = decay_curve(cell, v0, tp)
    v_min_v = read_margin_threshold(cell, tp=tp)
    crossed = vs < v_min_v
    idx = jnp.argmax(crossed)                       # first crossing (0 if none)
    any_cross = jnp.any(crossed)
    # log-linear interpolation between grid points
    i0 = jnp.maximum(idx - 1, 0)
    t0, t1 = ts[i0], ts[idx]
    v_hi_v, v_lo_v = vs[i0], vs[idx]
    frac = jnp.clip((v_hi_v - v_min_v) / jnp.maximum(v_hi_v - v_lo_v, 1e-9),
                    0.0, 1.0)
    t_cross_s = jnp.exp(jnp.log(t0) + frac * (jnp.log(t1) - jnp.log(t0)))
    return jnp.where(any_cross, t_cross_s, ts[-1])


def retention_estimate(cell: bitcells.BitcellParams, level_shift=0, tp=None):
    """Closed-form sanity estimate t ~ C*dV/I_leak(V0) (first-order; the
    transient solve is more accurate because I_sub varies with V)."""
    tp = corners.resolve(tp)
    v0 = bitcells.sn_high_level(cell, level_shift, tp)
    dv = jnp.maximum(v0 - read_margin_threshold(cell, tp=tp), 0.0)
    i0 = leak_current(cell, v0, tp)
    return cell.c_sn * dv / jnp.maximum(i0, 1e-30)


def pack_cell(name: str, level_shift: int) -> list:
    """One row of the Pallas retention kernel's packed parameters
    (``repro.kernels.ref.N_FIELDS``) for bitcell ``name`` (a key of
    ``bitcells.BITCELLS``) at the nominal corner: the write device's
    subthreshold leak, the read device's gate leak, the storage node, and
    the written level and read-margin threshold."""
    c = bitcells.BITCELLS[name]
    wd = devices.take_device(bitcells.DEVICE_STACK, int(c.write_dev))
    rd = devices.take_device(bitcells.DEVICE_STACK, int(c.read_dev))
    return [float(wd.vt), float(wd.n), float(wd.ispec), float(wd.eta_dibl),
            float(wd.i_floor), float(rd.j_gate * c.w_read / tech.VDD),
            float(c.c_sn), float(c.w_write),
            float(bitcells.sn_high_level(c, level_shift)),
            float(read_margin_threshold(c))]


retention_time_batch = jax.jit(jax.vmap(retention_time, in_axes=(0, 0)))
