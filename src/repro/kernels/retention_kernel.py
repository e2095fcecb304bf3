"""Pallas TPU kernel: batched transient retention simulation.

This is OpenGCRAM's characterization hot loop — one SPICE transient per
(device x VT x cap x sizing) configuration, embarrassingly parallel across
the design space. The TPU mapping tiles 128 configurations per program into
VMEM and runs the full RK4 log-grid integration on the VPU; HBM traffic is
one (10,128) parameter tile in + one (1,128) retention vector out, so the
kernel is compute-bound by design.

Layout: params (10, B) fp32, B padded to a multiple of 128. The (N+1,) time
grid lives in SMEM, shared by every program: the RK4 loop reads its two
bracketing grid points as scalars (Mosaic cannot dynamically slice a loaded
vector).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import N_FIELDS, UT

BLOCK_B = 128


def _F(u):
    sp = jnp.where(u > 40.0, u / 2.0, jnp.log1p(jnp.exp(jnp.minimum(u / 2.0, 40.0))))
    return sp * sp


def _retention_kernel(params_ref, ts_ref, out_ref, *, n_steps):
    p = params_ref[...]                      # (10, BLOCK_B)
    vt, n, ispec, eta, i_floor, jg, c_sn, w = (p[i] for i in range(8))
    v0, v_min = p[8], p[9]

    def leak(v):
        vt_eff = vt - eta * v
        nut = n * UT
        i_ch = ispec * (_F((0.0 - vt_eff) / nut) - _F((0.0 - vt_eff - n * v) / nut))
        return (jnp.maximum(i_ch, 0.0) + i_floor) * w + jg * v

    def f(v):
        return -leak(jnp.maximum(v, 0.0)) / jnp.maximum(c_sn, 1e-18)

    def body(i, carry):
        v, t_ret, found = carry
        t0 = ts_ref[i]
        t1 = ts_ref[i + 1]
        dt = t1 - t0
        k1 = f(v)
        k2 = f(v + 0.5 * dt * k1)
        k3 = f(v + 0.5 * dt * k2)
        k4 = f(v + dt * k3)
        v_new = jnp.clip(v + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4), 0.0, 2.0)
        crossed = (v_new < v_min) & (found == 0.0)
        frac = jnp.clip((v - v_min) / jnp.maximum(v - v_new, 1e-9), 0.0, 1.0)
        t_cross = jnp.exp(jnp.log(t0) + frac * (jnp.log(t1) - jnp.log(t0)))
        t_ret = jnp.where(crossed, t_cross, t_ret)
        return v_new, t_ret, jnp.where(crossed, 1.0, found)

    # the "already crossed" flag is carried as a 0/1 float: Mosaic cannot
    # carry a boolean vector through the loop
    init = (v0, jnp.full_like(v0, ts_ref[n_steps]),
            jnp.where(v0 < v_min, 1.0, 0.0))
    _, t_ret, _ = jax.lax.fori_loop(0, n_steps, body, init)
    out_ref[...] = t_ret[None, :]


def retention_pallas(params, ts, *, interpret=False):
    """params (B, 10) fp32, ts (N+1,) -> (B,) retention seconds."""
    B = params.shape[0]
    pad = (-B) % BLOCK_B
    p = jnp.pad(params, ((0, pad), (0, 0)),
                constant_values=1.0).T.astype(jnp.float32)   # (10, B')
    Bp = B + pad
    n_steps = ts.shape[0] - 1
    out = pl.pallas_call(
        functools.partial(_retention_kernel, n_steps=n_steps),
        grid=(Bp // BLOCK_B,),
        in_specs=[
            pl.BlockSpec((N_FIELDS, BLOCK_B), lambda i: (0, i)),
            pl.BlockSpec((n_steps + 1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_B), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(p, ts.astype(jnp.float32))
    return out[0, :B]
