"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

UT = 0.02585
_NEG = -0.7 * jnp.finfo(jnp.float32).max


# ---------------------------------------------------------------------------
# retention transient (oracle for retention_kernel)
# ---------------------------------------------------------------------------
# packed config rows (repro.core.retention.pack_cell):
# [vt, n, ispec, eta, i_floor, jg_coef, c_sn, w, v0, v_min]
N_FIELDS = 10


def _F(u):
    sp = jnp.where(u > 40.0, u / 2.0, jnp.log1p(jnp.exp(jnp.minimum(u / 2.0, 40.0))))
    return sp * sp


def _leak(p, v):
    vt, n, ispec, eta, i_floor, jg, c_sn, w = (p[..., i] for i in range(8))
    vt_eff = vt - eta * v
    nut = n * UT
    i_ch = ispec * (_F((0.0 - vt_eff) / nut) - _F((0.0 - vt_eff - n * v) / nut))
    return (jnp.maximum(i_ch, 0.0) + i_floor) * w + jg * v


def retention_ref(params, ts):
    """params (B, 10), ts (N+1,) log grid -> retention times (B,).

    RK4 + first-crossing with log-linear interpolation (same discretization
    as the Pallas kernel)."""
    v = params[:, 8]
    v_min = params[:, 9]
    c_sn = params[:, 6]

    def f(v):
        return -_leak(params, jnp.maximum(v, 0.0)) / jnp.maximum(c_sn, 1e-18)

    def step(carry, i):
        v, t_ret, found = carry
        dt = ts[i + 1] - ts[i]
        k1 = f(v)
        k2 = f(v + 0.5 * dt * k1)
        k3 = f(v + 0.5 * dt * k2)
        k4 = f(v + dt * k3)
        v_new = jnp.clip(v + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4), 0.0, 2.0)
        crossed = (v_new < v_min) & (~found)
        frac = jnp.clip((v - v_min) / jnp.maximum(v - v_new, 1e-9), 0.0, 1.0)
        t_cross = jnp.exp(jnp.log(ts[i]) + frac *
                          (jnp.log(ts[i + 1]) - jnp.log(ts[i])))
        t_ret = jnp.where(crossed, t_cross, t_ret)
        return (v_new, t_ret, found | crossed), None

    n = ts.shape[0] - 1
    init = (v, jnp.full_like(v, ts[-1]), v < v_min)
    (v, t_ret, found), _ = jax.lax.scan(step, init, jnp.arange(n))
    return t_ret


# ---------------------------------------------------------------------------
# flash attention forward (oracle)
# ---------------------------------------------------------------------------


def attention_ref(q, k, v, causal=True, scale=None):
    """q,k,v (B,H,S,D) -> (B,H,S,D), fp32 softmax."""
    B, H, S, D = q.shape
    scale = scale or 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, k.shape[2]), bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# selective scan (oracle)
# ---------------------------------------------------------------------------


def ssm_scan_ref(x, dt, A, Bc, Cc, D, h0):
    """Sequential reference. x/dt (B,S,di); Bc/Cc (B,S,n); A (di,n); D (di,);
    h0 (B,di,n) -> (y (B,S,di), h_final)."""

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        a = jnp.exp(dt_t[..., None] * A)
        h = a * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t
        return h, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bc, Cc))
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h
