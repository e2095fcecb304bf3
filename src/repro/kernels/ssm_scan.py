"""Pallas TPU kernel: chunked selective-scan (hymba SSM heads / mamba-style).

Grid (B, di_blocks, n_chunks) with the chunk dimension innermost and
"arbitrary": the recurrent state h (di_blk, n) lives in VMEM scratch across
chunk iterations; within a chunk the T timesteps run as a fori_loop of
VPU-width (di_blk, n) updates. HBM traffic per program = the (T, di_blk)
x/dt tiles + (T, n) B/C tiles + (T, di_blk) y tile out — the sequential
dependency never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_D = 512
CHUNK_T = 128


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_scr, *,
                chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr[...])

    a_log = a_ref[...]                      # (di_blk, n)
    d_coef = d_ref[...]                     # (1, di_blk)

    def body(t, h):
        x_t = x_ref[0, t, :]                # (di_blk,)
        dt_t = dt_ref[0, t, :]
        b_t = b_ref[0, t, :]                # (n,)
        c_t = c_ref[0, t, :]
        a = jnp.exp(dt_t[:, None] * a_log)  # (di_blk, n)
        h = a * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y = jnp.sum(h * c_t[None, :], axis=-1) + d_coef[0] * x_t
        y_ref[0, t, :] = y
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, body, h_scr[...])


def ssm_scan_pallas(x, dt, A, Bc, Cc, D, *, block_d=BLOCK_D, chunk=CHUNK_T,
                    interpret=False):
    """x/dt (B,S,di) fp32; Bc/Cc (B,S,n); A (di,n); D (di,) -> y (B,S,di).

    h0 = 0 (training/prefill path; decode uses the single-step jnp update)."""
    B, S, di = x.shape
    n = A.shape[1]
    block_d = min(block_d, di)
    chunk = min(chunk, S)
    assert di % block_d == 0 and S % chunk == 0, "pad di/S to block size"
    grid = (B, di // block_d, S // chunk)
    y = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((block_d, n), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, block_d), lambda b, d, c: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.astype(jnp.float32), dt.astype(jnp.float32), Bc.astype(jnp.float32),
      Cc.astype(jnp.float32), A.astype(jnp.float32),
      D[None, :].astype(jnp.float32))
    return y
