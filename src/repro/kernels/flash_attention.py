"""Pallas TPU kernel: flash-attention forward (serving prefill hot-spot).

Grid (B*H, nq, nk) with the kv dimension innermost/"arbitrary": the online
softmax state (m, l, acc) lives in VMEM scratch across kv iterations and the
output tile is emitted on the last kv step. Causal masking is applied at
block granularity (off-diagonal blocks need no mask; blocks strictly above
the diagonal are skipped with @pl.when).

Tile sizes default to (128, 128) q x kv — MXU-aligned for head_dim >= 64.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -0.7 * jnp.finfo(jnp.float32).max


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                              # (bq, d)
        k = k_ref[0]                              # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret=False):
    """q,k,v (B,H,S,D) -> (B,H,S,D). Forward-only (serving path)."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    # D is a static shape int: host math, no device round-trip (the previous
    # float(jnp.sqrt(...)) forced a sync before the kernel even launched)
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    assert S % block_q == 0 and Sk % block_k == 0, "pad sequence to block size"
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    grid = (B * H, S // block_q, Sk // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
