"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitcells, retention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref, retention_ref, ssm_scan_ref
from repro.kernels.retention_kernel import retention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


@pytest.mark.parametrize("shape", [(1, 2, 256, 64), (2, 1, 128, 128),
                                   (1, 4, 512, 64), (2, 2, 256, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, dtype, causal):
    B, H, S, D = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=shape), dtype)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    o = flash_attention(q, k, v, causal=causal, interpret=True)
    o_ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bk", [(128, 128), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(bq, bk):
    B, H, S, D = 1, 2, 256, 64
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(
        attention_ref(q, k, v)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,di,n", [(1, 128, 256, 16), (2, 256, 512, 8),
                                      (1, 64, 1024, 16)])
def test_ssm_scan_matches_ref(B, S, di, n):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, S, di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(di, n)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(di,)), jnp.float32)
    y = ssm_scan_pallas(x, dt, A, Bc, Cc, D, block_d=min(256, di),
                        chunk=min(64, S), interpret=True)
    y_ref, _ = ssm_scan_ref(x, dt, A, Bc, Cc, D, jnp.zeros((B, di, n)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssm_scan_chunk_invariance():
    """Result must not depend on the chunk partitioning."""
    rng = np.random.default_rng(3)
    B, S, di, n = 1, 128, 256, 8
    x = jnp.asarray(rng.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, S, di)), jnp.float32)
    A = -jnp.ones((di, n), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    D = jnp.zeros((di,), jnp.float32)
    y1 = ssm_scan_pallas(x, dt, A, Bc, Cc, D, chunk=32, interpret=True)
    y2 = ssm_scan_pallas(x, dt, A, Bc, Cc, D, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)


def _pack_cells(names, ls):
    return jnp.asarray([retention.pack_cell(name, ls) for name in names],
                       jnp.float32)


def test_retention_kernel_matches_ref_and_core():
    names = ["gc_sisi", "gc_sisi_hvt", "gc_ossi", "gc_ossi_hvt", "gc_osos"]
    ts = retention.time_grid()
    for ls in (0, 1):
        p = _pack_cells(names, ls)
        t_k = retention_pallas(p, ts, interpret=True)
        t_r = retention_ref(p, ts)
        np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r),
                                   rtol=1e-5)
        # and both match the core solver (same physics, structured API)
        for i, name in enumerate(names):
            t_core = float(retention.retention_time(bitcells.BITCELLS[name], ls))
            if t_core <= 2e-9:      # unwritable corner (HVT without LS)
                continue
            assert abs(np.log(float(t_r[i]) / t_core)) < 0.2, (name, ls)


def test_retention_kernel_padding():
    """Non-multiple-of-128 batch sizes are padded correctly."""
    ts = retention.time_grid()
    p = _pack_cells(["gc_sisi", "gc_ossi", "gc_osos"], 0)
    t3 = retention_pallas(p, ts, interpret=True)
    t1 = retention_pallas(p[:1], ts, interpret=True)
    np.testing.assert_allclose(np.asarray(t3[:1]), np.asarray(t1), rtol=1e-6)
