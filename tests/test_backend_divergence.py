"""Interpret-vs-xla divergence sweep over every registered backend op.

``test_kernels.py`` proves each kernel against its oracle on hand-picked
shapes; this sweep closes the registry-level gap: every op that registers
BOTH an ``interpret`` and an ``xla`` implementation is driven through both
on the same inputs and compared under a per-op tolerance budget. A new op
cannot land without a builder here (``test_every_registered_op_has_builder``
fails), so silent interpret/xla divergence has nowhere to hide.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.hetero.system  # noqa: F401  (registers the compose_score op)
import repro.sim.engine  # noqa: F401  (registers the sim_replay op)
from repro.core import bitcells, retention
from repro.kernels import backend, ops  # noqa: F401  (registers kernel ops)


def _pack_cells(names, ls):
    return jnp.asarray([retention.pack_cell(name, ls) for name in names],
                       jnp.float32)


def _attention_inputs():
    rng = np.random.default_rng(11)
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    return (q, k, v), {"causal": True}


def _ssm_inputs():
    # di = 512, S = 128: divisible by the kernel's default block sizes, so
    # the same positional args drive both impls with no backend-only kwargs
    rng = np.random.default_rng(12)
    B, S, di, n = 1, 128, 512, 8
    x = jnp.asarray(rng.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, S, di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(di, n)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(di,)), jnp.float32)
    return (x, dt, A, Bc, Cc, D), {}


def _retention_inputs():
    params = _pack_cells(sorted(bitcells.BITCELLS), ls=0)
    ts = jnp.asarray(retention.time_grid(), jnp.float32)
    return (params, ts), {}


def _sim_replay_inputs():
    rng = np.random.default_rng(13)
    J, S, T = 3, 2, 8
    base = {"bits": 4096.0, "word_bits": 32.0, "e_read_j": 1e-12,
            "e_write_j": 2e-12, "f_op_hz": 1e9, "p_leak_w": 1e-6,
            "retention_s": 1e-3, "tiles": 4.0, "interval_s": 5e-4}
    params = {k: jnp.asarray(v * rng.uniform(0.5, 1.5, (J, S)), jnp.float32)
              for k, v in base.items()}
    slot = {"cap_bits": jnp.full((S,), 1e6, jnp.float32),
            "lifetime_s": jnp.full((S,), 1e-2, jnp.float32)}
    xs = (jnp.full((T,), 1e-5, jnp.float32),
          jnp.asarray(rng.uniform(0, 100, (T, S)), jnp.float32),
          jnp.asarray(rng.uniform(0, 512, (T, S)), jnp.float32),
          jnp.asarray(rng.uniform(0, 1, (T, S)), jnp.float32))
    # drift + adaptive controller active so the divergence sweep exercises
    # the in-scan Arrhenius/turnover terms too (T = 8 bins x 1e-5 s window)
    consts = jnp.asarray([1.0, 2.0, 1.0, 45.0, 8e-5], jnp.float32)
    return (params, slot, xs, consts), {}


def _compose_score_inputs():
    from repro.hetero.system import METRIC_COLS
    rng = np.random.default_rng(14)
    n, J, S = 12, 9, 3
    scale = {"area_um2": 1e4, "bits": 65536.0, "p_leak_w": 1e-5,
             "p_refresh_w": 1e-6, "e_read_j": 1e-12, "f_op_hz": 2e9}
    cols = {k: jnp.asarray(scale[k] * rng.uniform(0.5, 1.5, n), jnp.float32)
            for k in METRIC_COLS}
    idx = rng.integers(0, n, (J, S)).astype(np.int32)
    idx[-1, 1] = -1         # a sentinel slot: both impls must price it +inf
    cap = jnp.asarray([1e6, 4e6, 2e5], jnp.float32)
    f_req = jnp.asarray([1.5e9, 4e8, 8e8], jnp.float32)
    return (jnp.asarray(idx), cols, cap, f_req), {}


# op -> (input builder, rtol/atol budget). sim_replay's interpret path is a
# Python loop over the very scan the xla path vmaps, so it must agree to
# float32 roundoff; the Pallas kernels accumulate in different block orders
# and get the same budgets the oracle tests use.
BUILDERS = {
    "attention": (_attention_inputs, 2e-5),
    "ssm_scan": (_ssm_inputs, 1e-4),
    "retention": (_retention_inputs, 1e-5),
    "sim_replay": (_sim_replay_inputs, 1e-6),
    # numpy float32 mirror of the one-dispatch gather/reduce scorer: same
    # dtype, same reduction order (axis-1 sums) — float32 roundoff only
    "compose_score": (_compose_score_inputs, 1e-6),
}


def test_every_registered_op_has_builder():
    missing = [op for op in backend.registered() if op not in BUILDERS]
    assert not missing, (
        f"registered op(s) {missing} have no divergence builder — add them "
        f"to BUILDERS in {__file__}")


def _as_arrays(out):
    if isinstance(out, dict):
        return {k: np.asarray(v, np.float64) for k, v in sorted(out.items())}
    return {"out": np.asarray(out, np.float64)}


@pytest.mark.parametrize("op", sorted(BUILDERS))
def test_interpret_matches_xla(op):
    impls = backend.impl_map(op)
    if not {"interpret", "xla"} <= set(impls):
        pytest.skip(f"{op}: needs both interpret and xla impls "
                    f"(has {sorted(impls)})")
    build, tol = BUILDERS[op]
    args, kwargs = build()
    got = _as_arrays(impls["interpret"](*args, **kwargs))
    want = _as_arrays(impls["xla"](*args, **kwargs))
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=tol, atol=tol,
                                   err_msg=f"{op}[{key}]")
