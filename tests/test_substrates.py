"""Data pipeline, optimizer, checkpointing, supervisor, compression."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev extra: property tests skip where absent, unit tests run
from _hypothesis_compat import given, settings, st

from repro.checkpoint.ckpt import Checkpointer
from repro.configs import get_config, reduce_config
from repro.data.pipeline import SyntheticLMData
from repro.optim.adamw import (AdamWConfig, adamw_init, adamw_update,
                               cosine_schedule)
from repro.parallel.compression import ef_int8_psum_mean, init_residuals
from repro.runtime.supervisor import Supervisor, SupervisorConfig
from repro.train.step import init_train_state, make_train_step

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


def tiny_cfg():
    return reduce_config(get_config("internlm2-1.8b")).replace(num_layers=2)


# ----------------------------------------------------------------------- data
def test_data_deterministic_and_resumable():
    cfg = tiny_cfg()
    d1 = SyntheticLMData(cfg, 2, 16, seed=7)
    ref = [d1.next_batch()["tokens"] for _ in range(5)]
    d2 = SyntheticLMData(cfg, 2, 16, seed=7)
    d2.next_batch(), d2.next_batch()
    d2.state.step = 3                      # resume mid-stream
    np.testing.assert_array_equal(d2.next_batch()["tokens"], ref[3])


# ------------------------------------------------------------------ optimizer
def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    acfg = AdamWConfig(weight_decay=0.0)
    state = adamw_init(params, acfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(grads, state, params, 0.05, acfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_int8_opt_state_tracks_fp32():
    """int8 moments: single-step drift bounded by quantization resolution and
    the optimizer still minimizes (the property that matters)."""
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    acfg8 = AdamWConfig(quantized=True, weight_decay=0.0)
    acfg32 = AdamWConfig(weight_decay=0.0)
    s32 = adamw_init(params, acfg32)
    s8 = adamw_init(params, acfg8)
    assert isinstance(s8["m"]["w"], dict)          # quantized layout
    p32, s32, _ = adamw_update(g, s32, dict(params), 1e-2, acfg32)
    p8, s8, _ = adamw_update(g, s8, dict(params), 1e-2, acfg8)
    assert float(jnp.max(jnp.abs(p32["w"] - p8["w"]))) < 2e-3
    # and the quantized optimizer converges on a quadratic
    p = {"w": jnp.asarray([4.0, -2.0])}
    s = adamw_init(p, acfg8)
    for _ in range(300):
        p, s, _ = adamw_update({"w": 2 * p["w"]}, s, p, 0.05, acfg8)
    assert float(jnp.abs(p["w"]).max()) < 0.1


@given(st.integers(1, 10_000))
def test_cosine_schedule_bounds(step):
    lr = cosine_schedule(1e-3, warmup=100, total=10_000)
    v = float(lr(jnp.asarray(step)))
    assert 0.0 <= v <= 1e-3 + 1e-9


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    cfg = tiny_cfg()
    params, opt = init_train_state(cfg, jax.random.key(0))
    ck = Checkpointer(tmp_path, keep=2, async_write=False)
    for s in (10, 20, 30):
        ck.save(s, params, opt, {"seed": 7, "step": s})
    assert sorted(ck.steps()) == [20, 30]          # gc keeps last 2
    step, p2, o2, ds = ck.restore(params_template=params, opt_template=opt)
    assert step == 30 and ds["step"] == 30
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_checksum_detects_corruption(tmp_path):
    cfg = tiny_cfg()
    params, opt = init_train_state(cfg, jax.random.key(0))
    ck = Checkpointer(tmp_path, async_write=False)
    ck.save(1, params, opt, {"seed": 0, "step": 1})
    man = json.loads((tmp_path / "step_1" / "manifest.json").read_text())
    man["params_sha256"] = "0" * 64
    (tmp_path / "step_1" / "manifest.json").write_text(json.dumps(man))
    with pytest.raises(IOError):
        ck.restore(params_template=params, opt_template=opt)


def test_checkpoint_reshard_on_restore(tmp_path):
    """Restore places leaves with target-mesh shardings (elastic restart)."""
    from repro.compat import make_mesh, replicated_like
    cfg = tiny_cfg()
    params, opt = init_train_state(cfg, jax.random.key(0))
    ck = Checkpointer(tmp_path, async_write=False)
    ck.save(5, params, opt, {"seed": 0, "step": 5})
    mesh = make_mesh((1,), ("data",))
    _, p2, _, _ = ck.restore(params_template=params, opt_template=opt,
                             shardings=(replicated_like(mesh, params),
                                        replicated_like(mesh, opt)))
    leaf = jax.tree.leaves(p2)[0]
    assert leaf.sharding.mesh.axis_names == ("data",)
    # mesh= alone must reshard too (previously a silently-ignored kwarg)
    _, p3, _, _ = ck.restore(params_template=params, opt_template=opt,
                             mesh=mesh)
    assert jax.tree.leaves(p3)[0].sharding.mesh.axis_names == ("data",)


# ------------------------------------------------------------------ supervisor
def test_supervisor_recovers_from_injected_failures(tmp_path):
    cfg = tiny_cfg()
    lm, step_fn = make_train_step(cfg, base_lr=1e-3, total_steps=40)
    step_fn = jax.jit(step_fn)
    params, opt = init_train_state(cfg, jax.random.key(0))
    data = SyntheticLMData(cfg, 2, 16, seed=3)
    crashed = {"done": False}

    def inject(step):
        if step == 12 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("simulated node failure")

    sup = Supervisor(step_fn, Checkpointer(tmp_path, async_write=False),
                     SupervisorConfig(ckpt_every=5, max_restarts=2),
                     failure_injector=inject)
    params, opt, report = sup.run(params, opt, data, total_steps=20)
    assert report.restarts == 1
    assert report.steps_run >= 20
    assert all(np.isfinite(report.losses))


def test_supervisor_detects_stragglers(tmp_path):
    cfg = tiny_cfg()
    _, step_fn = make_train_step(cfg, base_lr=1e-3, total_steps=40)
    step_fn = jax.jit(step_fn)
    params, opt = init_train_state(cfg, jax.random.key(0))
    data = SyntheticLMData(cfg, 2, 16, seed=3)

    def slow(step):
        return 0.6 if step == 15 else 0.0

    sup = Supervisor(step_fn, Checkpointer(tmp_path, async_write=False),
                     SupervisorConfig(ckpt_every=100, straggler_factor=3.0),
                     straggler_injector=slow)
    _, _, report = sup.run(params, opt, data, total_steps=18)
    assert 15 in report.straggler_events


# ----------------------------------------------------------------- compression
def test_ef_int8_psum_single_axis():
    """On a size-1 axis the compressed mean must equal plain quantization,
    and error feedback must carry the residual exactly."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh
    mesh = make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)}
    r = init_residuals(g)

    def f(g, r):
        return ef_int8_psum_mean(g, r, "data")

    mean, resid = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                    out_specs=(P(), P())))(g, r)
    np.testing.assert_allclose(np.asarray(mean["w"] + resid["w"]),
                               np.asarray(g["w"]), rtol=1e-6, atol=1e-6)
    # quantization error bounded by scale/2
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.max(jnp.abs(resid["w"]))) <= scale * 0.5 + 1e-7


def test_ef_int8_bias_vanishes_over_steps():
    """Accumulated compressed updates converge to accumulated true updates."""
    rng = np.random.default_rng(1)
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh
    mesh = make_mesh((1,), ("data",))
    g_seq = [jnp.asarray(rng.normal(size=(16,)), jnp.float32) for _ in range(50)]
    r = {"w": jnp.zeros((16,))}
    acc_c = jnp.zeros((16,))
    acc_t = jnp.zeros((16,))
    f = jax.jit(jax.shard_map(lambda g, r: ef_int8_psum_mean(g, r, "data"),
                          mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))
    for g in g_seq:
        mean, r = f({"w": g}, r)
        acc_c = acc_c + mean["w"]
        acc_t = acc_t + g
    # EF guarantees sum of compressed means = sum of true grads - final resid
    np.testing.assert_allclose(np.asarray(acc_c + r["w"]), np.asarray(acc_t),
                               rtol=1e-5, atol=1e-5)
