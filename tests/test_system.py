"""End-to-end behaviour: training convergence, microbatch equivalence, MoE
balancing, serve engine generation, and the compiler->DSE->accelerator loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data.pipeline import SyntheticLMData
from repro.models import LM
from repro.serve.engine import Engine
from repro.train.step import init_train_state, make_train_step


def test_training_reduces_loss():
    """A tiny dense LM must learn the synthetic bigram structure."""
    cfg = reduce_config(get_config("internlm2-1.8b")).replace(num_layers=2)
    lm, step = make_train_step(cfg, base_lr=3e-3, warmup=10, total_steps=300)
    step = jax.jit(step)
    params, opt = init_train_state(cfg, jax.random.key(0))
    data = SyntheticLMData(cfg, 8, 32, seed=5)
    losses = []
    for i in range(120):
        params, opt, m = step(params, opt, data.next_batch(), i)
        losses.append(float(m["loss"]))
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    assert last < first - 0.5, (first, last)


def test_microbatch_accumulation_matches_full_batch():
    cfg = reduce_config(get_config("internlm2-1.8b")).replace(num_layers=2)
    _, step_full = make_train_step(cfg, base_lr=1e-3)
    _, step_mb = make_train_step(cfg, base_lr=1e-3, microbatch=2)
    params, opt = init_train_state(cfg, jax.random.key(1))
    data = SyntheticLMData(cfg, 4, 16, seed=2)
    batch = data.next_batch()
    p1, _, m1 = jax.jit(step_full)(params, opt, batch, 0)
    p2, _, m2 = jax.jit(step_mb)(params, opt, batch, 0)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))
             if a.dtype in (jnp.float32, jnp.bfloat16)]
    assert max(diffs) < 5e-3


def test_moe_bias_balancing_mechanism():
    """Aux-loss-free balancing: the routing bias must move AGAINST observed
    load (overloaded experts get pushed down), and metrics must be present."""
    cfg = reduce_config(get_config("moonshot-v1-16b-a3b")).replace(num_layers=2)
    lm, step = make_train_step(cfg, base_lr=1e-3)
    step = jax.jit(step)
    params, opt = init_train_state(cfg, jax.random.key(0))
    data = SyntheticLMData(cfg, 4, 32, seed=1)
    seen_metric = False
    loads = None
    for i in range(10):
        batch = data.next_batch()
        # observe the load this step will see, then check the bias reaction
        loss, metrics = jax.jit(lm.loss)(params, batch)
        loads = np.asarray(metrics["moe_load"])          # (Lmoe, E)
        bias_before = np.asarray(params["moe"]["moe"]["bias"])
        params, opt, m = step(params, opt, batch, i)
        seen_metric |= "moe_balance" in m
        bias_after = np.asarray(params["moe"]["moe"]["bias"])
        delta = bias_after - bias_before
        for l in range(loads.shape[0]):
            over = loads[l] > loads[l].mean()
            under = loads[l] < loads[l].mean()
            if over.any():
                assert np.all(delta[l][over] <= 0)       # pushed down
            if under.any():
                assert np.all(delta[l][under] >= 0)      # pulled up
    assert seen_metric, "moe metrics missing"


def test_engine_generates_tokens():
    cfg = reduce_config(get_config("qwen3-8b")).replace(num_layers=2)
    lm = LM(cfg)
    params = lm.init(jax.random.key(0))
    eng = Engine(cfg, params, max_seq=64)
    batch = {"tokens": np.ones((3, 8), np.int32)}
    out = eng.generate(batch, steps=5)
    assert out.shape == (3, 5)
    assert out.dtype == np.int32
    out_t = eng.generate(batch, steps=5, temperature=0.7, seed=1)
    assert out_t.shape == (3, 5)


def test_profiler_to_dse_loop():
    """The paper's technique applied to an assigned arch: dry-run record ->
    requirements -> heterogeneous memory selection."""
    import pathlib
    if not pathlib.Path("artifacts/dryrun").exists():
        pytest.skip("dry-run artifacts not generated")
    from repro.api import DesignTable, design_space
    from repro.core import select
    from repro.profiler.traffic import arch_requirements, load_dryrun_record
    rec = load_dryrun_record("qwen3-8b", "decode_32k")
    if rec is None:
        pytest.skip("qwen3-8b decode record missing")
    reqs = arch_requirements("qwen3-8b", "decode_32k", rec)
    table = DesignTable.from_configs(design_space())
    label_l1 = select.select_level(table.metrics, table.families,
                                   reqs["L1"]).label
    label_l2 = select.select_level(table.metrics, table.families,
                                   reqs["L2"]).label
    assert label_l1 != "infeasible"
    assert label_l2 != "infeasible"
    # L1-analog buffers are core-clock latency-critical -> never OS-Si
    assert "OS-Si" not in label_l1
