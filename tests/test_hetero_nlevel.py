"""N-level composition: SystemBudget rails, branch-and-bound vs exhaustive
rank-identity (property-tested), deep-hierarchy trimming/pinning, the
``levels=`` subset path, cache roundtrips of the search fields, and the 2D
(compositions x corners) scoring/sharding equivalences."""
import dataclasses
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import api
from repro.api import Compiler, DesignTable, design_space
from repro.core import gainsight
from repro.core.gainsight import nlevel_task
from repro.core.select import Bucket, LevelReq, SelectionPolicy, TaskReq
from repro.hetero import (ComposePolicy, SystemBudget, bucket_candidates,
                          compose, composition_eval_count)
from repro.hetero.compose import OBJECTIVES, _trim_to_budget
from repro.hetero.search import balanced_norms, slot_contributions
from repro.hetero.system import SYSTEM_METRICS, score_grid, score_grid_corners
from repro.parallel.grid import _factor_devices

KB = gainsight.KB


@pytest.fixture(scope="module")
def table():
    return DesignTable.from_configs(design_space())


# --------------------------------------------------------------- SystemBudget
def test_system_budget_basics():
    none = SystemBudget()
    assert not none.active and none.ensure_orders() == ()
    b = SystemBudget(area_um2=1e6, bw_margin_min=1.0)
    assert b.active
    assert b.ensure_orders() == ("area", "bandwidth")
    scores = {"area_um2": np.array([5e5, 2e6]),
              "p_w": np.array([1.0, 1.0]),
              "bw_margin": np.array([1.5, 0.5])}
    np.testing.assert_array_equal(b.feasible(scores), [True, False])
    assert SystemBudget(power_w=2.0).feasible(scores).all()


def test_compose_policy_validation():
    with pytest.raises(ValueError):
        ComposePolicy(search="nosuch")
    with pytest.raises(ValueError):
        ComposePolicy(budget=SystemBudget(area_um2=1e6), area_budget_um2=1e6)
    # legacy rails fold into the effective SystemBudget
    legacy = ComposePolicy(area_budget_um2=2e6, power_budget_w=0.5)
    assert legacy.system_budget() == SystemBudget(area_um2=2e6, power_w=0.5)
    assert ComposePolicy(budget=SystemBudget(power_w=1.0)).system_budget() \
        == SystemBudget(power_w=1.0)


def test_bandwidth_rail_pins_fastest_row(table):
    metrics, fams = table.metrics, table.families
    b = Bucket(1.0, 0.5e9, 1e-4)
    bc = bucket_candidates(metrics, fams, b, level_name="L1", bucket_index=0,
                           capacity_bits=1e6, ensure_orders=("bandwidth",))
    f_op = np.asarray(metrics["f_op_hz"], np.float64)
    kept = [c.config_idx for c in bc.candidates]
    fastest_kept = max(kept, key=lambda r: f_op[r])
    # the pinned row is at least as fast as anything else in the list
    assert any(f_op[r] >= f_op[fastest_kept] for r in bc.pinned)
    assert set(bc.pinned) <= set(kept)


def test_bw_margin_budget_filters_and_proves_unmeetable(table):
    t = gainsight.TASKS[0]
    base = compose(table, t)
    need = base.best.metrics["bw_margin"] * 0.999
    rb = compose(table, t, compose_policy=ComposePolicy(
        budget=SystemBudget(bw_margin_min=need)))
    assert rb.n_feasible > 0
    assert rb.best.feasible and rb.best.metrics["bw_margin"] >= need
    # an absurd floor: the argmax-f_op pins make "nothing fits" trustworthy
    impossible = compose(table, t, compose_policy=ComposePolicy(
        budget=SystemBudget(bw_margin_min=1e9)))
    assert impossible.n_feasible == 0 and not impossible.best.feasible


# ------------------------------------------------------------ levels= subset
def test_levels_subset_matches_dedicated_task():
    """Composing 3 levels out of the 5-level reference == composing the
    3-level task directly (identical slots => identical scores, bitwise)."""
    full = compose(None, nlevel_task(5), levels=("RF", "L1", "L2"))
    direct = compose(None, nlevel_task(3))
    assert full.labels() == direct.labels()
    assert full.n_space == direct.n_space
    for a, b in zip(full.ranked, direct.ranked):
        for m in SYSTEM_METRICS:
            av, bv = a.metrics[m], b.metrics[m]
            assert av == bv or (av != av and bv != bv), m


def test_levels_subset_through_compiler_reproduces_table2():
    c = Compiler()
    hits = sum(c.compose(t, levels=("L1", "L2")).matches(
        gainsight.TABLE2_EXPECTED[t.task_id]) for t in gainsight.TASKS)
    assert hits == 7
    with pytest.raises(KeyError):
        c.compose(gainsight.TASKS[0], levels=("L1", "L3"))


def test_single_level_subset(table):
    rep = compose(table, nlevel_task(3), levels=("L2",))
    assert list(rep.best.levels) == ["L2"]
    assert rep.best.feasible


# ------------------------------------- branch-and-bound vs exhaustive (prop.)
_MEM_TYPES = ("sram6t", "gc_sisi", "gc_ossi", "gc_osos", "gc_sisi_hvt")


def _random_space(seed: int):
    """A synthetic DesignTable + 2..4-level task with randomized metrics,
    small enough that the exhaustive grid is never trimmed."""
    rng = np.random.default_rng(1000 + seed)
    n = 10
    metrics = {
        "area_um2": rng.uniform(100.0, 5000.0, n).astype(np.float32),
        "bits": rng.choice([1024.0, 4096.0, 16384.0, 65536.0],
                           n).astype(np.float32),
        "p_leak_w": rng.uniform(1e-7, 1e-4, n).astype(np.float32),
        "p_refresh_w": rng.uniform(0.0, 1e-5, n).astype(np.float32),
        "p_dyn_w": rng.uniform(1e-6, 1e-3, n).astype(np.float32),
        "e_read_j": rng.uniform(1e-13, 1e-11, n).astype(np.float32),
        "f_op_hz": rng.uniform(0.2e9, 3e9, n).astype(np.float32),
        "retention_s": (10.0 ** rng.uniform(-5, 2, n)).astype(np.float32),
    }
    axes = {"mem_type": rng.choice(_MEM_TYPES, n)}
    table = DesignTable(axes, metrics)
    n_levels = 2 + seed % 3
    levels = {}
    for i in range(n_levels):
        name = f"V{i}"
        levels[name] = LevelReq(name, int(rng.uniform(1e5, 1e7)), (
            Bucket(1.0, float(rng.uniform(0.3e9, 2.5e9)),
                   float(10.0 ** rng.uniform(-6, 0))),))
    return table, TaskReq(f"rand{seed}", f"rand-{seed}", levels)


@settings(max_examples=24, deadline=None)
@given(seed=st.integers(0, 2),
       objective=st.sampled_from(OBJECTIVES),
       budgeted=st.booleans())
def test_bb_rank_identical_to_exhaustive(seed, objective, budgeted):
    """On every untruncated grid, branch-and-bound must return the same
    ranked list as the exhaustive cross-product — same rows, same float32
    metrics bit-for-bit, same feasibility — for all four objectives, with
    and without an active SystemBudget."""
    table, task = _random_space(seed)
    budget = None
    if budgeted:
        ref = compose(table, task, compose_policy=ComposePolicy(
            objective=objective, candidate_mode="all_feasible", top_k=5))
        m = ref.best.metrics
        budget = SystemBudget(
            area_um2=float(m["area_um2"]) * 1.5,
            power_w=float(m["p_w"]) * 3.0,
            bw_margin_min=1.0)
    cp_ex = ComposePolicy(objective=objective, candidate_mode="all_feasible",
                          top_k=5, budget=budget, search="exhaustive")
    cp_bb = dataclasses.replace(cp_ex, search="branch_and_bound")
    r_ex = compose(table, task, compose_policy=cp_ex)
    r_bb = compose(table, task, compose_policy=cp_bb)
    assert not r_ex.truncated and not r_bb.truncated
    assert r_ex.search == "exhaustive"
    assert r_bb.search == "branch_and_bound"
    assert r_bb.n_space == r_ex.n_space == r_ex.n_compositions
    assert r_bb.n_compositions <= r_ex.n_compositions
    assert len(r_bb.ranked) == len(r_ex.ranked)
    for k, (a, b) in enumerate(zip(r_ex.ranked, r_bb.ranked)):
        for lvl in task.levels:
            assert [p.config_idx for p in a.levels[lvl].picks] == \
                [p.config_idx for p in b.levels[lvl].picks], (k, lvl)
        assert a.feasible == b.feasible and a.pref_rank == b.pref_rank
        for m in SYSTEM_METRICS:
            av, bv = a.metrics[m], b.metrics[m]
            assert av == bv or (av != av and bv != bv), (k, m)


# --------------------------------------------------- deep-hierarchy trimming
def _fake_slots(n_slots=11, n_cands=64, pinned=()):
    from repro.hetero.candidates import BucketCandidates, Candidate
    return [BucketCandidates(
        level_name=f"M{s}", bucket_index=0, bucket=Bucket(1.0, 1e9, 1e-3),
        capacity_bits=1e6,
        candidates=tuple(Candidate("sram", i, 0) for i in range(n_cands)),
        pinned=tuple(pinned)) for s in range(n_slots)]


def test_trim_to_budget_11_slots_past_int64():
    """11 slots at the 64-candidate cap: the product (2^66) overflows int64,
    the regime where an np.prod-based guard would wrap (to 0 here) and skip
    trimming entirely. math.prod must keep trimming."""
    slots = _fake_slots()
    full = math.prod(len(s.candidates) for s in slots)
    assert full == 64 ** 11 > np.iinfo(np.int64).max
    # the exact wrap an int64 product would produce: 2**66 mod 2**64 == 0,
    # so a `<= max_compositions` guard on it would never trim at all
    wrapped = np.multiply.reduce(np.full(11, 64, np.int64), dtype=np.int64)
    assert wrapped == 0
    lists, truncated = _trim_to_budget(slots, 10_000)
    assert truncated
    assert math.prod(len(c) for c in lists) <= 10_000


def test_trim_to_budget_keeps_pins_at_depth():
    """Budget-pinned rows (worst-positioned on purpose) survive trimming in
    every one of the 11 slots."""
    slots = _fake_slots(pinned=(63,))
    lists, truncated = _trim_to_budget(slots, 1_000)
    assert truncated
    for lst in lists:
        assert any(c.config_idx == 63 for c in lst)
    assert math.prod(len(c) for c in lists) <= 1_000


def _deep_task(n_slots=11):
    """An 11-slot hierarchy over the real table: per-level requirements
    cycle through Fig-10-plausible (f, lifetime) points so every slot has a
    rich feasible set."""
    reqs = [(0.40e9, 5e-3), (1.2e9, 2e-6), (0.50e9, 2e-3), (1.6e9, 3e-6),
            (0.35e9, 8e-4), (1.3e9, 2e-6), (0.55e9, 1e-3), (1.8e9, 3e-6),
            (0.45e9, 1e-3), (0.30e9, 1e-2), (1.5e9, 3e-6)]
    levels = {}
    for i in range(n_slots):
        f, lt = reqs[i % len(reqs)]
        name = f"M{i}"
        levels[name] = LevelReq(name, 64 * KB, (Bucket(1.0, f, lt),))
    return TaskReq("deep11", "deep-11", levels)


def test_compose_truncates_and_pins_at_11_slots(table):
    task = _deep_task()
    cp = ComposePolicy(objective="power", candidate_mode="all_feasible",
                       search="exhaustive", max_compositions=4096)
    rep = compose(table, task, compose_policy=cp)
    assert rep.truncated
    assert rep.n_compositions <= 4096
    assert rep.n_space > 10 ** 9           # deep grid, python-int exact
    assert rep.best.feasible
    # exact min-area at depth, via branch-and-bound...
    bb = compose(table, task, compose_policy=ComposePolicy(
        objective="area", candidate_mode="all_feasible",
        search="branch_and_bound"))
    # (bb.truncated may be set by per-bucket caps — the search proof itself
    # closed: far fewer scored than the node budget)
    assert bb.n_compositions < bb.compose_policy.max_compositions
    # ...equals the analytic slot-decomposed optimum
    from repro.hetero.candidates import level_candidates
    slots = [bc for lvl in task.levels.values()
             for bc in level_candidates(table.metrics, table.families, lvl,
                                        SelectionPolicy(),
                                        mode="all_feasible",
                                        order_by="area")]
    area_c, _ = slot_contributions(slots, table.metrics)
    analytic = sum(float(np.min(a)) for a in area_c)
    assert bb.best.metrics["area_um2"] == pytest.approx(analytic, rel=1e-5)
    # an area budget just above that optimum stays feasible on the trimmed
    # exhaustive grid: the pin puts the min-area composition into the grid
    # no matter how hard max_compositions squeezes 11 slots
    budgeted = compose(table, task, compose_policy=ComposePolicy(
        objective="power", candidate_mode="all_feasible",
        search="exhaustive", max_compositions=64,
        budget=SystemBudget(area_um2=analytic * 1.001)))
    assert budgeted.truncated
    assert budgeted.n_feasible > 0 and budgeted.best.feasible
    assert budgeted.best.metrics["area_um2"] <= analytic * 1.0011


def test_balanced_norms_are_candidate_analytic(table):
    """The balanced normalizers depend on the candidate lists alone, and
    lower-bound every scored composition's area/power."""
    from repro.hetero.candidates import level_candidates
    task = nlevel_task(3)
    slots = [bc for lvl in task.levels.values()
             for bc in level_candidates(table.metrics, table.families, lvl,
                                        SelectionPolicy(),
                                        mode="all_feasible",
                                        order_by="balanced")]
    a0, p0 = balanced_norms(slots, table.metrics)
    assert a0 > 0 and p0 > 0
    rep = compose(table, task, compose_policy=ComposePolicy(
        objective="balanced", candidate_mode="all_feasible"))
    assert rep.best.metrics["area_um2"] >= a0 * (1 - 1e-6)
    assert rep.best.metrics["p_w"] >= p0 * (1 - 1e-6)


# -------------------------------------------------- pruning on 4-level space
def test_bb_prunes_4level_space_10x_with_identical_best(table):
    task = nlevel_task(4)
    kw = dict(objective="power", candidate_mode="all_feasible",
              max_candidates_per_bucket=16)
    ex = compose(table, task, compose_policy=ComposePolicy(
        search="exhaustive", max_compositions=50_000, **kw))
    bb = compose(table, task, compose_policy=ComposePolicy(
        search="branch_and_bound", **kw))
    assert bb.n_space == ex.n_space >= 16 ** 4
    # the bound proof closed well inside the node budget
    assert bb.n_compositions < bb.compose_policy.max_compositions
    assert bb.n_compositions * 10 <= ex.n_compositions
    assert bb.labels() == ex.labels()
    for lvl in task.levels:
        assert [p.config_idx for p in bb.best.levels[lvl].picks] == \
            [p.config_idx for p in ex.best.levels[lvl].picks]
    assert bb.best.metrics["p_w"] == ex.best.metrics["p_w"]


def test_4level_power_space_and_winner_are_pinned(table):
    """The 4-level reference under the power objective has a fixed search
    space and a fixed winner, not only the same winner in both engines."""
    task = nlevel_task(4)
    rep = compose(table, task, compose_policy=ComposePolicy(
        search="branch_and_bound", objective="power",
        candidate_mode="all_feasible", max_candidates_per_bucket=16))
    assert task.task_id == "nlevel4"
    assert sum(len(lv.buckets) for lv in task.levels.values()) == 5
    assert rep.n_space == 917_504
    assert rep.labels() == {"RF": "SRAM", "L1": "SRAM",
                            "L2": "OS-Si GCRAM + SRAM", "SPM": "OS-Si GCRAM"}


def test_auto_search_switches_on_space_size(table):
    task = nlevel_task(4)
    big = compose(table, task, compose_policy=ComposePolicy(
        objective="power", candidate_mode="all_feasible"))
    assert big.n_space > big.compose_policy.search_threshold
    assert big.search == "branch_and_bound"
    small = compose(table, task)               # per_family_best: tiny grid
    assert small.n_space <= small.compose_policy.search_threshold
    assert small.search == "exhaustive"


# ------------------------------------------------------------------- caching
def test_bb_cache_roundtrip_preserves_search_fields(tmp_path):
    task = nlevel_task(3)
    cp = ComposePolicy(objective="power", candidate_mode="all_feasible",
                       search="branch_and_bound")
    r1 = compose(None, task, compose_policy=cp, cache=tmp_path)
    n_chz, n_eval = api.characterize_call_count(), composition_eval_count()
    r2 = compose(None, task, compose_policy=cp, cache=tmp_path)
    assert api.characterize_call_count() == n_chz
    assert composition_eval_count() == n_eval, \
        "cache hit must not re-run the branch-and-bound scoring"
    assert r2.search == "branch_and_bound" == r1.search
    assert r2.n_space == r1.n_space > 10 ** 6
    assert (r2.n_compositions, r2.n_feasible, r2.truncated) == \
        (r1.n_compositions, r1.n_feasible, r1.truncated)
    assert [c.labels() for c in r2.ranked] == [c.labels() for c in r1.ranked]
    for a, b in zip(r1.ranked, r2.ranked):
        for m in SYSTEM_METRICS:
            assert b.metrics[m] == pytest.approx(a.metrics[m])
    # the search mode is part of the cache key: not a false hit
    compose(None, task, cache=tmp_path,
            compose_policy=dataclasses.replace(cp, search="exhaustive"))
    n_after_mode = composition_eval_count()
    assert n_after_mode > n_eval
    # ...and a different budget misses too
    compose(None, task, cache=tmp_path, compose_policy=dataclasses.replace(
        cp, budget=SystemBudget(bw_margin_min=1.0)))
    assert composition_eval_count() > n_after_mode


# ----------------------------------------- corners x compositions (2D) path
def test_score_grid_corners_matches_per_corner_sweeps():
    t = DesignTable.from_configs(
        design_space(word_sizes=(16, 64), num_words=(32, 256)),
        corners=("nominal", "hot"))
    cms = [t.corner_metrics(c) for c in t.corner_labels]
    rng = np.random.default_rng(5)
    idx = rng.integers(0, len(t), (37, 3)).astype(np.int32)
    idx[3, 1] = -1                             # sentinel slot
    cap, f = [1e5, 2e5, 1e6], [1e9, 5e8, 2e9]
    n_eval = composition_eval_count()
    out = score_grid_corners(cms, idx, cap, f)
    assert composition_eval_count() == n_eval + 1    # ONE dispatch for all C
    for c, m in enumerate(cms):
        ref = score_grid(m, idx, cap, f)
        for k in SYSTEM_METRICS:
            np.testing.assert_array_equal(out[k][c], ref[k], err_msg=(c, k))


def test_factor_devices():
    assert _factor_devices(8, 3) == (4, 2)     # largest divisor of 8 <= 3
    assert _factor_devices(8, 1) == (8, 1)
    assert _factor_devices(6, 4) == (2, 3)
    assert _factor_devices(8, 16) == (1, 8)
    assert _factor_devices(1, 5) == (1, 1)
    assert _factor_devices(7, 3) == (7, 1)     # prime: all on the major axis


_SHARD2D_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import numpy as np, jax
sys.path.insert(0, "src")
assert jax.device_count() == 8
from repro.api import DesignTable, design_space
from repro.hetero.system import score_grid_corners

table = DesignTable.from_configs(
    design_space(word_sizes=(16, 64), num_words=(32, 256)),
    corners=("nominal", "hot", "cold"))
cms = [table.corner_metrics(c) for c in table.corner_labels]
rng = np.random.default_rng(0)
idx = rng.integers(0, len(table), size=(1003, 4)).astype(np.int32)
idx[7, 2] = -1
cap, f = [1e5, 2e5, 4e5, 1e6], [1e9, 5e8, 2e9, 1e9]
a = score_grid_corners(cms, idx, cap, f, sharded=False)
b = score_grid_corners(cms, idx, cap, f, sharded=True)
print(json.dumps({
    "exact": all(bool(np.array_equal(a[k], b[k])) for k in a),
    "shape_ok": all(b[k].shape == (3, 1003) for k in b)}))
"""


def test_shard2d_equals_single_device_8dev(tmp_path):
    """8-virtual-device 2D (compositions x corners) mesh == single device,
    bit exact (subprocess: the device count must be set before jax
    initializes). 3 corners forces uneven padding on the minor axis."""
    script = tmp_path / "shard2d_equiv.py"
    script.write_text(_SHARD2D_SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True,
                         cwd=str(Path(__file__).resolve().parents[1]),
                         env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"exact": True, "shape_ok": True}
