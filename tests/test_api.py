"""The repro.api façade: Compiler/Macro, DesignTable queries + caching,
explore() -> DSEReport, and consistency with the legacy dse free functions."""
import warnings

import numpy as np
import pytest

from repro import api
from repro.api import (Compiler, DesignTable, MacroConfig, SelectionPolicy,
                       explore)
from repro.core import gainsight


def small_space():
    return api.design_space(word_sizes=(16, 32), num_words=(32, 64))


# ----------------------------------------------------------------- Compiler
def test_compiler_compile_macro(tmp_path):
    m = Compiler().compile(mem_type="gc_sisi", word_size=16, num_words=32,
                           level_shift=True)
    assert isinstance(m.ppa["f_op_hz"], float) and m.ppa["f_op_hz"] > 0
    assert m.retention_s == m.ppa["retention_s"]
    assert m.family == "si-si"
    assert "module gc_sisi_16x32" in m.verilog()
    assert "library (" in m.lib()
    assert "MACRO gc_sisi_16x32" in m.lef()
    rep = m.write_all(tmp_path)
    assert rep["drc_clean"] and rep["lvs_clean"]
    assert {p.suffix for p in tmp_path.iterdir()} >= {".sp", ".v", ".lib",
                                                      ".lef", ".json"}
    # write_all must reuse the Macro's PPA, not re-characterize
    assert rep["characterization"] is m.ppa


def test_compiler_rejects_unknown_mem_type():
    with pytest.raises(KeyError):
        Compiler(mem_types=("gc_sisi", "nosuch"))
    with pytest.raises(KeyError):
        Compiler().compile(mem_type="nosuch", word_size=16, num_words=16)


# -------------------------------------------------------------- DesignTable
def test_table_roundtrip_and_cache_hit(tmp_path):
    cfgs = small_space()
    t1 = DesignTable.build(cfgs, cache=tmp_path)
    n_sweeps = api.characterize_call_count()
    t2 = DesignTable.build(cfgs, cache=tmp_path)          # second run: cached
    assert api.characterize_call_count() == n_sweeps, \
        "cache hit must not re-run the vmap characterization"
    assert t2.to_configs() == cfgs                        # axis round-trip
    for k in t1.metric_names:
        np.testing.assert_array_equal(t1[k], t2[k])
    assert t1.grid_hash == t2.grid_hash
    # a different grid gets a different cache key
    other = api.design_space(word_sizes=(64,), num_words=(64,))
    assert api.grid_hash(other) != t1.grid_hash


def test_table_cache_misses_on_another_device(tmp_path, monkeypatch):
    """A table saved under one device stamp is never served under another:
    the stamp is part of the grid hash, and so of every hetero/sim report
    key derived from it."""
    cfgs = small_space()
    monkeypatch.setattr(api, "_device_stamp", lambda: "tpu:TPU v5 lite")
    DesignTable.build(cfgs, cache=tmp_path)
    n_sweeps = api.characterize_call_count()
    tpu_hash = api.grid_hash(cfgs)
    DesignTable.build(cfgs, cache=tmp_path)
    assert api.characterize_call_count() == n_sweeps      # same device: hit
    monkeypatch.setattr(api, "_device_stamp", lambda: "cpu:cpu")
    t = DesignTable.build(cfgs, cache=tmp_path)
    assert api.characterize_call_count() == n_sweeps + 1  # other device: miss
    assert t.grid_hash == api.grid_hash(cfgs) != tpu_hash
    assert len(list(tmp_path.glob("table_*.npz"))) == 2


def test_table_save_load_explicit(tmp_path):
    t = DesignTable.from_configs(small_space())
    path = t.save(tmp_path / "t.npz")
    t2 = DesignTable.load(path)
    assert len(t2) == len(t)
    np.testing.assert_array_equal(t["f_op_hz"], t2["f_op_hz"])
    assert list(t2["mem_type"]) == list(t["mem_type"])


def test_feasible_pareto_chain_matches_legacy():
    from repro.core import dse
    cfgs = small_space()
    table = DesignTable.from_configs(cfgs)
    f_hz, lt = 1.0e9, 1e-5
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res = dse.evaluate_space(cfgs)
        mask = dse.feasible_mask(res, f_hz, lt)
    chain = table.feasible(f_hz, lt)
    assert len(chain) == int(mask.sum())
    assert chain.to_configs() == [c for c, m in zip(cfgs, mask) if m]

    chain = chain.with_column("p_static_w",
                              chain["p_leak_w"] + chain["p_refresh_w"])
    pts = np.stack([chain["area_um2"], chain["p_static_w"],
                    chain["t_read_s"]], axis=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy_front = dse.pareto_front(pts)
    front = chain.pareto("area_um2", "p_static_w", "t_read_s")
    assert len(front) == int(legacy_front.sum())
    assert front.to_configs() == [c for c, m in zip(chain.to_configs(),
                                                    legacy_front) if m]


def test_table_best_and_maximize():
    table = DesignTable.from_configs(small_space())
    smallest = table.best("area_um2")
    assert smallest.ppa["area_um2"] == pytest.approx(
        float(np.min(table["area_um2"])))
    fastest = table.best("f_op_hz", ascending=False)
    assert fastest.ppa["f_op_hz"] == pytest.approx(
        float(np.max(table["f_op_hz"])))
    # "-col" objective maximizes in pareto()
    front = table.pareto("-retention_s")
    assert float(front["retention_s"][0]) == float(np.max(table["retention_s"]))


def test_table_filter_callable_and_columns():
    table = DesignTable.from_configs(small_space())
    gc = table.filter(lambda t: t["mem_type"] != "sram6t")
    assert set(gc["mem_type"]) <= {"gc_sisi", "gc_ossi"}
    assert set(table.axis_names) == set(DesignTable.AXIS_NAMES)
    assert "f_op_hz" in table and "word_size" in table


# ------------------------------------------------------------------ explore
def test_explore_reproduces_table2_and_hits_cache(tmp_path):
    report = explore(tasks=gainsight.TASKS, cache=tmp_path)
    labels = report.labels()
    for t in gainsight.TASKS:
        exp = gainsight.TABLE2_EXPECTED[t.task_id]
        assert labels[t.task_id]["L1"] == exp["L1"], f"task {t.task_id} L1"
        assert labels[t.task_id]["L2"] == exp["L2"], f"task {t.task_id} L2"
    assert report.matches(gainsight.TABLE2_EXPECTED) == 7

    n_sweeps = api.characterize_call_count()
    report2 = explore(tasks=gainsight.TASKS, cache=tmp_path)
    assert api.characterize_call_count() == n_sweeps, \
        "second explore() on the same grid must hit the DesignTable cache"
    assert report2.labels() == labels


def test_explore_report_structure():
    report = explore(tasks=gainsight.TASKS[:2])
    t1 = report.tasks[0]
    sel = report.selections[t1.task_id]["L1"]
    assert sel.feasible and sel.picks[0].config_idx >= 0
    macro = report.pick_macro(t1.task_id, "L1")
    assert macro.family == sel.picks[0].family
    shmoo = report.shmoo(t1.task_id, "L2")
    assert shmoo.dtype == bool and len(shmoo) == len(report.table)
    assert f"task {t1.task_id}" in report.summary()


def test_explore_policy_preference():
    # SRAM-only preference must never label a level with GCRAM
    report = explore(tasks=gainsight.TASKS[:1],
                     policy=SelectionPolicy(preference=("sram",)))
    for levels in report.labels().values():
        for label in levels.values():
            assert label in ("SRAM", "infeasible")


def test_legacy_select_level_matches_explore():
    from repro.core import dse
    cfgs = api.design_space()
    table = DesignTable.from_configs(cfgs)
    report = explore(space=table, tasks=gainsight.TASKS)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res = dse.evaluate_space(cfgs)
        for t in gainsight.TASKS:
            l1, picks = dse.select_level(cfgs, res, t.l1)
            assert l1 == report.selections[t.task_id]["L1"].label
            new_picks = report.selections[t.task_id]["L1"].picks
            assert [p["config_idx"] for p in picks] == \
                [p.config_idx for p in new_picks]


# ---------------------------------------------------------------- gainsight
def test_task_req_normalization():
    t = api.as_task_req(gainsight.TASKS[0])
    assert t.task_id == 1 and set(t.levels) == {"L1", "L2"}
    same = api.as_task_req(t)
    assert same is t
    with pytest.raises(TypeError):
        api.as_task_req(42)
