"""The repro.api façade: Compiler/Macro, DesignTable queries + caching,
explore() -> DSEReport, and consistency with the repro.core.select
primitives they are built from."""
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
    """The feasible -> pareto chain selects what ``select.feasible_mask`` and
    ``select.pareto_mask`` select over ``characterize_batch``'s metrics."""
    import jax.numpy as jnp

    from repro.core import select
    from repro.core.characterize import characterize_batch
    cfgs = small_space()
    table = DesignTable.from_configs(cfgs)
    f_hz, lt = 1.0e9, 1e-5
    res = characterize_batch(jnp.stack([c.to_vector() for c in cfgs]))
    mask = select.feasible_mask(res, f_hz, lt)
    chain = table.feasible(f_hz, lt)
    assert len(chain) == int(mask.sum())
    assert chain.to_configs() == [c for c, m in zip(cfgs, mask) if m]

    chain = chain.with_column("p_static_w",
                              chain["p_leak_w"] + chain["p_refresh_w"])
    pts = np.stack([chain["area_um2"], chain["p_static_w"],
                    chain["t_read_s"]], axis=1)
    legacy_front = select.pareto_mask(pts)
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


def _edge_configs():
    """Every bitcell, banked, every mux mode, both booleans on."""
    from repro.core import bitcells
    out = [MacroConfig(mem_type=mt, word_size=64, num_words=256)
           for mt in bitcells.MEM_TYPE]
    out += [MacroConfig(mem_type=mt, word_size=16, num_words=512, banks=4,
                        mux=mux, level_shift=True, sa_current_mode=True)
            for mt in ("sram6t", "gc_ossi") for mux in (0, 2, 8)]
    return out


def test_encode_axes_equals_the_per_config_encoding():
    """One host array of the axis columns holds, bit for bit, what the
    per-config encoding of the same list stacks (and what the literal
    per-field list gives)."""
    import jax.numpy as jnp

    from repro.core import bitcells, macro
    cfgs = list(api.design_space()) + _edge_configs()
    table_axes = {
        "mem_type": np.array([c.mem_type for c in cfgs]),
        **{f: np.array([getattr(c, f) for c in cfgs])
           for f in macro.VEC_FIELDS[1:]}}
    enc = macro.encode_axes(table_axes)
    assert enc.dtype == np.float32 and enc.shape == (len(cfgs), 7)
    per_config = np.stack([np.asarray(c.to_vector()) for c in cfgs])
    literal = np.stack([np.asarray(jnp.asarray(
        [bitcells.MEM_TYPE[c.mem_type], c.word_size, c.num_words, c.banks,
         int(c.level_shift), int(c.sa_current_mode), c.mux], jnp.float32))
        for c in cfgs])
    assert per_config.dtype == np.float32
    assert np.array_equal(enc, per_config)
    assert np.array_equal(enc, literal)
    # the table's own axis columns encode the same way
    table_enc = macro.encode_axes(DesignTable(table_axes, {}).axes)
    assert np.array_equal(table_enc, enc)


@pytest.mark.parametrize("corners", [None, ("nominal", "hot")])
def test_from_configs_columns_equal_the_per_config_stack(corners):
    """A table encoded as one host array characterizes to exactly the
    columns of the per-config ``to_vector`` stack."""
    import jax.numpy as jnp

    from repro.core import characterize as chz
    from repro.core import corners as corners_mod
    cfgs = small_space()[:6] + _edge_configs()[:4]
    table = DesignTable.from_configs(cfgs, corners=corners)
    vecs = jnp.stack([c.to_vector() for c in cfgs])
    if corners is None:
        ref = {k: np.asarray(v) for k, v in
               chz.characterize_batch(vecs).items()}
    else:
        ops = corners_mod.as_corners(corners)
        ref = {}
        for k, v in chz.characterize_corners(vecs, ops).items():
            grid = np.asarray(v)
            ref[k] = grid[:, 0]
            ref.update({f"{k}@{op.corner}": grid[:, c]
                        for c, op in enumerate(ops)})
    assert set(table.metric_names) == set(ref)
    for k, v in ref.items():
        np.testing.assert_array_equal(table.metrics[k], v, err_msg=k)


def _per_column(out):
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_bit_equal(got, ref):
    assert set(got) == set(ref)
    for k, v in ref.items():
        assert got[k].dtype == v.dtype, k
        assert np.array_equal(got[k], v), k


@pytest.mark.parametrize("corners", [None, ("nominal", "hot", "cold")])
def test_from_configs_one_fetch_is_bit_identical_to_per_column(corners):
    """The whole grid's columns, joined on the device and fetched in one
    transfer, are the very bits of a per-column ``np.asarray`` of each
    corner's own characterize dispatch."""
    import jax.numpy as jnp

    from repro.core import characterize as chz
    from repro.core import corners as corners_mod
    from repro.core.macro import encode_axes
    table = DesignTable.from_configs(api.design_space(), corners=corners)
    vecs = jnp.asarray(encode_axes(table.axes))
    if corners is None:
        ref = _per_column(chz.characterize_batch(vecs))
    else:
        ref = {}
        for op in corners_mod.as_corners(corners):
            tp = corners_mod.resolve(op)
            fn = chz.characterize_batch if tp == corners_mod.NOMINAL_TECH \
                else chz._characterize_vmap_jit(tp)
            cols = _per_column(fn(vecs))
            ref.update({f"{k}@{op.corner}": v for k, v in cols.items()})
            if op.corner == "nominal":
                ref.update(cols)
        stacked = _per_column(chz.characterize_corners(
            vecs, corners_mod.as_corners(corners)))
        for c, op in enumerate(corners_mod.as_corners(corners)):
            for k, grid in stacked.items():
                assert np.array_equal(grid[:, c], ref[f"{k}@{op.corner}"])
    _assert_bit_equal(table.metrics, ref)


def test_fetch_tree_mixed_shapes_one_fetch_per_call():
    """Leaves of shape (N,) and (N, C) come back exact, in their shapes,
    for one ``device.fetches`` increment per call."""
    import jax.numpy as jnp

    from repro import obs
    from repro.transfer import fetch_tree
    rng = np.random.default_rng(3)
    host = {"a": rng.standard_normal(5).astype(np.float32),
            "b": rng.standard_normal((5, 3)).astype(np.float32),
            "c": [rng.standard_normal((2, 4)).astype(np.float32)]}
    tree = {"a": jnp.asarray(host["a"]), "b": jnp.asarray(host["b"]),
            "c": [jnp.asarray(host["c"][0])]}
    before = obs.snapshot()["counters"].get("device.fetches", 0)
    back = fetch_tree(tree)
    back2 = fetch_tree(tree)
    assert obs.snapshot()["counters"]["device.fetches"] - before == 2
    for got in (back, back2):
        assert isinstance(got["c"], list)
        _assert_bit_equal({"a": got["a"], "b": got["b"], "c": got["c"][0]},
                          {"a": host["a"], "b": host["b"],
                           "c": host["c"][0]})


def test_fetch_tree_rejects_mixed_dtypes():
    import jax.numpy as jnp

    from repro.transfer import fetch_tree
    with pytest.raises(TypeError, match="one dtype"):
        fetch_tree({"x": jnp.zeros(3, jnp.float32),
                    "n": jnp.zeros(3, jnp.int32)})


def test_fetch_tree_same_structure_compiles_once():
    """A second call with the same leaf shapes reuses the join program:
    its span records no compile."""
    import jax.numpy as jnp

    from repro import obs
    from repro.transfer import fetch_tree
    tree = {"p": jnp.arange(7, dtype=jnp.float32),
            "q": jnp.ones((7, 2), jnp.float32)}
    obs.clear()
    with obs.enabled_scope(True):
        with obs.span("t.first"):
            fetch_tree(tree)
        with obs.span("t.second"):
            fetch_tree(tree)
    ev = {e["name"]: e for e in obs.events()}
    assert ev["t.first"]["args"]["fetches"] == 1
    assert ev["t.second"]["args"] == {"fetches": 1}


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
    """explore's L1 labels and picks are ``select.select_level``'s over the
    same table's metrics and families."""
    from repro.core import select
    table = DesignTable.from_configs(api.design_space())
    report = explore(space=table, tasks=gainsight.TASKS)
    for t in gainsight.TASKS:
        sel = select.select_level(table.metrics, table.families, t.l1)
        assert sel.label == report.selections[t.task_id]["L1"].label
        new_picks = report.selections[t.task_id]["L1"].picks
        assert [p.config_idx for p in sel.picks] == \
            [p.config_idx for p in new_picks]


# ---------------------------------------------------------------- gainsight
def test_task_req_normalization():
    t = api.as_task_req(gainsight.TASKS[0])
    assert t.task_id == 1 and set(t.levels) == {"L1", "L2"}
    same = api.as_task_req(t)
    assert same is t
    with pytest.raises(TypeError):
        api.as_task_req(42)
