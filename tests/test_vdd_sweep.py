"""The (vdd, refresh-margin) co-optimization axis of ``compose``.

Covers the searched expansion end to end: golden-locked Table-2 winner flips
at the frozen cold-boost sweep point (the MCAIMem effect — a scaled/boosted
supply changes which technology wins a retention-marginal level), block-0
passthrough bit-exactness, branch-and-bound rank identity on the enlarged
grid, cache key sensitivity + swept-report roundtrip, policy validation, and
the solver-property tests (retention monotone in temperature, swept refresh
intervals positive/finite).
"""
import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

from _hypothesis_compat import given, settings, st  # noqa: E402
from update_golden import (FULL_MARGINS, FULL_VDDS,  # noqa: E402
                           VDD_PATH, VDD_SWEEP_POINT, compose_vdd,
                           full_sweep_tasks, golden_diff, vdd_tasks,
                           write_vdd_snapshot)

from repro.api import DesignTable, design_space  # noqa: E402
from repro.core import bitcells, corners, gainsight, retention  # noqa: E402
from repro.hetero import (ComposePolicy, compose,  # noqa: E402
                          composition_eval_count, expand)
from repro.sim.refresh import refresh_interval_s  # noqa: E402


@pytest.fixture(scope="module")
def table():
    return DesignTable.from_configs(design_space())


@pytest.fixture(scope="module")
def vdd_golden(request):
    if request.config.getoption("--update-golden"):
        write_vdd_snapshot()
    assert VDD_PATH.exists(), \
        "missing tests/golden/table2_vdd.json (run scripts/update_golden.py)"
    return json.loads(VDD_PATH.read_text())


# ------------------------------------------------------------ golden flips
def test_vdd_sweep_flips_table2_winners_golden(vdd_golden):
    """The frozen cold-boost point must keep flipping exactly the same
    Table-2 winners, with identical labels, picks and operating points and
    the winners' power within the golden contract."""
    assert vdd_golden["vdd_sweep_point"] == list(VDD_SWEEP_POINT)
    live = vdd_tasks()
    drift, _ = golden_diff(vdd_golden["tasks"], live)
    assert not drift, drift[:20]
    assert any(t["flipped"] for t in live.values()), \
        "the sweep point no longer flips any Table-2 winner"


def test_full_sweep_winners_match_golden(vdd_golden):
    """Over the full 3-point x 2-margin sweep every Table-2 winner keeps
    its labels, picks, operating points and tiles, and its power within
    the golden contract."""
    drift, _ = golden_diff(vdd_golden["full_sweep"], full_sweep_tasks())
    assert not drift, drift[:20]


def test_full_sweep_is_the_chip_cells_sweep():
    """The golden's full sweep is the very sweep the chip benchmark's
    ``table2_vdd_sweep`` cell composes over."""
    traffic = json.loads(
        (REPO / "bench" / "traffic" / "table2_vdd_sweep.json").read_text())
    policy = traffic["policy"]
    assert [tuple(p) for p in policy["vdd_sweep"]] == list(FULL_VDDS)
    assert tuple(policy["refresh_margin_sweep"]) == FULL_MARGINS


def test_base_table2_parity_survives_the_sweep_machinery():
    """With empty sweeps the compose path must still reproduce all 7 paper
    selections (the expansion is pure opt-in)."""
    for t in gainsight.TASKS:
        rep = compose_vdd(t, swept=False)
        assert rep.labels() == gainsight.TABLE2_EXPECTED[t.task_id]
        for lc in rep.best.levels.values():
            assert all(p.op is None and p.refresh_margin is None
                       for p in lc.picks)


# ------------------------------------------------------- expansion mechanics
def test_block0_passthrough_is_bit_identical(table):
    """The base block of an expanded metric dict is the input columns
    untouched — the sweep can never perturb un-swept numbers."""
    cp = ComposePolicy(vdd_sweep=(VDD_SWEEP_POINT,),
                       refresh_margin_sweep=(0.8,))
    points = expand.expansion_points(cp)
    assert points[0] == (None, None)
    assert len(points) == 4          # (base + 1 vdd) x (base + 1 margin)
    metrics, fams = expand.expand_metrics(table, table.metrics, points)
    n = len(table)
    assert len(fams) == 4 * n
    assert list(fams[:n]) == list(np.asarray(table.families))
    assert list(fams[n:2 * n]) == list(np.asarray(table.families))
    for k, col in table.metrics.items():
        np.testing.assert_array_equal(np.asarray(metrics[k][:n]),
                                      np.asarray(col), err_msg=k)
    # margin block: refresh power scaled by 1/margin, retention untouched
    np.testing.assert_array_equal(
        np.asarray(metrics["p_refresh_w"][n:2 * n]),
        np.asarray(table.metrics["p_refresh_w"]) / 0.8)
    np.testing.assert_array_equal(
        np.asarray(metrics["retention_s"][n:2 * n]),
        np.asarray(table.metrics["retention_s"]))


def test_expand_one_fetch_is_bit_identical_to_per_column(table):
    """Over the benchmark's sweep (3 vdd points x 2 refresh margins), every
    expanded column is the very bits, and dtype, of a per-column
    ``np.asarray`` of each swept point's own characterize dispatch."""
    import jax.numpy as jnp

    from repro.core import characterize as chz
    from repro.core.macro import encode_axes
    cp = ComposePolicy(vdd_sweep=FULL_VDDS, refresh_margin_sweep=FULL_MARGINS)
    points = expand.expansion_points(cp)
    assert len(points) == 12
    metrics, _ = expand.expand_metrics(table, table.metrics, points)
    vecs = jnp.asarray(encode_axes(table.axes))
    blocks = []
    for op, margin in points:
        block = dict(table.metrics)
        if op is not None:
            tp = corners.resolve(corners.as_operating_point(op))
            out = chz._characterize_vmap_jit(tp)(vecs)
            block.update({k: np.asarray(v) for k, v in out.items()})
        if margin is not None:
            block["p_refresh_w"] = block["p_refresh_w"] / float(margin)
        blocks.append(block)
    for k in table.metrics:
        ref = np.concatenate([b[k] for b in blocks])
        assert metrics[k].dtype == ref.dtype, k
        assert np.array_equal(metrics[k], ref), k


def test_to_base_preserves_sentinels():
    idx = np.array([[0, 5, -1], [7, 3, 9]])
    out = expand.to_base(idx, 4)
    np.testing.assert_array_equal(out, [[0, 1, -1], [3, 3, 1]])


def test_bb_rank_identical_to_exhaustive_on_expanded_grid(table):
    """Per-slot contributions still decompose over virtual rows, so the
    branch-and-bound proof stays lossless on the enlarged grid."""
    t = gainsight.TASKS[0]
    kw = dict(vdd_sweep=(VDD_SWEEP_POINT, (0.9, 300.0)),
              refresh_margin_sweep=(0.8,),
              candidate_mode="all_feasible", top_k=5)
    for objective in ("preference", "power"):
        rx = compose(table, t, compose_policy=ComposePolicy(
            search="exhaustive", objective=objective, **kw))
        rb = compose(table, t, compose_policy=ComposePolicy(
            search="branch_and_bound", objective=objective, **kw))
        assert rx.n_space == rb.n_space
        for cx, cb in zip(rx.ranked, rb.ranked):
            assert cx.labels() == cb.labels(), objective
            assert cx.metrics == cb.metrics, objective
            for lvl in cx.levels:
                assert [(p.family, p.config_idx,
                         p.op.corner if p.op else None, p.refresh_margin)
                        for p in cx.levels[lvl].picks] == \
                       [(p.family, p.config_idx,
                         p.op.corner if p.op else None, p.refresh_margin)
                        for p in cb.levels[lvl].picks], objective


# ------------------------------------------------------------------- caching
def test_vdd_sweep_cache_key_sensitivity_and_roundtrip(table, tmp_path):
    """A changed sweep misses; an identical re-call hits and reconstructs
    the swept picks (operating point + margin) exactly."""
    t = gainsight.TASKS[0]
    cp = ComposePolicy(vdd_sweep=(VDD_SWEEP_POINT,),
                       refresh_margin_sweep=(0.8,))
    r1 = compose(table, t, cache=tmp_path, compose_policy=cp)
    n = composition_eval_count()
    r2 = compose(table, t, cache=tmp_path, compose_policy=cp)
    assert composition_eval_count() == n, "identical sweep re-call must hit"
    def picks(rep):
        return {lvl: [(p.family, p.config_idx,
                       p.op.corner if p.op is not None else None,
                       p.refresh_margin) for p in lc.picks]
                for lvl, lc in rep.best.levels.items()}
    assert picks(r2) == picks(r1)
    assert {lvl: lc.tiles for lvl, lc in r2.best.levels.items()} == \
           {lvl: lc.tiles for lvl, lc in r1.best.levels.items()}
    assert r2.best.metrics == r1.best.metrics
    # any change to either sweep axis is a different key -> miss
    compose(table, t, cache=tmp_path,
            compose_policy=ComposePolicy(vdd_sweep=((0.9, 300.0),),
                                         refresh_margin_sweep=(0.8,)))
    assert composition_eval_count() == n + 1
    compose(table, t, cache=tmp_path,
            compose_policy=ComposePolicy(vdd_sweep=(VDD_SWEEP_POINT,),
                                         refresh_margin_sweep=(0.5,)))
    assert composition_eval_count() == n + 2
    compose(table, t, cache=tmp_path,
            compose_policy=ComposePolicy(vdd_sweep=(VDD_SWEEP_POINT,)))
    assert composition_eval_count() == n + 3


# ---------------------------------------------------------------- validation
def test_compose_policy_sweep_validation():
    cp = ComposePolicy(vdd_sweep=(0.9, "hot", (1.2, 233.0)))
    assert [p.corner for p in cp.vdd_sweep] == \
        ["v0.9_t300", "hot", "v1.2_t233"]
    assert all(isinstance(p, corners.OperatingPoint) for p in cp.vdd_sweep)
    with pytest.raises(ValueError, match="collide"):
        ComposePolicy(vdd_sweep=(0.9, (0.9, 300.0)))
    for bad in (0.0, -0.5, 1.5, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="refresh_margin_sweep"):
            ComposePolicy(refresh_margin_sweep=(bad,))
    with pytest.raises(ValueError, match="repeats"):
        ComposePolicy(refresh_margin_sweep=(0.8, 0.8))


def test_sweeps_reject_robust_mode():
    with pytest.raises(ValueError, match="worst_case"):
        compose(None, gainsight.TASKS[0], robust="worst_case",
                compose_policy=ComposePolicy(vdd_sweep=(0.9,)))
    with pytest.raises(ValueError, match="worst_case"):
        compose(None, gainsight.TASKS[0], robust="worst_case",
                compose_policy=ComposePolicy(refresh_margin_sweep=(0.5,)))


# ------------------------------------------------------- solver properties
_GC_CELLS = tuple(sorted(set(bitcells.BITCELLS) - {"sram6t"}))


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(_GC_CELLS),
       st.floats(min_value=233.0, max_value=370.0),
       st.floats(min_value=1.0, max_value=40.0))
def test_solver_retention_monotone_non_increasing_in_temperature(
        name, temp_k, dt_k):
    """Hotter die -> the transient solver may never report LONGER retention
    (the property the vdd/temp sweep and the sim drift schedule rely on)."""
    cell = bitcells.BITCELLS[name]
    tp_lo = corners.resolve(corners.as_operating_point((1.1, temp_k)))
    tp_hi = corners.resolve(corners.as_operating_point((1.1, temp_k + dt_k)))
    r_lo = float(retention.retention_time(cell, 0, tp_lo))
    r_hi = float(retention.retention_time(cell, 0, tp_hi))
    assert np.isfinite(r_lo) and r_lo > 0.0
    assert np.isfinite(r_hi) and r_hi > 0.0
    assert r_lo >= r_hi, f"{name}: retention rose {r_lo} -> {r_hi} " \
                         f"with temperature {temp_k} -> {temp_k + dt_k}"


@functools.lru_cache(maxsize=None)
def _swept_retention(vdd: float) -> tuple:
    tbl = DesignTable.from_configs(
        design_space(word_sizes=(16, 64), num_words=(32, 256)))
    pts = ((None, None),
           (corners.as_operating_point((vdd, 300.0)), None))
    metrics, _ = expand.expand_metrics(tbl, tbl.metrics, pts)
    return tuple(np.asarray(metrics["retention_s"], np.float64))


@settings(max_examples=25, deadline=None)
@given(st.sampled_from((0.8, 0.9, 1.2, 1.3)),
       st.floats(min_value=0.05, max_value=1.0))
def test_swept_refresh_intervals_positive_finite(vdd, margin):
    """Every refresh interval derived across the vdd_sweep grid must stay
    positive and finite for every legal margin."""
    ret = np.asarray(_swept_retention(vdd))
    iv = refresh_interval_s(ret, margin)
    assert np.all(iv > 0.0)
    assert np.all(np.isfinite(iv))
