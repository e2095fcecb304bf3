"""Regenerate the golden regression snapshot ``tests/golden/table2.json``.

The snapshot freezes (a) the nominal-corner Table-2 selections through
``explore``, (b) the full characterization of a small, fixed config slice
and (c) the same slice at the four named operating corners — every metric
as the float64 repr of the float32 the vmap pipeline produced. ``tests/test_golden.py`` diffs live results against this file
under the contract of ``golden_diff`` (discrete values exact, float metrics
within ``FLOAT_RTOL``), so any edit to the physics fails loudly instead of
silently drifting. ``chip_smoke.py`` holds a chip run to the same contract.

Two equivalent update paths (documented in docs/API.md):

    python scripts/update_golden.py
    python -m pytest tests/test_golden.py --update-golden

Only regenerate after an *intentional* physics change, and say so in the
commit message.
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from typing import Dict, List, Tuple

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

GOLDEN_PATH = REPO / "tests" / "golden" / "table2.json"
NLEVEL_PATH = REPO / "tests" / "golden" / "table2_nlevel.json"
VDD_PATH = REPO / "tests" / "golden" / "table2_vdd.json"

# the frozen vdd-sweep operating point: a cold, boosted-supply block
# ((vdd [V], temp_k [K])) under which OS-Si gains the frequency headroom to
# take over retention-marginal L1/L2 buckets — the co-optimization axis must
# keep flipping exactly these Table-2 winners (the MCAIMem effect)
VDD_SWEEP_POINT = (1.2, 233.0)

# the frozen slice: small but covers every mem type, LS on/off, and both a
# shallow and a deep array (delay-chain quantization edge)
SLICE_KW = dict(word_sizes=(16, 64), num_words=(32, 256))

# the frozen N-level reference: 3 levels of gainsight.NLEVEL_REFERENCE,
# composed under each of these (name -> ComposePolicy kwargs) settings
NLEVEL_POLICIES = {
    "preference": dict(),
    "power_bb": dict(objective="power", candidate_mode="all_feasible",
                     search="branch_and_bound"),
}


# The golden contract, shared by the golden tests and chip_smoke.py.
# Labels, picks, config indices, operating points, tile counts and array
# geometry compare exactly: they are discrete, and a flipped one is a real
# change, never noise. Float metrics compare within FLOAT_RTOL relative
# error: they are float32 results of exp/log-heavy device models and a
# 480-step RK4, and the TPU's exp/log/divide round the last bits
# differently from XLA:CPU, which the subthreshold exp and the RK4 integral
# carry into leakage, retention and refresh power. On a TPU v5e the worst
# drift from these XLA:CPU goldens was 3.3e-4 (retention_s at the hot
# corner of the corner slice); at the nominal corner 1.5e-4 (the
# vdd-sweep winners' p_w; retention_s 1.4e-4, p_leak_w 1.3e-4). The bound
# keeps 3x headroom over the worst.
FLOAT_RTOL = 1e-3
# keys whose values are discrete although some are stored as floats
EXACT_KEYS = frozenset({"rows", "cols", "mux", "bits", "picks", "tiles"})


def _rel_err(want: float, got: float) -> float:
    if want == got or (math.isnan(want) and math.isnan(got)):
        return 0.0
    if math.isnan(want) or math.isnan(got) or math.isinf(want) \
            or math.isinf(got) or want == 0.0:
        return math.inf
    return abs(got - want) / abs(want)


def golden_diff(want, got) -> Tuple[List[str], Dict[str, float]]:
    """Compare a live snapshot ``got`` with the golden ``want`` (nested
    dicts/lists of JSON values) under the contract above.

    Returns ``(mismatches, worst)``: one line per value that breaks the
    contract, and the worst relative error seen per float metric name."""
    mismatches: List[str] = []
    worst: Dict[str, float] = {}

    def walk(w, g, path, key, exact):
        if isinstance(w, dict):
            if not isinstance(g, dict) or set(w) != set(g):
                mismatches.append(f"{path}: keys {sorted(w)} != "
                                  f"{sorted(g) if isinstance(g, dict) else g!r}")
                return
            for k in w:
                # a corner column "<metric>@<corner>" is as exact as <metric>
                walk(w[k], g[k], f"{path}.{k}", k,
                     exact or k.split("@")[0] in EXACT_KEYS)
        elif isinstance(w, list):
            if not isinstance(g, (list, tuple)) or len(w) != len(g):
                mismatches.append(f"{path}: golden={w!r} live={g!r}")
                return
            for i, (wi, gi) in enumerate(zip(w, g)):
                walk(wi, gi, f"{path}[{i}]", key, exact)
        elif isinstance(w, float) and not exact:
            err = _rel_err(w, float(g))
            worst[key] = max(worst.get(key, 0.0), err)
            if not err <= FLOAT_RTOL:
                mismatches.append(f"{path}: golden={w!r} live={float(g)!r} "
                                  f"(rel {err:.3g} > {FLOAT_RTOL:g})")
        elif isinstance(w, float):
            if _rel_err(w, float(g)) != 0.0:
                mismatches.append(f"{path}: golden={w!r} live={g!r} "
                                  f"(discrete, must be exact)")
        elif w != g:
            mismatches.append(f"{path}: golden={w!r} live={g!r}")

    walk(want, got, "", "", False)
    return mismatches, worst


def characterization_rows(table, idx=None) -> List[dict]:
    """Rows of a DesignTable as golden-snapshot dicts (``idx``: row order,
    default all rows), with every ``<metric>@<corner>`` column of a
    multi-corner table."""
    idx = range(len(table)) if idx is None else idx
    rows = []
    for i in idx:
        row = table.row(int(i))
        rows.append({k: (float(v) if isinstance(v, float) else v)
                     for k, v in row.items()})
    return rows


def corner_slice_table():
    """The frozen slice characterized at the four named corners."""
    from repro.api import DesignTable, design_space
    from repro.core.corners import CORNERS
    return DesignTable.from_configs(design_space(**SLICE_KW),
                                    corners=list(CORNERS.values()))


def build_snapshot() -> dict:
    import jax

    from repro.api import DesignTable, design_space, explore
    from repro.core import gainsight

    report = explore(tasks=gainsight.TASKS)
    table2 = {str(t.task_id): report.labels()[t.task_id]
              for t in gainsight.TASKS}

    table = DesignTable.from_configs(design_space(**SLICE_KW))
    return {
        "comment": "golden regression snapshot - regenerate ONLY via "
                   "scripts/update_golden.py or pytest --update-golden",
        "jax_version": jax.__version__,
        "slice": {k: list(v) for k, v in SLICE_KW.items()},
        "table2": table2,
        "characterization": characterization_rows(table),
        "corners": characterization_rows(corner_slice_table()),
    }


def compose_nlevel(policy_kw: dict):
    """One 3-level reference composition (shared with the golden test so the
    live recomputation and the snapshot can never use different settings)."""
    from repro.core.gainsight import nlevel_task
    from repro.hetero import ComposePolicy, compose
    return compose(None, nlevel_task(3),
                   compose_policy=ComposePolicy(**policy_kw))


def build_nlevel_snapshot() -> dict:
    import jax

    compositions = {}
    for name, kw in NLEVEL_POLICIES.items():
        rep = compose_nlevel(kw)
        best = rep.best
        compositions[name] = {
            "labels": best.labels(),
            "picks": {lvl: [p.config_idx for p in lc.picks]
                      for lvl, lc in best.levels.items()},
            "tiles": {lvl: list(lc.tiles)
                      for lvl, lc in best.levels.items()},
            # float64 repr of the float32 the scoring kernel produced
            "metrics": {k: float(v) for k, v in best.metrics.items()},
            "search": rep.search,
            "n_space": rep.n_space,
        }
    return {
        "comment": "golden N-level composition snapshot - regenerate ONLY "
                   "via scripts/update_golden.py or pytest --update-golden",
        "jax_version": jax.__version__,
        "task": "nlevel3",
        "compositions": compositions,
    }


def compose_vdd(task, swept: bool):
    """One Table-2 task composed with/without the frozen vdd sweep (shared
    with tests/test_vdd_sweep.py so live and snapshot settings cannot
    diverge)."""
    from repro.hetero import ComposePolicy, compose
    cp = ComposePolicy(vdd_sweep=(VDD_SWEEP_POINT,)) if swept \
        else ComposePolicy()
    return compose(None, task, compose_policy=cp)


def _picks(comp) -> dict:
    """Per level: [family, config index, corner, refresh margin] per pick."""
    return {lvl: [[p.family, p.config_idx,
                   p.op.corner if p.op is not None else None,
                   p.refresh_margin]
                  for p in lc.picks]
            for lvl, lc in comp.levels.items()}


def vdd_tasks() -> dict:
    """The 7 Table-2 tasks with and without the frozen vdd sweep point."""
    from repro.core import gainsight

    tasks = {}
    for t in gainsight.TASKS:
        base = compose_vdd(t, swept=False)
        swept = compose_vdd(t, swept=True)
        tasks[str(t.task_id)] = {
            "base_labels": base.labels(),
            "swept_labels": swept.labels(),
            "flipped": swept.labels() != base.labels(),
            "picks": _picks(swept.best),
            "p_w": {"base": float(base.best.metrics["p_w"]),
                    "swept": float(swept.best.metrics["p_w"])},
        }
    return tasks


def full_sweep_tasks() -> dict:
    """The 7 Table-2 tasks composed over the full sweep of
    ``benchmarks/vdd_sweep.py`` (3 vdd points x 2 refresh margins): the
    winner's labels, picks, tiles and power."""
    from benchmarks.vdd_sweep import FULL_MARGINS, FULL_VDDS
    from repro.core import gainsight
    from repro.hetero import ComposePolicy, compose

    cp = ComposePolicy(vdd_sweep=FULL_VDDS, refresh_margin_sweep=FULL_MARGINS)
    tasks = {}
    for t in gainsight.TASKS:
        best = compose(None, t, compose_policy=cp).best
        tasks[str(t.task_id)] = {
            "labels": best.labels(),
            "picks": _picks(best),
            "tiles": {lvl: list(lc.tiles) for lvl, lc in best.levels.items()},
            "p_w": float(best.metrics["p_w"]),
        }
    return tasks


def build_vdd_snapshot() -> dict:
    import jax

    return {
        "comment": "golden vdd-sweep flip snapshot - regenerate ONLY via "
                   "scripts/update_golden.py or pytest --update-golden",
        "jax_version": jax.__version__,
        "vdd_sweep_point": list(VDD_SWEEP_POINT),
        "tasks": vdd_tasks(),
        "full_sweep": full_sweep_tasks(),
    }


def write_snapshot(path: Path = GOLDEN_PATH) -> Path:
    snap = build_snapshot()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(snap, indent=1, sort_keys=True) + "\n")
    return path


def write_nlevel_snapshot(path: Path = NLEVEL_PATH) -> Path:
    snap = build_nlevel_snapshot()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(snap, indent=1, sort_keys=True) + "\n")
    return path


def write_vdd_snapshot(path: Path = VDD_PATH) -> Path:
    snap = build_vdd_snapshot()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(snap, indent=1, sort_keys=True) + "\n")
    return path


if __name__ == "__main__":
    for p in (write_snapshot(), write_nlevel_snapshot(),
              write_vdd_snapshot()):
        print(f"wrote {p}")
