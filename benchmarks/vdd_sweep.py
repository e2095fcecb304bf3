"""Per-level (vdd, refresh-margin) co-optimization benchmark -> BENCH_vdd.json.

Measures the throughput of the operating-point expansion (swept configs/s,
where a swept config = one table row characterized/priced at one extra
(vdd, margin) point), the end-to-end swept compose latency over the 7
Table-2 tasks, and the search-quality anchors the axis exists for: the
cold-boost sweep point must keep flipping the golden-locked winners, and
branch-and-bound must stay rank-identical to exhaustive on the enlarged
grid. Run::

    python -m benchmarks.vdd_sweep            # full grid, 3 sweep points
    python -m benchmarks.vdd_sweep --quick    # CI-sized

One record per run (overwritten) so CI can upload it as an artifact;
fields:

``configs`` / ``points``       base rows and expansion points (incl. base)
``rows``                       configs × points in the expanded grid
``expand``       {latency_s, swept_configs_per_s} — the per-corner vmapped
                 expansion of every non-base block
``compose``      {latency_s, tasks_per_s} — 7 swept Table-2 composes
``flips``        {matches} — tasks whose winner the sweep flips (golden: 4)
``task<k>``      {best_labels} for every flipped task (exact parity)
``bb.identical_best``          B&B == exhaustive best on the enlarged grid
``table2_matches``             base-point Table-2 parity (must be 7)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):            # `python benchmarks/vdd_sweep.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# the golden flip point (scripts/update_golden.py VDD_SWEEP_POINT): cold die,
# boosted supply — OS-Si gains the frequency headroom to take L1/L2 buckets
SWEEP_POINT = (1.2, 233.0)
# the full sweep: 3 (vdd [V], temp_k [K]) points x 2 refresh margins
FULL_VDDS = (SWEEP_POINT, (0.9, 300.0), (1.1, 358.0))
FULL_MARGINS = (0.8, 0.5)


def _time(fn, repeats: int) -> float:
    fn()                                           # warm (jit compile)
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fewer sweep points + reps (CI-sized)")
    ap.add_argument("--out", default="BENCH_vdd.json")
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    compile_cache.enable()
    from repro.api import DesignTable, design_space
    from repro.core import gainsight
    from repro.hetero import ComposePolicy, compose, expand

    if args.quick:
        vdds = (SWEEP_POINT,)
        margins = (0.8,)
        reps = 2
    else:
        vdds = FULL_VDDS
        margins = FULL_MARGINS
        reps = 5

    table = DesignTable.from_configs(design_space())
    cp = ComposePolicy(vdd_sweep=vdds, refresh_margin_sweep=margins)
    points = expand.expansion_points(cp)
    n_base = len(table)
    rows = n_base * len(points)
    swept = rows - n_base                      # non-base blocks actually built

    def expand_once():
        metrics, fams = expand.expand_metrics(table, table.metrics, points)
        jax.block_until_ready(metrics["retention_s"])
        return metrics

    t_expand = _time(expand_once, reps)

    flip_cp = ComposePolicy(vdd_sweep=(SWEEP_POINT,))

    def compose_tasks():
        return [compose(table, t, compose_policy=flip_cp)
                for t in gainsight.TASKS]

    t_compose = _time(compose_tasks, max(reps // 2, 1))

    # search-quality anchors: base parity, golden flips, B&B losslessness
    base = {t.task_id: compose(table, t) for t in gainsight.TASKS}
    matches = sum(base[t.task_id].matches(gainsight.TABLE2_EXPECTED[t.task_id])
                  for t in gainsight.TASKS)
    flipped = {}
    for t, rep in zip(gainsight.TASKS, compose_tasks()):
        if rep.labels() != base[t.task_id].labels():
            flipped[f"task{t.task_id}"] = {"best_labels": rep.labels()}

    bb_kw = dict(vdd_sweep=vdds, refresh_margin_sweep=margins,
                 objective="power", candidate_mode="all_feasible")
    ex = compose(table, gainsight.TASKS[0], compose_policy=ComposePolicy(
        search="exhaustive", **bb_kw))
    bb = compose(table, gainsight.TASKS[0], compose_policy=ComposePolicy(
        search="branch_and_bound", **bb_kw))
    bb_same = bool(bb.labels() == ex.labels()
                   and bb.best.metrics == ex.best.metrics)

    record = {
        "bench": "vdd_sweep",
        "quick": bool(args.quick),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "configs": n_base,
        "points": len(points),
        "rows": rows,
        "sweep_point": list(SWEEP_POINT),
        "expand": {
            "latency_s": round(t_expand, 6),
            "swept_configs_per_s": round(swept / t_expand, 1),
        },
        "compose": {
            "latency_s": round(t_compose, 6),
            "tasks_per_s": round(len(gainsight.TASKS) / t_compose, 2),
        },
        "flips": {"matches": sorted(flipped)},
        **flipped,
        "bb": {"identical_best": bb_same},
        "table2_matches": int(matches),
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
