"""N-level branch-and-bound benchmark -> BENCH_hetero_nlevel.json.

Pits the two composition search engines against each other on the 4-level
reference hierarchy (``repro.core.gainsight.nlevel_task(4)``) with
``all_feasible`` candidates: the exhaustive cross-product grid (trimmed to
``max_compositions``) versus the lossless branch-and-bound of
``repro.hetero.search``. Records scoring throughput and — the headline —
the pruning ratio: how many fewer compositions branch-and-bound scored
while returning the identical best design. Run::

    python -m benchmarks.hetero_nlevel            # full
    python -m benchmarks.hetero_nlevel --quick    # fewer reps (CI)

Fields:

``n_space``                full cross-product size (python int)
``exhaustive``             {n_scored, latency_s, scored_per_s, truncated}
``branch_and_bound``       {n_scored, latency_s, scored_per_s, truncated}
``pruning_ratio``          exhaustive.n_scored / branch_and_bound.n_scored
``identical_best``         both engines picked the same composition (picks
                           AND float32 system metrics, bit-for-bit)
``corner_grid``            2D (compositions x corners) scoring throughput
                           via ``score_grid_corners``
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):          # `python benchmarks/hetero_nlevel.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _time(fn, repeats: int) -> float:
    fn()                                           # warm (jit compile)
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing reps (CI-sized)")
    ap.add_argument("--out", default="BENCH_hetero_nlevel.json")
    ap.add_argument("--cache", default="artifacts/dse_cache")
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    compile_cache.enable()
    from repro.api import DesignTable, design_space
    from repro.core.gainsight import nlevel_task
    from repro.hetero import ComposePolicy, compose
    from repro.hetero.system import SYSTEM_METRICS, score_grid_corners

    table = DesignTable.build(design_space(), cache=args.cache)
    task = nlevel_task(4)
    reps = 2 if args.quick else 5
    kw = dict(objective="power", candidate_mode="all_feasible",
              max_candidates_per_bucket=16)
    cp_ex = ComposePolicy(search="exhaustive", max_compositions=200_000, **kw)
    cp_bb = ComposePolicy(search="branch_and_bound", **kw)

    r_ex = compose(table, task, compose_policy=cp_ex)
    r_bb = compose(table, task, compose_policy=cp_bb)
    t_ex = _time(lambda: compose(table, task, compose_policy=cp_ex), reps)
    t_bb = _time(lambda: compose(table, task, compose_policy=cp_bb), reps)

    same_picks = all(
        [p.config_idx for p in r_ex.best.levels[lvl].picks]
        == [p.config_idx for p in r_bb.best.levels[lvl].picks]
        for lvl in task.levels)
    same_metrics = all(r_ex.best.metrics[m] == r_bb.best.metrics[m]
                       for m in SYSTEM_METRICS)

    # --- 2D (compositions x corners) scoring throughput --------------------
    corner_table = DesignTable.build(design_space(), cache=args.cache,
                                     corners=("nominal", "hot", "low_vdd"))
    cms = [corner_table.corner_metrics(c)
           for c in corner_table.corner_labels]
    J = 5_000 if args.quick else 50_000
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(corner_table), size=(J, 5)).astype(np.int32)
    cap = [1e6, 1e8, 1e8, 5e7, 1e6]
    f_req = [1e9, 2e9, 1e9, 5e8, 1e9]
    t_corner = _time(lambda: score_grid_corners(cms, idx, cap, f_req), reps)

    record = {
        "bench": "hetero_nlevel",
        "quick": bool(args.quick),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "task": str(task.task_id),
        "slots": sum(len(lv.buckets) for lv in task.levels.values()),
        "n_space": int(r_ex.n_space),
        "exhaustive": {
            "n_scored": int(r_ex.n_compositions),
            "latency_s": round(t_ex, 6),
            "scored_per_s": round(r_ex.n_compositions / t_ex, 1),
            "truncated": bool(r_ex.truncated),
        },
        "branch_and_bound": {
            "n_scored": int(r_bb.n_compositions),
            "latency_s": round(t_bb, 6),
            "scored_per_s": round(r_bb.n_compositions / t_bb, 1),
            "truncated": bool(r_bb.truncated),
        },
        "pruning_ratio": round(r_ex.n_compositions
                               / max(r_bb.n_compositions, 1), 2),
        "identical_best": bool(same_picks and same_metrics),
        "best_labels": r_bb.labels(),
        "corner_grid": {
            "compositions": J,
            "corners": len(cms),
            "latency_s": round(t_corner, 6),
            "rows_per_s": round(J * len(cms) / t_corner, 1),
        },
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
