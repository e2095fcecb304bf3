"""Heterogeneous-composition throughput benchmark -> BENCH_hetero.json.

Measures the composition engine's scoring rate (compositions priced per
second) over a large joint (L1, L2) grid, single-device vs sharded across
every visible device, plus the end-to-end ``compose()`` latency and the
Table-2 parity count. Run::

    python -m benchmarks.hetero_dse            # full grid
    python -m benchmarks.hetero_dse --quick    # small grid (CI)

The record is appended-to-by-overwrite (one file per run) so CI can upload
it as an artifact; fields:

``grid``             compositions scored per timing rep
``single_device``    {latency_s, configs_per_s}
``sharded``          {latency_s, configs_per_s, devices}  (equal results —
                     see tests/test_hetero.py for the equivalence proof)
``compose_ms``       end-to-end compose() wall time for one paper task
``table2_matches``   how many of the 7 paper tasks compose() reproduces
``arch_tasks``       profiler-side (arch x shape) cells composed, if dry-run
                     artifacts exist in this checkout
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):            # `python benchmarks/hetero_dse.py`
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
                    help="small grid + fewer reps (CI-sized)")
    ap.add_argument("--out", default="BENCH_hetero.json")
    ap.add_argument("--cache", default="artifacts/dse_cache")
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    compile_cache.enable()
    from repro.api import DesignTable, design_space
    from repro.core import gainsight
    from repro.hetero import ComposePolicy, compose
    from repro.hetero.system import METRIC_COLS, score_grid

    table = DesignTable.build(design_space(), cache=args.cache)

    # --- correctness anchor: Table 2 through the joint path ----------------
    t0 = time.perf_counter()
    matches = sum(
        compose(table, t).matches(gainsight.TABLE2_EXPECTED[t.task_id])
        for t in gainsight.TASKS)
    compose_ms = (time.perf_counter() - t0) / len(gainsight.TASKS) * 1e3

    # --- profiler-side tasks (present only when dry-runs were generated) ---
    from repro.profiler.traffic import available_arch_tasks
    arch_tasks = available_arch_tasks()
    arch_labels = {}
    for t in arch_tasks:
        arch_labels[str(t.task_id)] = compose(
            table, t, compose_policy=ComposePolicy(objective="power")).labels()

    # --- throughput: one big synthetic joint grid --------------------------
    # (uniform random rows per slot — same gather/reduce cost profile as a
    # real all_feasible cross-product, but with a controllable J)
    J = 20_000 if args.quick else 500_000
    S = 5                                   # L1 x1 + L2 x3 + spill slot
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(table), size=(J, S)).astype(np.int32)
    cap = [1e6, 1e8, 1e8, 5e7, 1e6]
    f_req = [1e9, 2e9, 1e9, 5e8, 1e9]
    reps = 3 if args.quick else 10

    t_single = _time(lambda: score_grid(table.metrics, idx, cap, f_req,
                                        sharded=False), reps)
    t_sharded = _time(lambda: score_grid(table.metrics, idx, cap, f_req,
                                         sharded=True), reps)

    record = {
        "bench": "hetero_dse",
        "quick": bool(args.quick),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "table_configs": len(table),
        "metric_cols": list(METRIC_COLS),
        "grid": J,
        "slots": S,
        "single_device": {
            "latency_s": round(t_single, 6),
            "configs_per_s": round(J / t_single, 1),
        },
        "sharded": {
            "latency_s": round(t_sharded, 6),
            "configs_per_s": round(J / t_sharded, 1),
            "devices": jax.device_count(),
        },
        "compose_ms": round(compose_ms, 3),
        "table2_matches": int(matches),
        "arch_tasks": len(arch_tasks),
        "arch_labels": arch_labels,
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
