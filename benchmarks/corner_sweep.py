"""Operating-corner sweep benchmark -> BENCH_corners.json.

Measures the throughput of the (designs × corners) vmapped characterization
(rows/s where a row = one config at one corner), the corner-robust
worst-case DSE latency, and the physics deltas the corner axis exists for
(hot-corner retention shrink, nominal Table-2 parity). Run::

    python -m benchmarks.corner_sweep            # full grid, 4 corners
    python -m benchmarks.corner_sweep --quick    # CI-sized

One record per run (overwritten) so CI can upload it as an artifact;
fields:

``configs`` / ``corners``      sweep problem size
``rows``                       configs × corners characterized per sweep
``sweep``        {latency_s, rows_per_s} — the jit(vmap(vmap)) corner grid
``nominal``      {latency_s, rows_per_s} — the single-corner baseline vmap
``robust_explore_ms``          worst-case explore() over the corner table
``retention_shrink_hot``       median nominal/hot retention ratio (GC rows)
``table2_matches``             nominal-corner Table-2 parity (must be 7)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):          # `python benchmarks/corner_sweep.py`
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
                    help="smaller grid + fewer reps (CI-sized)")
    ap.add_argument("--out", default="BENCH_corners.json")
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    compile_cache.enable()
    import jax.numpy as jnp
    from repro.api import DesignTable, design_space, explore
    from repro.core import corners, gainsight
    from repro.core.characterize import (characterize_batch,
                                         characterize_corners)

    if args.quick:
        configs = design_space(word_sizes=(16, 64), num_words=(32, 256))
        ops = [corners.NOMINAL, corners.HOT]
        reps = 3
    else:
        configs = design_space(
            word_sizes=(16, 32, 64, 128),
            num_words=(16, 32, 64, 128, 256, 512, 1024))
        ops = [corners.NOMINAL, corners.HOT, corners.COLD, corners.LOW_VDD]
        reps = 10

    vecs = jnp.stack([c.to_vector() for c in configs])
    rows = len(configs) * len(ops)

    def sweep():
        out = characterize_corners(vecs, ops)
        jax.block_until_ready(out["retention_s"])
        return out

    def nominal():
        out = characterize_batch(vecs)
        jax.block_until_ready(out["retention_s"])
        return out

    t_sweep = _time(sweep, reps)
    t_nom = _time(nominal, reps)

    # physics deltas + DSE anchors
    grid = sweep()
    ret = np.asarray(grid["retention_s"], np.float64)
    gc = ret[:, 0] < 1e11                          # GC rows (SRAM rows = 1e12)
    shrink = float(np.median(ret[gc, 0] / ret[gc, 1]))   # nominal / hot

    table = DesignTable.from_configs(configs, corners=ops)
    t0 = time.perf_counter()
    explore(space=table, tasks=gainsight.TASKS, robust="worst_case")
    robust_ms = (time.perf_counter() - t0) * 1e3

    matches = explore(tasks=gainsight.TASKS).matches(
        gainsight.TABLE2_EXPECTED)

    record = {
        "bench": "corner_sweep",
        "quick": bool(args.quick),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "configs": len(configs),
        "corners": [op.corner for op in ops],
        "rows": rows,
        "sweep": {
            "latency_s": round(t_sweep, 6),
            "rows_per_s": round(rows / t_sweep, 1),
        },
        "nominal": {
            "latency_s": round(t_nom, 6),
            "rows_per_s": round(len(configs) / t_nom, 1),
        },
        "robust_explore_ms": round(robust_ms, 3),
        "retention_shrink_hot": round(shrink, 2),
        "table2_matches": int(matches),
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
