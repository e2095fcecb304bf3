"""Trace-replay throughput benchmark -> BENCH_sim.json.

Measures the simulator's replay rate (compositions simulated per second)
over a large (J compositions x S slots) grid and multi-phase traces, the
end-to-end ``compose(refine="simulate")`` latency, and the Table-2 parity
count through the simulated re-rank. Run::

    python -m benchmarks.sim_replay            # full grid
    python -m benchmarks.sim_replay --quick    # small grid (CI)

One record per run (overwritten) so CI can upload it as an artifact;
fields:

``grid`` / ``slots`` / ``bins`` / ``phases``   replay problem size
``xla``          {latency_s, comps_per_s} — the jit(vmap(scan)) grid path
``interpret``    {latency_s, comps_per_s} — the per-composition loop oracle
                 (quick mode only times a small slice; reported per-comp)
``simulate_ms``  end-to-end Compiler.simulate() wall time for one paper task
``table2_matches``  how many of the 7 paper tasks refine="simulate" keeps
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):            # `python benchmarks/sim_replay.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _time(fn, repeats: int) -> float:
    fn()                                           # warm (jit compile)
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


# the replayed task: two levels of two buckets each (S = 4 slots)
REPLAY_TASK_LEVELS = (
    ("L1", 1 << 20, ((0.6, 1.2e9, 2e-6), (0.4, 5e8, 1e-4))),
    ("L2", 64 << 20, ((0.5, 1e9, 1e-3), (0.5, 2e9, 3e-6))),
)
REPLAY_PHASES = ("prefill", "decode")


def replay_grid(table, J: int, bins: int, seed: int = 0):
    """The synthetic replay problem: ``(cols, idx (J, S), traces)`` with
    uniform random table rows per slot — the same gather + scan cost profile
    as a real top-K re-rank, but with a controllable J."""
    from repro.core.select import Bucket, LevelReq, TaskReq
    from repro.sim import task_traces
    from repro.sim.rerank import sim_cols

    task = TaskReq("bench", "bench", {
        name: LevelReq(name, cap, tuple(Bucket(*b) for b in buckets))
        for name, cap, buckets in REPLAY_TASK_LEVELS})
    traces = task_traces(task, phases=REPLAY_PHASES, n_bins=bins)
    S = sum(len(b) for _, _, b in REPLAY_TASK_LEVELS)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(table), size=(J, S)).astype(np.int32)
    return sim_cols(table), idx, traces


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small grid + fewer reps (CI-sized)")
    ap.add_argument("--out", default="BENCH_sim.json")
    ap.add_argument("--cache", default="artifacts/dse_cache")
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache
    from repro.api import Compiler, DesignTable, design_space
    from repro.core import gainsight
    from repro.sim import simulate_traces
    from repro.sim.engine import SIM_METRICS

    compile_cache.enable()

    table = DesignTable.build(design_space(), cache=args.cache)

    # --- correctness anchor: Table 2 through the simulated re-rank ---------
    c = Compiler()
    t0 = time.perf_counter()
    matches = sum(
        c.simulate(t, space=table).matches(gainsight.TABLE2_EXPECTED[t.task_id])
        for t in gainsight.TASKS)
    simulate_ms = (time.perf_counter() - t0) / len(gainsight.TASKS) * 1e3

    # --- throughput: one big synthetic replay grid -------------------------
    J = 2_000 if args.quick else 50_000
    bins = 16 if args.quick else 32
    cols, idx, traces = replay_grid(table, J, bins)
    S = idx.shape[1]
    reps = 3 if args.quick else 10

    t_xla = _time(lambda: simulate_traces(cols, idx, traces, backend="xla"),
                  reps)
    # the interpret oracle is O(J) python dispatches; time a small slice
    J_int = min(J, 64)
    t_int = _time(lambda: simulate_traces(cols, idx[:J_int], traces,
                                          backend="interpret"), 1)

    record = {
        "bench": "sim_replay",
        "quick": bool(args.quick),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "table_configs": len(table),
        "metrics": list(SIM_METRICS),
        "grid": J,
        "slots": S,
        "bins": bins,
        "phases": list(REPLAY_PHASES),
        "xla": {
            "latency_s": round(t_xla, 6),
            "comps_per_s": round(J / t_xla, 1),
        },
        "interpret": {
            "grid": J_int,
            "latency_s": round(t_int, 6),
            "comps_per_s": round(J_int / t_int, 1),
        },
        "simulate_ms": round(simulate_ms, 3),
        "table2_matches": int(matches),
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
