"""Benchmark harness: CSV summary (default) or the JSON suite + compare mode.

Default (no flags) prints the legacy ``name,us_per_call,derived`` CSV —
one line per paper table/figure plus compiler-throughput and roofline
summaries::

    pip install -e . && python -m benchmarks.run

Suite mode runs the four record-emitting benchmark modules **in-process**
(one process, so a single ``REPRO_TRACE`` trace covers the whole suite) and
optionally diffs the emitted ``BENCH_*.json`` set against committed
baselines (``benchmarks/compare.py``)::

    python -m benchmarks.run --quick --compare benchmarks/baselines

Emitted file set (the *only* BENCH files this repo produces; committed
baselines live under ``benchmarks/baselines/``):

    BENCH_hetero.json          benchmarks.hetero_dse
    BENCH_hetero_nlevel.json   benchmarks.hetero_nlevel
    BENCH_sim.json             benchmarks.sim_replay
    BENCH_corners.json         benchmarks.corner_sweep
    BENCH_vdd.json             benchmarks.vdd_sweep
    BENCH_diff.json            the compare result (suite mode only)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):                    # `python benchmarks/run.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# (bench label, module name, emitted record file) — keep in sync with the
# committed baseline set and docs/OBSERVABILITY.md
SUITE = (
    ("hetero", "benchmarks.hetero_dse", "BENCH_hetero.json"),
    ("hetero_nlevel", "benchmarks.hetero_nlevel", "BENCH_hetero_nlevel.json"),
    ("sim", "benchmarks.sim_replay", "BENCH_sim.json"),
    ("corners", "benchmarks.corner_sweep", "BENCH_corners.json"),
    ("vdd", "benchmarks.vdd_sweep", "BENCH_vdd.json"),
)


def _timed(fn, repeats=1):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn()
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6


def run_suite(quick: bool, out_dir: Path,
              compare_dir=None, rate_tolerance: float = 0.5) -> dict:
    """Run every SUITE module main() in-process, then (optionally) diff the
    emitted records against ``compare_dir``. Returns the diff (or a stub
    with ``ok=True`` when no compare was requested)."""
    import importlib

    out_dir.mkdir(parents=True, exist_ok=True)
    for label, modname, fname in SUITE:
        print(f"[suite] {label}: python -m {modname}"
              f"{' --quick' if quick else ''}", flush=True)
        mod = importlib.import_module(modname)
        argv = ["--out", str(out_dir / fname)] + (["--quick"] if quick else [])
        mod.main(argv)

    if compare_dir is None:
        return {"ok": True, "benches": {}, "regressions": []}

    from benchmarks import compare

    diff = compare.diff_suite(compare_dir, out_dir,
                              rate_tolerance=rate_tolerance)
    diff_path = out_dir / "BENCH_diff.json"
    diff_path.write_text(json.dumps(diff, indent=2) + "\n")
    print(compare.summarize(diff))
    print(f"[suite] wrote {diff_path}")
    return diff


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="suite mode, CI-sized grids")
    ap.add_argument("--suite", action="store_true",
                    help="suite mode, full grids")
    ap.add_argument("--compare", metavar="BASELINE_DIR", default=None,
                    help="diff emitted BENCH_*.json against this directory "
                         "and write BENCH_diff.json (implies suite mode)")
    ap.add_argument("--out-dir", default=".",
                    help="where suite mode writes BENCH_*.json (default: cwd)")
    ap.add_argument("--rate-tolerance", type=float, default=0.5,
                    help="throughput ratio below this is a regression "
                         "(default 0.5 = 2x band)")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when the compare finds regressions "
                         "(default: informational, exit 0)")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()
    if args.quick or args.suite or args.compare is not None:
        diff = run_suite(args.quick, Path(args.out_dir),
                         compare_dir=args.compare,
                         rate_tolerance=args.rate_tolerance)
        if args.fail_on_regression and not diff["ok"]:
            sys.exit(1)
        return
    _csv_main()


def _csv_main() -> None:
    from benchmarks import paper_figs

    print("name,us_per_call,derived")
    for fn in paper_figs.ALL:
        (rows, derived), us = _timed(fn)
        print(f"{fn.__name__},{us:.0f},\"{derived}\"")

    # compiler throughput: vmap'd characterization of the whole design space
    from repro.api import DesignTable, design_space

    def sweep():
        table = DesignTable.from_configs(design_space())
        return table, len(table)

    (table, n), us = _timed(sweep)
    print(f"characterize_design_space,{us:.0f},\"{n} configs PPA+retention "
          f"({us / max(n,1):.0f} us/config incl. transient solve)\"")

    # Pallas retention kernel (interpret mode on CPU)
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.retention_kernel import retention_pallas
    from repro.core.retention import time_grid
    rng = np.random.default_rng(0)
    params = jnp.asarray(np.column_stack([
        rng.uniform(0.4, 0.8, 256), np.full(256, 1.2),
        np.full(256, 2e-6), np.full(256, 0.05), np.full(256, 1e-16),
        np.full(256, 3e-15), np.full(256, 1e-15), np.full(256, 0.1),
        rng.uniform(0.6, 1.1, 256), np.full(256, 0.5)]), jnp.float32)
    ts = time_grid()
    _, us = _timed(lambda: retention_pallas(params, ts, interpret=True)
                   .block_until_ready())
    print(f"retention_kernel_interpret,{us:.0f},\"256-config RK4 transient "
          f"(Pallas interpret; TPU target is the native path)\"")

    # joint composition throughput (full record: python -m benchmarks.hetero_dse)
    from repro.core import gainsight
    from repro.hetero import compose

    def compose_all(refine=None):
        reports = [compose(table, t, refine=refine) for t in gainsight.TASKS]
        return reports, sum(r.matches(gainsight.TABLE2_EXPECTED[r.task.task_id])
                            for r in reports)

    (_, n_match), us = _timed(compose_all)
    print(f"hetero_compose,{us:.0f},\"joint (L1,L2) composition for 7 tasks; "
          f"Table 2 matches {n_match}/7\"")

    # N-level branch-and-bound vs exhaustive (full record:
    # python -m benchmarks.hetero_nlevel)
    from repro.core.gainsight import nlevel_task
    from repro.hetero import ComposePolicy

    def nlevel_bb():
        kw = dict(objective="power", candidate_mode="all_feasible",
                  max_candidates_per_bucket=16)
        ex = compose(table, nlevel_task(4), compose_policy=ComposePolicy(
            search="exhaustive", max_compositions=50_000, **kw))
        bb = compose(table, nlevel_task(4), compose_policy=ComposePolicy(
            search="branch_and_bound", **kw))
        same = bb.labels() == ex.labels()
        return (ex, bb), (ex.n_compositions, bb.n_compositions, same)

    (_, (n_ex, n_bb, same)), us = _timed(nlevel_bb)
    print(f"hetero_nlevel,{us:.0f},\"4-level B&B scored {n_bb} vs "
          f"{n_ex} exhaustive ({n_ex / max(n_bb, 1):.0f}x pruning); "
          f"identical best: {same}\"")

    # trace replay + simulated re-rank (full record: python -m benchmarks.sim_replay)
    (_, n_sim_match), us = _timed(lambda: compose_all(refine="simulate"))
    print(f"sim_replay,{us:.0f},\"simulate-then-rerank for 7 tasks "
          f"(prefill+decode traces, top-8 re-rank); Table 2 matches "
          f"{n_sim_match}/7\"")

    # operating-corner sweep (full record: python -m benchmarks.corner_sweep)
    import jax
    from repro.core import corners
    from repro.core.characterize import characterize_corners

    ops = [corners.NOMINAL, corners.HOT]
    corner_vecs = jnp.stack([c.to_vector() for c in design_space()])

    def corner_sweep():
        out = characterize_corners(corner_vecs, ops)
        jax.block_until_ready(out["retention_s"])
        return out, corner_vecs.shape[0] * len(ops)

    (_, n_rows), us = _timed(corner_sweep)
    print(f"corner_sweep,{us:.0f},\"{n_rows} (config,corner) rows "
          f"PPA+retention under one vmapped corner grid\"")

    # per-arch heterogeneous-memory DSE (the paper's technique on our archs)
    # and the roofline table: both read dry-run records, which a fresh
    # checkout does not have; any other failure raises
    from benchmarks.arch_dse import arch_dse_table
    (rows, derived), us = _timed(arch_dse_table)
    if rows:
        print(f"arch_dse,{us:.0f},\"{derived}\"")
    else:
        print("arch_dse,0,\"not measured: no dry-run records\"")

    from repro.launch.roofline import load_table
    rows = load_table()
    if rows:
        worst = min(rows, key=lambda r: r["roofline_fraction"])
        bound = {}
        for r in rows:
            bound[r["bottleneck"]] = bound.get(r["bottleneck"], 0) + 1
        print(f"roofline_single_pod,0,\"{len(rows)} cells; bottlenecks "
              f"{bound}; worst fraction {worst['roofline_fraction']:.2%} "
              f"({worst['arch']}/{worst['shape']})\"")
    else:
        print("roofline_single_pod,0,\"not measured: no dry-run records\"")


if __name__ == '__main__':
    main()
