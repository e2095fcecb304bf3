"""One run of one benchmark cell on the accelerator.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/<workload>.json``); the general
generator (``bench/traffic.py``) turns them into a fixed catalog of DSE
queries, each one public ``compose`` call. A run:

1. fails unless JAX's first device is a TPU and there are as many as the
   cell asks for;
2. turns on the persistent compilation cache at the program's fixed path
   and warms every query of the catalog once (set-up ends here);
3. runs the window: a closed loop of one client that issues the next query,
   in the seed's order, when the last returns, for ``--seconds``;
4. compares every distinct answer of the window with the plain reference
   (``bench/reference.py``) by the numbers of ``bench/compare.py``, each
   against its limit in ``bench/limits/<workload>.json``;
5. prints the numbers and limits as its last lines on standard error, and
   one JSON object as the last line of standard output.

With ``--trace 0`` the metrics are the cell's end-to-end ones. With
``--trace 1`` the window runs under the JAX profiler with the program's
spans on, and the metrics are the per-layer ones, each computed by its
reader ``bench/metrics/<name>.py`` from the spans, the compile counter and
the reduced device trace.
"""
from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = ROOT / "bench"
OUT = BENCH / ".out"
# a program compiled, or loaded from the persistent cache: either way one
# the warm-up did not make ready
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def fail(msg: str, code: int = 2) -> None:
    print(f"bench.run: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def require_chips(devices, n: int) -> None:
    """Refuse to measure anything but the chip: JAX's first device must be
    a TPU, and there must be ``n`` of them."""
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < n:
        fail(f"the cell asks for {n} chips, JAX sees {len(devices)}")


def load_spec(workload: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        fail(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return spec, cell, config


def cell_metrics(spec, workload: str, kind: str) -> List[Dict]:
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, from a
    ``jax.monitoring`` listener."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name in COMPILE_EVENTS:
            self.n += 1


def run_window(cases, calls, order, seconds: float):
    """The closed loop: returns (latencies [s], catalog index per query,
    reports by catalog index, failed queries, window seconds). The window
    ends with the first query to return after ``seconds``."""
    from bench.tracing import query_span
    lat, which, reports, failed = [], [], {}, 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t_end = t_start
    while t_end < deadline:
        i = next(order)
        t0 = time.perf_counter()
        try:
            with query_span(cases[i].name):
                report = calls[i]()
        except Exception as e:             # a failed query is counted
            report = None
            failed += 1
            print(f"query {cases[i].name} failed: {e!r}", file=sys.stderr)
        t_end = time.perf_counter()
        lat.append(t_end - t0)
        which.append(i)
        if report is not None:
            reports.setdefault(i, []).append(report)
    return lat, which, reports, failed, t_end - t_start


def p95(values: List[float]) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def check(cases, reports, limits) -> Dict:
    """Compare every distinct answer of the window with the reference's
    answer to its query; the worst of each number against its limit."""
    from bench import compare as C
    from bench import program as P
    from bench import reference as R
    readings = []
    n_distinct = 0
    for i, reps in sorted(reports.items()):
        want, ctx = R.answer(R.NB64, cases[i].query)
        seen = set()
        for rep in reps:
            got = P.to_answer(rep, ctx)
            key = _digest(got)
            if key in seen:
                continue
            seen.add(key)
            n_distinct += 1
            readings.append(C.compare(got, want, ctx))
    worst = C.worst(readings)
    ok, rows = C.judge(worst, limits)
    return {"correct": ok, "rows": rows, "distinct": n_distinct}


def _digest(ans) -> str:
    h = hashlib.sha256()
    for k in sorted(ans.table):
        h.update(ans.table[k].tobytes())
    h.update(ans.ranked.tobytes())
    h.update(ans.tiles.tobytes())
    h.update(json.dumps(ans.metrics, sort_keys=True).encode())
    h.update(json.dumps(ans.labels, sort_keys=True).encode())
    return h.hexdigest()


def read_per_layer(metrics, ctx) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    t_setup = time.perf_counter()
    # the TPU runtime's logs stay in the checkout, not at a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    spec, cell, config = load_spec(args.workload)
    from bench import program as P
    from bench import traffic as T
    P.load()
    import jax

    devices = jax.devices()
    require_chips(devices, cell["chips"])
    from repro import compile_cache
    compile_cache.enable()
    # every program the cell runs goes to the cache, however fast it
    # compiled, so that a later run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()

    cases = T.catalog(T.load_json(ROOT / config["file"]),
                      T.load_json(BENCH / "traffic" / f"{args.workload}.json"))
    limits = json.loads((BENCH / "limits" / f"{args.workload}.json")
                        .read_text())
    calls = [P.make_call(c.query) for c in cases]
    for call in calls:                     # warm every shape the window uses
        call()
    setup_s = time.perf_counter() - t_setup

    order = T.schedule(len(cases), args.seed)
    trace = None
    n_compiles0 = compiles.n
    if args.trace:
        from bench import tracing
        trace = tracing.Tracer(OUT / "trace" / args.workload)
        trace.start()
    try:
        lat, which, reports, failed, window_s = run_window(
            cases, calls, order, args.seconds)
    finally:
        if trace is not None:
            trace.stop()
    n_compiles = compiles.n - n_compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    extra = {}
    if args.trace:
        ctx = trace.context(queries=len(lat), window_s=window_s,
                            compiles=n_compiles, cases=cases, which=which,
                            reports=reports,
                            device_kind=devices[0].device_kind)
        trace.discard()
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.trace_window_s
        metrics = read_per_layer(cell_metrics(spec, args.workload,
                                              "per_layer"), ctx)
        extra["breakdown"] = ctx.breakdown()
    else:
        measured = {"queries_per_s": lambda: len(lat) / window_s,
                    "query_p95_ms": lambda: p95(lat) * 1e3,
                    "setup_s": lambda: setup_s}
        metrics = {m["name"]: {"value": measured[m["name"]](),
                               "unit": m["unit"]}
                   for m in cell_metrics(spec, args.workload, "end_to_end")}

    verdict = check(cases, reports, limits)
    reports.clear()
    for row in verdict["rows"]:
        print(f"check {row['name']}: {row['value']!r} (limit "
              f"{row['limit']!r})", file=sys.stderr)
    print(f"check correct: {verdict['correct']} over "
          f"{verdict['distinct']} distinct answers of {len(lat)} queries, "
          f"{failed} failed", file=sys.stderr, flush=True)
    result = {"correct": verdict["correct"] and not failed,
              "attempted": len(lat), "failed": failed, "metrics": metrics,
              "device": device, **extra,
              "checks": {r["name"]: {"value": r["value"], "limit": r["limit"]}
                         for r in verdict["rows"]}}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
