"""Record a slice of a traced window on the chip for ``test_trace_reduce``.

    python3 -m bench.tests.record_trace --workload table2_simulate \
        --out bench/tests/data/tpu_trace_rows.json

Warms the cell's catalog as a run does, traces a short window, and keeps
the extracted rows (``trace_reduce.extract``) of the window's first
``--slice-ms`` milliseconds, with the ``bench.window`` mark cut to that
length, so that the file stays small.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program as P  # noqa: E402
from bench import run  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench import tracing  # noqa: E402
from bench.tests.test_trace_reduce import SCORE_PROGRAM  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--slice-ms", type=float, default=40.0)
    args = ap.parse_args(argv)

    _, cell, config = run.load_spec(args.workload)
    P.load()
    import jax
    run.require_chips(jax.devices(), cell["chips"])
    from repro import compile_cache
    compile_cache.enable()
    cases = T.catalog(T.load_json(ROOT / config["file"]),
                      T.load_json(run.BENCH / "traffic"
                                  / f"{args.workload}.json"))
    calls = [P.make_call(c.query) for c in cases]
    for call in calls:
        call()
    tracer = tracing.Tracer(run.OUT / "record" / args.workload)
    tracer.start()
    try:
        run.run_window(cases, calls, T.schedule(len(cases), 1), args.seconds)
    finally:
        tracer.stop()
    rows = TR.extract(sorted(tracer.out_dir.rglob("*.xplane.pb"))[-1])
    tracer.discard()
    mark = next(r for r in rows if r[2] == tracing.WINDOW)
    w0, w1 = mark[3], mark[3] + int(args.slice_ms * 1e6)
    # the slice reaches past the window's first scoring program
    first_score = min((r[3] + r[4] for r in rows
                       if r[1] == TR.PROGRAMS_LINE and r[3] >= w0
                       and re.search(SCORE_PROGRAM, r[2])), default=w1)
    w1 = max(w1, first_score + 1_000_000)
    kept = [list(mark[:4]) + [w1 - w0]]
    kept += [list(r) for r in rows
             if r is not mark and r[3] < w1 and r[3] + r[4] > w0]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "workload": args.workload, "slice_ms": args.slice_ms,
        "rows": kept}) + "\n")
    print(f"{len(kept)} rows to {args.out}")


if __name__ == "__main__":
    main()
