"""The control of the comparison: the reference, computed one precision
below the program's (bfloat16 for float32), answers the cell's queries in
the program's place, and the comparison must find it not correct.

    python -m bench.control --workload <name> --seeds 1,2,3

For each seed it takes one round of the cell's queries in the seed's order
(every catalog entry once), answers each with the control, compares the
answers with the float64 reference exactly as a run compares the program's,
and prints one JSON line: the worst numbers, the limits, and the verdict.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare as C  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench.run import load_spec  # noqa: E402


def readings(workload: str, seed: int, cache=None):
    """Worst numbers of the control over one seeded round of the cell."""
    spec, cell, config = load_spec(workload)
    cases = T.catalog(T.load_json(ROOT / config["file"]),
                      T.load_json(ROOT / "bench" / "traffic"
                                  / f"{workload}.json"))
    order = T.schedule(len(cases), seed)
    cache = {} if cache is None else cache
    out = []
    for _ in range(len(cases)):
        i = next(order)
        if i not in cache:
            want, ctx = R.answer(R.NB64, cases[i].query)
            got, _ = R.answer(R.bf16(), cases[i].query)
            cache[i] = C.compare(got, want, ctx)
        out.append(cache[i])
    return C.worst(out)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    limits = json.loads((ROOT / "bench" / "limits"
                         / f"{args.workload}.json").read_text())
    cache: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        worst = readings(args.workload, seed, cache)
        ok, rows = C.judge(worst, limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": ok, "numbers": rows}), flush=True)


if __name__ == "__main__":
    main()
