"""The control, the reference in bfloat16 in the program's place, must come
out not correct under every cell's limits, while the reference against
itself comes out exact. One query of each cell, at the cell's sizes."""
import json

import pytest

from bench import compare as C
from bench import reference as R
from bench import traffic as T
from bench.run import BENCH, ROOT, load_spec

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def first_case(workload):
    _, _, config = load_spec(workload)
    return T.catalog(T.load_json(ROOT / config["file"]),
                     T.load_json(BENCH / "traffic" / f"{workload}.json"))[0]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    case = first_case(workload)
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    want, ctx = R.answer(R.NB64, case.query)
    assert C.judge(C.compare(want, want, ctx), limits)[0]
    got, _ = R.answer(R.bf16(), case.query)
    ok, rows = C.judge(C.compare(got, want, ctx), limits)
    assert not ok, rows
