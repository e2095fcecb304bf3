"""The reduction from trace rows to device numbers, on a hand-made trace
and on a small trace recorded on a TPU v5e chip."""
import importlib.util
import json
import re
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _score_program():
    path = DATA.parent.parent / "metrics" / "score_device_ms.py"
    spec = importlib.util.spec_from_file_location("score_device_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SCORE_PROGRAM


SCORE_PROGRAM = _score_program()


def hand_made():
    return [
        ("/host:CPU", "main", "bench.window", 1_000, 10_000),
        # overlapping ops, one that starts before the window
        (DEV, TR.OPS_LINE, "fusion.1", 500, 1_000),
        (DEV, TR.OPS_LINE, "fusion.2", 2_000, 2_000),
        (DEV, TR.OPS_LINE, "copy.3", 3_000, 2_000),
        (DEV, TR.OPS_LINE, "fusion.4", 8_000, 500),
        (DEV, TR.PROGRAMS_LINE, "jit_score_kernel(17)", 2_000, 3_000),
        (DEV, TR.PROGRAMS_LINE, "jit_characterize(3)", 8_000, 500),
        (DEV, TR.PROGRAMS_LINE, "jit_score_kernel(17)", 9_000, 5_000),
    ]


def test_hand_made_trace():
    red = TR.reduce(hand_made(), "bench.window", window_obs_us=100.0)
    assert red.window == (1_000, 11_000)
    # busy: [1000, 1500) + [2000, 5000) + [8000, 8500)
    assert red.busy_ns == 500 + 3_000 + 500
    assert red.programs == {"jit_score_kernel": 3_000 + 2_000,
                            "jit_characterize": 500}
    assert red.program_ns(SCORE_PROGRAM) == 5_000
    assert red.gaps() == [(1_500, 2_000), (5_000, 8_000), (8_500, 11_000)]
    # a host span (µs on its own clock) open over the 5000..8000 gap
    spans = [{"name": "hetero.compose", "ts": 100.0 + 3.0, "dur": 4.0,
              "depth": 1},
             {"name": "bench.query", "ts": 100.0, "dur": 9.0, "depth": 0}]
    assert red.longest_gaps(2, spans) == [("hetero.compose", 3_000),
                                          ("bench.query", 2_500)]


def test_no_device_op_is_an_error():
    rows = [r for r in hand_made() if r[1] != TR.OPS_LINE]
    with pytest.raises(ValueError):
        TR.reduce(rows, "bench.window", 0.0)


def brute_busy(rows, window):
    """Busy nanoseconds by marking every nanosecond an op covers."""
    lo, hi = window
    covered = bytearray(hi - lo)
    for plane, line, _, s, d in rows:
        if plane == DEV and line == TR.OPS_LINE:
            a, b = max(s, lo) - lo, min(s + d, hi) - lo
            if b > a:
                covered[a:b] = b"\x01" * (b - a)
    return sum(covered)


def brute_program_ns(rows, window, pattern):
    """Device ns of the programs matching ``pattern``, clipped to the
    window, summed row by row."""
    lo, hi = window
    return sum(max(0, min(s + d, hi) - max(s, lo))
               for plane, line, name, s, d in rows
               if plane == DEV and line == TR.PROGRAMS_LINE
               and re.search(pattern, name))


@pytest.mark.skipif(not (DATA / "tpu_trace_rows.json").exists(),
                    reason="no recorded trace")
def test_recorded_trace():
    """The plane, line and program names the reducer and the score reader
    look for are the ones a TPU trace has, and the reduction agrees with a
    brute-force count on it."""
    rec = json.loads((DATA / "tpu_trace_rows.json").read_text())
    rows = [tuple(r) for r in rec["rows"]]
    lines = {(r[0], r[1]) for r in rows}
    assert (DEV, TR.OPS_LINE) in lines and (DEV, TR.PROGRAMS_LINE) in lines
    red = TR.reduce(rows, "bench.window", 0.0)
    assert red.busy_ns == brute_busy(rows, red.window)
    assert 0 < red.busy_ns <= red.window_ns
    assert sum(e - s for s, e in red.gaps()) == red.window_ns - red.busy_ns
    score = red.program_ns(SCORE_PROGRAM)
    assert score > 0
    assert score == brute_program_ns(rows, red.window, SCORE_PROGRAM)
