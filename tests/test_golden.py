"""Golden regression snapshot: the nominal-corner physics may not drift.

``tests/golden/table2.json`` freezes (a) the paper's Table-2 selections at
the nominal operating point, (b) the full characterization of a small
fixed config slice and (c) that slice at the four named operating corners,
with every metric stored as the float64 repr of the float32 the vmap
pipeline produced. These tests diff live results against
the snapshot under the golden contract (``update_golden.golden_diff``):
labels and array geometry exact, float metrics within ``FLOAT_RTOL`` — an
unintended edit to any physics module fails loudly here instead of
silently shifting DSE winners.

After an *intentional* physics change, regenerate with either

    python scripts/update_golden.py
    python -m pytest tests/test_golden.py --update-golden

and commit the new snapshot alongside the change that motivated it.
"""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

from update_golden import (GOLDEN_PATH, SLICE_KW,  # noqa: E402
                           characterization_rows, corner_slice_table,
                           golden_diff, write_snapshot)


@pytest.fixture(scope="module")
def golden(request):
    if request.config.getoption("--update-golden"):
        write_snapshot()
    assert GOLDEN_PATH.exists(), \
        "missing tests/golden/table2.json (run scripts/update_golden.py)"
    return json.loads(GOLDEN_PATH.read_text())


def test_table2_selections_match_golden(golden):
    from repro.api import explore
    from repro.core import gainsight
    report = explore(tasks=gainsight.TASKS)
    labels = report.labels()
    for tid, expected in golden["table2"].items():
        assert labels[int(tid)] == expected, f"task {tid} drifted"
    # and the snapshot itself agrees with the paper's ground truth
    for tid, expected in gainsight.TABLE2_EXPECTED.items():
        assert golden["table2"][str(tid)] == expected


def test_characterization_slice_is_bit_for_bit(golden):
    """The nominal slice under the golden contract: geometry exact, float
    metrics within FLOAT_RTOL (the name predates the contract)."""
    from repro.api import DesignTable, design_space
    slice_kw = {k: tuple(v) for k, v in golden["slice"].items()}
    assert slice_kw == SLICE_KW, \
        "golden slice definition changed; regenerate the snapshot"
    table = DesignTable.from_configs(design_space(**slice_kw))
    drift, _ = golden_diff(golden["characterization"],
                           characterization_rows(table))
    assert not drift, (
        "characterization drifted from the golden snapshot:\n  "
        + "\n  ".join(drift[:20])
        + "\nIf the physics change is intentional, regenerate via "
          "scripts/update_golden.py or pytest --update-golden.")


def test_corner_slice_matches_golden(golden):
    """Every ``<metric>@<corner>`` column of the slice at the four named
    corners, under the golden contract."""
    drift, _ = golden_diff(golden["corners"],
                           characterization_rows(corner_slice_table()))
    assert not drift, (
        "corner characterization drifted from the golden snapshot:\n  "
        + "\n  ".join(drift[:20]))


def test_update_golden_roundtrips(tmp_path, golden):
    """The update path rewrites a snapshot that agrees with a fresh build
    (so --update-golden immediately followed by the diff test passes)."""
    from update_golden import build_snapshot
    snap = build_snapshot()
    assert snap["table2"] == golden["table2"]
    for key in ("characterization", "corners"):
        drift, _ = golden_diff(golden[key], snap[key])
        assert not drift, drift[:20]
