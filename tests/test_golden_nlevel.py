"""Golden regression snapshot for the N-level composition path.

``tests/golden/table2_nlevel.json`` freezes the 3-level reference
composition (``repro.core.gainsight.nlevel_task(3)``) under two settings —
the default preference policy through the exhaustive grid, and the power
objective through forced branch-and-bound — with every system metric stored
as the float64 repr of the float32 the scoring kernel produced. These tests
diff live results against the snapshot under the golden contract
(``update_golden.golden_diff``: labels, picks, tiles and search exact,
float metrics within ``FLOAT_RTOL``), and separately
prove that the 2-level Table-2 results are unchanged through the N-level
code path (``levels=("L1", "L2")``).

Regenerate after an *intentional* physics or ranking change with either

    python scripts/update_golden.py
    python -m pytest tests/test_golden_nlevel.py --update-golden
"""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

from update_golden import (NLEVEL_PATH, NLEVEL_POLICIES,  # noqa: E402
                           build_nlevel_snapshot, golden_diff,
                           write_nlevel_snapshot)


@pytest.fixture(scope="module")
def golden(request):
    if request.config.getoption("--update-golden"):
        write_nlevel_snapshot()
    assert NLEVEL_PATH.exists(), \
        "missing tests/golden/table2_nlevel.json (run " \
        "scripts/update_golden.py)"
    return json.loads(NLEVEL_PATH.read_text())


def test_nlevel_composition_is_bit_for_bit(golden):
    """Under the golden contract: labels, picks, tiles and search exact,
    float metrics within FLOAT_RTOL (the name predates the contract)."""
    assert set(golden["compositions"]) == set(NLEVEL_POLICIES), \
        "golden policy set changed; regenerate the snapshot"
    drift, _ = golden_diff(golden["compositions"],
                           build_nlevel_snapshot()["compositions"])
    assert not drift, (
        "N-level composition drifted from the golden snapshot:\n  "
        + "\n  ".join(drift)
        + "\nIf intentional, regenerate via scripts/update_golden.py "
          "or pytest --update-golden.")


def test_table2_unchanged_through_nlevel_path(golden):
    """Regression proof: routing the 2-level tasks through the generalized
    N-level machinery (``levels=("L1", "L2")``) changes nothing — labels
    reproduce Table 2 and every system metric of the winner is bit-identical
    to the default invocation."""
    from repro.core import gainsight
    from repro.hetero import compose
    from repro.hetero.system import SYSTEM_METRICS

    for t in gainsight.TASKS:
        base = compose(None, t)
        via = compose(None, t, levels=("L1", "L2"))
        assert via.labels() == base.labels() == \
            gainsight.TABLE2_EXPECTED[t.task_id], t.task_id
        for a, b in zip(base.ranked, via.ranked):
            assert a.labels() == b.labels()
            for m in SYSTEM_METRICS:
                av, bv = a.metrics[m], b.metrics[m]
                assert av == bv or (av != av and bv != bv), (t.task_id, m)
