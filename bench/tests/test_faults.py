"""A run with the timed path broken underneath must print ``correct``
false. The chip check is skipped; everything else is a run of the cell:
warm-up, a window long enough for a round of every catalog query, the
comparison with the reference."""
import importlib
import json

import numpy as np
import pytest

from bench import run


def run_cell(monkeypatch, capsys, workload):
    monkeypatch.setattr(run, "require_chips", lambda devices, n: None)
    run.main(["--workload", workload, "--seed", "2718281828",
              "--seconds", "1.5", "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def scale_characterized(monkeypatch, column, factor):
    """A characterized column altered where the characterization makes it."""
    chz = importlib.import_module("repro.core.characterize")
    real = chz.characterize_batch

    def altered(vecs):
        out = dict(real(vecs))
        out[column] = out[column] * factor
        return out
    monkeypatch.setattr(chz, "characterize_batch", altered)


def scale_swept(monkeypatch, column, factor):
    """A column altered where the swept operating points are characterized
    (the expanded blocks of a vdd sweep; the base block is left alone)."""
    chz = importlib.import_module("repro.core.characterize")
    real = chz.characterize_corners

    def altered(vecs, ops, *a, **kw):
        out = dict(real(vecs, ops, *a, **kw))
        out[column] = out[column] * factor
        return out
    monkeypatch.setattr(chz, "characterize_corners", altered)


def scale_reported(monkeypatch, metric, factor):
    """A reported system metric altered where the report is built."""
    hc = importlib.import_module("repro.hetero.compose")
    real = hc._materialize

    def altered(table, task, idx_row, tiles_row, metrics_row, *a, **kw):
        metrics_row = {**metrics_row, metric: metrics_row[metric] * factor}
        return real(table, task, idx_row, tiles_row, metrics_row, *a, **kw)
    monkeypatch.setattr(hc, "_materialize", altered)


def drop_half(monkeypatch):
    """Half of each scored batch left out: the first half of the
    composition grid unscored, by the exhaustive search and by
    branch-and-bound alike (``search="auto"`` takes either)."""
    for name in ("repro.hetero.compose", "repro.hetero.search"):
        mod = importlib.import_module(name)

        def half(metrics, idx, *a, _real=mod.score_grid, **kw):
            out = _real(metrics, idx, *a, **kw)
            n = len(idx) // 2
            return {k: np.concatenate([np.full(n, np.inf, v.dtype), v[n:]])
                    for k, v in out.items()}
        monkeypatch.setattr(mod, "score_grid", half)


def test_sound_run_is_correct(monkeypatch, capsys):
    assert run_cell(monkeypatch, capsys, "table2_simulate")["correct"]


@pytest.mark.parametrize("workload,fault", [
    ("table2_simulate", lambda mp: scale_characterized(mp, "retention_s",
                                                        1.05)),
    ("table2_simulate", lambda mp: scale_reported(mp, "p_w", 1.01)),
    ("table2_vdd_sweep", drop_half),
    ("table2_vdd_sweep", lambda mp: scale_swept(mp, "p_leak_w", 1.05)),
    ("table2_vdd_sweep", lambda mp: scale_reported(mp, "area_um2", 0.99)),
])
def test_fault_is_not_correct(monkeypatch, capsys, workload, fault):
    fault(monkeypatch)
    assert not run_cell(monkeypatch, capsys, workload)["correct"]
