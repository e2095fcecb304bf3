"""The system under test, seen from the benchmark: how a query becomes one
public ``repro.api.compose`` call, and how its report reads back as an
``Answer`` the reference can compare.

Nothing else under ``bench/`` imports the program. The checkout's ``src/``
must hold it: ``load()`` refuses to run without it, so a copy of the
benchmark alone never measures an installed package in its place.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Callable, Dict

import numpy as np

from bench import reference as R

ROOT = Path(__file__).resolve().parents[1]


def load():
    """Put the checkout's ``src/`` first on the path and import the façade.
    Raises ``SystemExit`` when the checkout holds no program."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SystemExit(f"no program under {src}: the benchmark measures "
                         f"the checkout's src/repro")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro.api
    return repro.api


def make_call(q: R.Query) -> Callable[[], object]:
    """The query as one ``compose`` call, its arguments built once: the
    design space as ``MacroConfig``s, the task's buffer requirements, the
    compose policy and the replay policy. No disk cache is passed, so every
    call characterizes, searches and replays anew."""
    api = load()
    from repro.core.select import Bucket, LevelReq, TaskReq
    from repro.hetero import ComposePolicy

    space = [api.MacroConfig(**c) for c in q.configs]
    task = TaskReq(q.task["id"], q.task["name"], {
        name: LevelReq(name, int(cap_kib * 8 * 1024),
                       tuple(Bucket(*b) for b in buckets))
        for name, cap_kib, buckets in q.task["levels"]})
    p = q.policy
    cp = ComposePolicy(
        objective=p["objective"], candidate_mode=p["candidate_mode"],
        max_candidates_per_bucket=p["max_candidates_per_bucket"],
        max_compositions=p["max_compositions"], search=p["search"],
        search_threshold=p["search_threshold"], top_k=p["top_k"],
        vdd_sweep=tuple(tuple(v) for v in p["vdd_sweep"]),
        refresh_margin_sweep=tuple(p["refresh_margin_sweep"]))
    selection = api.SelectionPolicy(preference=tuple(p["preference"]))
    sim = None
    if q.refine == "simulate":
        s = q.sim
        sim = api.SimPolicy(
            phases=tuple(s["phases"]), duration_s=s["duration_s"],
            n_bins=s["n_bins"], refresh=s["refresh"],
            refresh_margin=s["refresh_margin"],
            rewrite_overhead=s["rewrite_overhead"], objective=s["objective"],
            adaptive_refresh=s["adaptive_refresh"],
            temp_drift_k=s["temp_drift_k"])

    def call():
        return api.compose(space, task, policy=selection, compose_policy=cp,
                           refine=q.refine, sim_policy=sim)
    return call


def to_answer(report, ctx: R.Context) -> R.Answer:
    """Read a ``CompositionReport`` as an ``Answer``: each pick's row in the
    reference's block order, found from the pick's operating point and
    refresh margin."""
    table = report.table
    n = len(table)
    blocks = {(None if op is None else (float(op[0]), float(op[1])),
               None if m is None else float(m)): b
              for b, (op, m) in enumerate(ctx.points)}
    ranked, tiles, metrics = [], [], []
    for comp in report.ranked:
        row, til = [], []
        for name in report.task.levels:
            lc = comp.levels[name]
            for pick, t in zip(lc.picks, lc.tiles):
                op = None if pick.op is None else (float(pick.op.vdd),
                                                   float(pick.op.temp_k))
                margin = None if pick.refresh_margin is None \
                    else float(pick.refresh_margin)
                row.append(-1 if pick.config_idx < 0
                           else blocks[(op, margin)] * n + pick.config_idx)
                til.append(int(t))
        ranked.append(row)
        tiles.append(til)
        metrics.append({k: float(v) for k, v in comp.metrics.items()
                        if k != "sim_stall_frac" and k != "sim_p_avg_w"})
    cols: Dict[str, np.ndarray] = {k: np.asarray(table.metrics[k],
                                                 np.float64)
                                   for k in R.CHAR_COLUMNS}
    return R.Answer(table=cols, ranked=np.asarray(ranked, np.int64),
                    tiles=np.asarray(tiles, np.int64), metrics=metrics,
                    labels=dict(report.labels()))
