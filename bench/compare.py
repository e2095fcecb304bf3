"""The comparison that decides ``correct``: a query's answer against the
reference's answer to the same query.

Four numbers, each the worst over the answers compared:

``table_err``   symmetric relative error of the characterized table, every
                column and every configuration.
``metric_err``  symmetric relative error of each ranked composition's
                reported metrics (system metrics, and the ``sim_*`` replay
                metrics after a re-rank) against the reference's pricing of
                the same picks at the same operating point and margin.
``rank_gap``    for each rank k, the gap between the reference's ranking
                keys of the k-th composition reported and of the
                reference's own k-th: the symmetric relative gap of the
                first key that differs, or 1 where it is an integer key
                (feasibility, preference rank).
``discrete_off``  tiles of a ranked composition, and level labels of the
                best one, that differ from the reference's: a count.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

from bench import reference as R

NUMBERS = ("table_err", "metric_err", "rank_gap", "discrete_off")


def rel_err(a, b) -> float:
    """Worst symmetric relative difference |a - b| / max(|a|, |b|); equal
    values (infinities and zeros included) differ by 0, NaN against
    anything by 1."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return 1.0
    with np.errstate(invalid="ignore", divide="ignore"):
        d = np.abs(a - b) / np.maximum(np.abs(a), np.abs(b))
    d = np.where(a == b, 0.0, d)
    d = np.where(np.isnan(a) | np.isnan(b), 1.0, d)
    return float(np.max(d, initial=0.0))


def key_gap(got: Tuple, want: Tuple) -> float:
    for x, y in zip(got, want):
        if x == y:
            continue
        if isinstance(x, (int, np.integer)):
            return 1.0
        return rel_err(x, y)
    return 0.0


def compare(got: R.Answer, want: R.Answer, ctx: R.Context
            ) -> Dict[str, float]:
    """The four numbers for one answer (see module docstring)."""
    table_err = max(rel_err(got.table[k], want.table[k])
                    for k in R.CHAR_COLUMNS)
    metrics, tiles, keys = R.evaluate(R.NB64, ctx, got.ranked)
    _, _, want_keys = R.evaluate(R.NB64, ctx, want.ranked)
    metric_err = max((rel_err(g[k], m[k]) for g, m in zip(got.metrics,
                                                         metrics)
                      for k in m), default=0.0)
    if len(got.metrics) != len(metrics) or any(
            set(g) != set(m) for g, m in zip(got.metrics, metrics)):
        metric_err = 1.0
    if len(keys) != len(want_keys):
        rank_gap = 1.0
    else:
        rank_gap = max((key_gap(g, w) for g, w in zip(keys, want_keys)),
                       default=0.0)
    discrete = int(np.sum(np.asarray(got.tiles) != np.asarray(tiles))) \
        if np.shape(got.tiles) == np.shape(tiles) else len(tiles)
    discrete += sum(got.labels.get(k) != v for k, v in want.labels.items())
    return {"table_err": table_err, "metric_err": metric_err,
            "rank_gap": rank_gap, "discrete_off": float(discrete)}


def worst(readings: Iterable[Dict[str, float]]) -> Dict[str, float]:
    out = {k: 0.0 for k in NUMBERS}
    for r in readings:
        for k in NUMBERS:
            out[k] = max(out[k], r[k])
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Dict[str, object]]]:
    """Each number against its limit: (all within, [{name, value, limit}])."""
    rows = [{"name": k, "value": numbers[k], "limit": limits[k]}
            for k in NUMBERS]
    return all(r["value"] <= r["limit"] for r in rows), rows
