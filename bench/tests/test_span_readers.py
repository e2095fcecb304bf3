"""The span and counter readers of ``bench/metrics/`` on a hand-made
window: nesting, a span or arg that is missing, and the thread, depth and
containment rule that finds a span's direct children."""
import importlib.util
from pathlib import Path

import pytest

from bench import trace_reduce as TR
from bench.tracing import Context

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ev(name, ts, dur, depth, tid=1, **args):
    return {"name": name, "ts": float(ts), "dur": float(dur), "tid": tid,
            "depth": depth, "args": args}


def ctx(spans, queries=2):
    device = TR.Reduced(window=(0, 1), busy_ns=0.0, programs={}, busy=[],
                        obs_origin=(0.0, 0), n_devices=1)
    return Context(queries=queries, window_s=1.0, compiles=0, spans=spans,
                   device=device, cases=[], which=[], reports={})


def two_queries():
    """Two queries (µs): the first encodes before characterizing, and
    encodes again inside hetero.expand; the second replays."""
    return [
        ev("bench.query", 0, 1000, 1, fetches=50),
        ev("api.encode", 10, 100, 2, n_configs=120),
        ev("api.characterize", 120, 200, 2, fetches=19),
        ev("hetero.compose", 400, 500, 2, fetches=31),
        ev("hetero.expand", 410, 300, 3),
        ev("api.encode", 420, 150, 4, n_configs=120),
        ev("bench.query", 2000, 800, 1),
        ev("api.encode", 2010, 50, 2),
        ev("hetero.compose", 2100, 600, 2),
        ev("sim.rerank", 2200, 400, 3),
        ev("sim.prepare", 2210, 30, 4),
        ev("sim.replay", 2250, 300, 4),
    ]


def test_encode_ms_sums_every_depth():
    assert reader("encode_ms")(ctx(two_queries())) == \
        pytest.approx((100 + 150 + 50) / 1e3 / 2)


def test_replay_prep_ms():
    assert reader("replay_prep_ms")(ctx(two_queries())) == \
        pytest.approx(30 / 1e3 / 2)


@pytest.mark.parametrize("name", ["encode_ms", "replay_prep_ms",
                                  "unattributed_ms"])
def test_span_reader_without_its_span_is_none(name):
    spans = [e for e in two_queries()
             if e["name"] not in ("api.encode", "sim.prepare", "bench.query")]
    assert reader(name)(ctx(spans)) is None
    assert reader(name)(ctx(two_queries(), queries=0)) is None


def test_unattributed_ms_subtracts_direct_children_only():
    # query 1: 1000 - (100 + 200 + 500); query 2: 800 - (50 + 600); the
    # grandchildren (expand, rerank and below) are inside their parents
    want = ((1000 - 800) + (800 - 650)) / 1e3 / 2
    assert reader("unattributed_ms")(ctx(two_queries())) == \
        pytest.approx(want)


def test_unattributed_ms_rule_skips_other_threads_and_overhangs():
    spans = [
        ev("bench.query", 0, 1000, 1),
        ev("api.characterize", 100, 200, 2),
        ev("x.other_thread", 100, 500, 2, tid=2),   # another thread
        ev("x.overhang", 900, 300, 2),               # ends past the query
        ev("x.grandchild", 150, 50, 3),              # not a direct child
        ev("x.before", -50, 20, 2),                  # starts before it
    ]
    assert reader("unattributed_ms")(ctx(spans, queries=1)) == \
        pytest.approx((1000 - 200) / 1e3)


def test_unattributed_ms_with_no_children_is_the_whole_query():
    spans = [ev("bench.query", 0, 1000, 0), ev("bench.query", 2000, 500, 0)]
    assert reader("unattributed_ms")(ctx(spans)) == pytest.approx(0.75)


def test_fetches_per_query_counts_a_query_without_the_arg_as_zero():
    assert reader("fetches_per_query")(ctx(two_queries())) == \
        pytest.approx(25.0)


def test_fetches_per_query_without_the_arg_is_none():
    spans = [dict(e, args={}) for e in two_queries()]
    assert reader("fetches_per_query")(ctx(spans)) is None
    no_queries = [e for e in two_queries() if e["name"] != "bench.query"]
    assert reader("fetches_per_query")(ctx(no_queries)) is None
