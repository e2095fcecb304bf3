"""repro.obs: tracer / metrics / export / report contracts and the hot-path
instrumentation.

The two non-negotiable guarantees proven here:

- **telemetry off is free**: compose results are bit-identical with tracing
  on vs off (under the JAX profiler too, with every span annotated), and
  re-driving a warm jit site under an enabled scope adds zero trace-cache
  entries and records no ``compiles``.
- **the catalog is the surface**: every span/metric name the pipeline emits
  is covered by ``repro.obs.catalog`` (and DC04 forces the docs to match).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.api import (Compiler, DesignTable, characterize_call_count,
                       design_space)
from repro.core import gainsight
from repro.hetero import ComposePolicy, compose, composition_eval_count
from repro.kernels import backend as kbackend
from repro.obs import catalog, export
from repro.obs import report as obs_report
from repro.sim.engine import sim_eval_count

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts with an empty event list and tracing off."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


@pytest.fixture(scope="module")
def table():
    return DesignTable.from_configs(design_space())


# ------------------------------------------------------------------ tracer
def test_span_nesting_depth_and_timing():
    with obs.enabled_scope(True):
        with obs.span("t.outer"):
            with obs.span("t.mid"):
                with obs.span("t.inner"):
                    pass
            with obs.span("t.mid2"):
                pass
    ev = {e["name"]: e for e in obs.events()}
    assert set(ev) == {"t.outer", "t.mid", "t.inner", "t.mid2"}
    assert ev["t.outer"]["depth"] == 0
    assert ev["t.mid"]["depth"] == ev["t.mid2"]["depth"] == 1
    assert ev["t.inner"]["depth"] == 2
    # children are contained in the parent's [ts, ts+dur] window
    o = ev["t.outer"]
    for child in ("t.mid", "t.inner", "t.mid2"):
        c = ev[child]
        assert o["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in obs.events())


def test_span_exception_closes_and_propagates():
    with obs.enabled_scope(True):
        with pytest.raises(ValueError, match="boom"):
            with obs.span("t.fail"):
                raise ValueError("boom")
        with obs.span("t.after"):
            pass
    ev = {e["name"]: e for e in obs.events()}
    assert ev["t.fail"]["args"]["error"] == "ValueError"
    # the failed span restored nesting depth for its successors
    assert ev["t.after"]["depth"] == 0
    assert "error" not in ev["t.after"]["args"]


def test_disabled_span_is_shared_noop_and_emits_nothing():
    assert not obs.enabled()
    s1, s2 = obs.span("t.a"), obs.span("t.b", k=1)
    assert s1 is s2                       # one shared null singleton
    with s1:
        s1.set(ignored=True)
    assert obs.events() == []


def test_span_set_lands_in_args():
    with obs.enabled_scope(True):
        with obs.span("t.s", static=1) as sp:
            sp.set(dynamic=2)
    (e,) = obs.events()
    assert e["args"]["static"] == 1 and e["args"]["dynamic"] == 2


# ----------------------------------------------------------------- metrics
def test_metrics_registry_shapes():
    c = obs.counter("t.count")
    assert obs.counter("t.count") is c    # get-or-create returns same object
    c.inc()
    c.inc(4)
    obs.gauge("t.level").set(2.5)
    h = obs.histogram("t.lat_s")
    for v in (0.1, 0.3, 0.2):
        h.observe(v)
    snap = obs.snapshot()
    assert snap["counters"]["t.count"] == 5
    assert obs.value("t.count") == 5
    assert snap["gauges"]["t.level"] == 2.5
    hs = snap["histograms"]["t.lat_s"]
    assert hs["count"] == 3 and hs["min"] == 0.1 and hs["max"] == 0.3
    assert hs["mean"] == pytest.approx(0.2)
    obs.REGISTRY.reset()
    snap = obs.snapshot()
    assert snap["counters"]["t.count"] == 0          # names survive a reset
    assert snap["histograms"]["t.lat_s"]["count"] == 0


# ------------------------------------------------------------------ export
@pytest.mark.parametrize("suffix", [".json", ".jsonl"])
def test_export_roundtrip(tmp_path, suffix):
    with obs.enabled_scope(True):
        with obs.span("t.a", k="v"):
            with obs.span("t.b"):
                pass
    n0 = obs.value("t.rt_count")
    obs.counter("t.rt_count").inc(3)
    path = tmp_path / f"trace{suffix}"
    export.write(path, obs.events(), obs.snapshot())
    events, metrics = export.read(path)
    assert len(events) == len(obs.events())
    for got, want in zip(events, obs.events()):
        assert set(got) == set(want)
        for k in ("name", "cat", "ph", "tid", "depth", "id", "parent",
                  "args"):
            assert got[k] == want[k]
        for k in ("ts", "dur"):                # writer rounds to 1 ns
            assert got[k] == pytest.approx(want[k], abs=1e-3)
    assert metrics["counters"]["t.rt_count"] == n0 + 3


def test_chrome_trace_is_perfetto_shaped(tmp_path):
    with obs.enabled_scope(True):
        with obs.span("t.x"):
            pass
    obs.counter("t.ctr").inc()
    path = tmp_path / "trace.json"
    export.write_chrome(path, obs.events(), obs.snapshot())
    doc = json.loads(path.read_text())
    assert doc["otherData"]["schema"] == export.SCHEMA_VERSION
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases == {"X", "C"}
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(x)
    c = next(e for e in doc["traceEvents"]
             if e["ph"] == "C" and e["name"] == "t.ctr")
    assert c["args"]["value"] == 1


def test_report_render(tmp_path):
    with obs.enabled_scope(True):
        with obs.span("t.render_me"):
            pass
    obs.counter("t.render_count").inc(7)
    text = obs_report.render(obs.events(), obs.snapshot())
    assert "t.render_me" in text and "t.render_count" in text
    path = tmp_path / "trace.json"
    obs.write(path)
    assert "t.render_me" in obs_report.render_file(path)


def test_report_cli_module(tmp_path):
    with obs.enabled_scope(True):
        with obs.span("t.cli"):
            pass
    path = tmp_path / "trace.json"
    obs.write(path)
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs", "report", str(path)],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(SRC)})
    assert out.returncode == 0, out.stderr
    assert "t.cli" in out.stdout


def test_env_var_enables_and_atexit_flushes(tmp_path):
    path = tmp_path / "envtrace.json"
    code = ("import sys\n"
            "import repro.obs as obs\n"
            "assert obs.enabled()\n"
            "with obs.span('t.env'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n")      # obs never imports jax
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(SRC),
             "REPRO_TRACE": str(path)})
    assert out.returncode == 0, out.stderr
    events, _ = export.read(path)
    assert [e["name"] for e in events] == ["t.env"]


# ----------------------------------------------- counter-backed public API
def test_counter_migration_backs_public_counts(table):
    t = gainsight.TASKS[0]
    c0, s0 = composition_eval_count(), sim_eval_count()
    compose(table, t, refine="simulate")
    assert composition_eval_count() > c0       # scoring sweep counted
    assert sim_eval_count() == s0 + 1          # one replay sweep
    assert obs.value("hetero.compose_evals") == composition_eval_count()
    assert obs.value("sim.replay_calls") == sim_eval_count()
    k0 = characterize_call_count()
    DesignTable.from_configs(design_space()[:2])
    assert characterize_call_count() == k0 + 1
    assert obs.value("api.characterize_calls") == characterize_call_count()


# --------------------------------------------------- off-is-free contracts
def test_bit_identical_with_telemetry_on(table):
    t = gainsight.TASKS[1]
    ref = compose(table, t)
    with obs.enabled_scope(True):
        traced = compose(table, t)
    assert obs.events()                        # tracing actually happened
    assert traced.labels() == ref.labels()
    for a, b in zip(ref.ranked, traced.ranked):
        assert set(a.metrics) == set(b.metrics)
        for k in a.metrics:
            assert a.metrics[k] == b.metrics[k], k   # bit-exact, no tol


def test_no_retrace_under_enabled_scope(table):
    from repro.hetero import system

    t = gainsight.TASKS[2]
    compose(table, t)                          # warm the score jit
    n0 = system._score_jit._cache_size()
    with obs.enabled_scope(True):
        compose(table, t)
    assert system._score_jit._cache_size() == n0
    score_spans = [e for e in obs.events() if e["name"] == "hetero.score"]
    assert score_spans
    assert all("compiles" not in e["args"] for e in score_spans)


def test_bit_identical_and_no_retrace_under_profiler(table, tmp_path):
    """With the JAX profiler recording, every span also opens a
    TraceAnnotation: results stay bit-identical and no jit retraces."""
    import jax

    from repro.hetero import system

    t = gainsight.TASKS[1]
    ref = compose(table, t)
    n0 = system._score_jit._cache_size()
    with jax.profiler.trace(str(tmp_path)):
        with obs.enabled_scope(True):
            traced = compose(table, t)
    assert system._score_jit._cache_size() == n0
    assert obs.events()
    assert all("compiles" not in e["args"] for e in obs.events())
    assert traced.labels() == ref.labels()
    for a, b in zip(ref.ranked, traced.ranked):
        for k in a.metrics:
            assert a.metrics[k] == b.metrics[k], k


# ------------------------------------------- span identity and counter deltas
def _tree_checks(events):
    """``id`` unique; ``parent`` the enclosing span on the same thread,
    which contains the child and sits one level up."""
    by_id = {e["id"]: e for e in events}
    assert len(by_id) == len(events)
    for e in events:
        if e["parent"] is None:
            assert e["depth"] == 0
            continue
        p = by_id[e["parent"]]
        assert p["tid"] == e["tid"] and p["depth"] == e["depth"] - 1
        assert p["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
    return by_id


@pytest.mark.parametrize("suffix", [".json", ".jsonl"])
def test_span_ids_tree_of_nested_compose(table, tmp_path, suffix):
    configs = design_space()[:6]
    cp = ComposePolicy(vdd_sweep=((1.0, 320.0),))
    with obs.enabled_scope(True):
        with obs.span("t.query"):
            small = DesignTable.from_configs(configs)
            compose(small, gainsight.TASKS[0], compose_policy=cp,
                    refine="simulate")
    events = obs.events()
    by_id = _tree_checks(events)
    (root,) = [e for e in events if e["name"] == "t.query"]

    def ancestors(e):
        while e["parent"] is not None:
            e = by_id[e["parent"]]
            yield e["name"]
    # every span of the query descends from it
    assert all(e is root or "t.query" in ancestors(e) for e in events)
    names = {e["name"]: e for e in events}
    # the table's encoding is a sibling opened before api.characterize
    enc = [e for e in events if e["name"] == "api.encode"]
    assert by_id[enc[0]["parent"]] is root
    assert enc[0]["ts"] + enc[0]["dur"] <= names["api.characterize"]["ts"]
    # each swept point's encoding nests inside hetero.expand
    assert [list(ancestors(e))[0] for e in enc[1:]] == ["hetero.expand"]
    # replay staging sits in sim.rerank, outside sim.replay
    assert list(ancestors(names["sim.prepare"]))[0] == "sim.rerank"
    assert list(ancestors(names["sim.replay_phase"]))[0] == "sim.replay"
    path = tmp_path / f"trace{suffix}"
    export.write(path, events, obs.snapshot())
    back, _ = export.read(path)
    assert [(e["id"], e["parent"]) for e in back] == \
        [(e["id"], e["parent"]) for e in events]
    _tree_checks(back)


def test_per_corner_jit_compiles_show_in_span():
    """A fresh per-corner jit compiles inside the enclosing span, and the
    span's ``compiles`` says so; the warm call records none."""
    import jax.numpy as jnp

    from repro.core import characterize as chz
    from repro.core.corners import OperatingPoint

    vecs = jnp.stack([c.to_vector() for c in design_space()[:2]])
    op = OperatingPoint(vdd=1.037, temp_k=311.5, corner="t_fresh")
    with obs.enabled_scope(True):
        with obs.span("t.cold"):
            chz.characterize_corners(vecs, (op,))
        with obs.span("t.warm"):
            chz.characterize_corners(vecs, (op,))
    ev = {e["name"]: e for e in obs.events()}
    assert ev["t.cold"]["args"].get("compiles", 0) >= 1
    assert "compiles" not in ev["t.warm"]["args"]


@pytest.mark.parametrize("corners", [None, ("nominal", "hot")])
def test_from_configs_fetches_once_per_characterize_call(corners):
    """Every metric column of a characterize call, at every corner, comes
    to the host in one transfer."""
    with obs.enabled_scope(True):
        with obs.span("t.build"):
            small = DesignTable.from_configs(design_space()[:3],
                                             corners=corners)
    ev = {e["name"]: e for e in obs.events()}
    assert len(small.metric_names) > 1
    assert ev["t.build"]["args"]["fetches"] == 1
    assert ev["api.characterize"]["args"]["fetches"] == 1
    assert "fetches" not in ev["api.encode"]["args"]


def test_expand_over_three_points_fetches_once():
    """Three swept operating points characterize in three dispatches and
    come to the host in one transfer, inside hetero.expand."""
    small = DesignTable.from_configs(design_space()[:6])
    cp = ComposePolicy(vdd_sweep=((1.2, 233.0), (0.9, 300.0),
                                  (1.1, 358.0)),
                       refresh_margin_sweep=(0.8,))
    with obs.enabled_scope(True):
        compose(small, gainsight.TASKS[0], compose_policy=cp)
    (expand,) = [e for e in obs.events() if e["name"] == "hetero.expand"]
    assert expand["args"]["n_points"] == 8
    assert expand["args"]["fetches"] == 1
    assert expand["args"]["puts"] == 1


def test_encode_of_from_configs_is_one_put():
    """The whole design space is encoded on the host and sent to the
    device as one array: one span, one put, no program, no fetch."""
    with obs.enabled_scope(True):
        DesignTable.from_configs(design_space())
    (enc,) = [e for e in obs.events() if e["name"] == "api.encode"]
    assert enc["args"] == {"n_configs": 120, "puts": 1}


def test_expand_encodes_once_for_every_swept_point():
    """Two swept points share one encoding of the table, inside
    hetero.expand."""
    small = DesignTable.from_configs(design_space()[:6])
    cp = ComposePolicy(vdd_sweep=((1.0, 320.0), (1.2, 233.0)))
    with obs.enabled_scope(True):
        compose(small, gainsight.TASKS[0], compose_policy=cp)
    events = obs.events()
    by_id = {e["id"]: e for e in events}
    (expand,) = [e for e in events if e["name"] == "hetero.expand"]
    assert expand["args"]["n_points"] == 3
    (enc,) = [e for e in events if e["name"] == "api.encode"]
    assert by_id[enc["parent"]] is expand
    assert enc["args"]["n_configs"] == 6 and enc["args"]["puts"] == 1
    assert expand["args"]["puts"] == 1


def test_spans_land_in_the_profiler_trace(table, tmp_path):
    """Under jax.profiler every obs span is a host event of the same name;
    aligned at one mark, the two clocks agree on every start."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with obs.enabled_scope(True):
            with obs.span("t.mark"):
                pass
            compose(table, gainsight.TASKS[0], refine="simulate")
    events = obs.events()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    host: dict = {}
    names = {e["name"] for e in events}
    for plane in jax.profiler.ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for x in line.events:
                if x.name in names:
                    host.setdefault(x.name, []).append(x.start_ns)
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["ts"])
    assert {n: len(v) for n, v in host.items()} == \
        {n: len(v) for n, v in by_name.items()}
    ns0 = host["t.mark"][0] - by_name["t.mark"][0] * 1e3
    offsets = [x - (us * 1e3 + ns0)
               for n in by_name
               for x, us in zip(sorted(host[n]), sorted(by_name[n]))]
    assert max(abs(o) for o in offsets) < 5e6, offsets          # 5 ms


# ------------------------------------------------- end-to-end acceptance
def test_trace_of_compose_simulate_run(table, tmp_path):
    """One compose(refine="simulate") under tracing yields a Perfetto-shaped
    trace holding characterize/score/search/replay spans plus cache-hit and
    B&B-pruning counters (the ISSUE acceptance criterion)."""
    t = gainsight.TASKS[0]
    hit0 = obs.value("hetero.cache_hits")
    miss0 = obs.value("hetero.cache_misses")
    nodes0 = obs.value("hetero.search_nodes")
    pruned0 = obs.value("hetero.search_pruned")
    cp = ComposePolicy(search="branch_and_bound")
    with obs.enabled_scope(True):
        small = DesignTable.from_configs(design_space())
        compose(small, t, compose_policy=cp, cache=tmp_path,
                refine="simulate")
        compose(small, t, compose_policy=cp, cache=tmp_path,
                refine="simulate")             # second call: report-cache hit
        path = tmp_path / "trace.json"
        obs.write(path)

    names = {e["name"] for e in obs.events()}
    assert {"api.characterize", "hetero.compose", "hetero.search",
            "hetero.score", "sim.replay", "sim.rerank"} <= names
    assert obs.value("hetero.cache_misses") == miss0 + 1
    assert obs.value("hetero.cache_hits") == hit0 + 1
    assert obs.value("hetero.search_nodes") > nodes0       # B&B ran
    assert obs.value("hetero.search_pruned") >= pruned0
    hits = [e for e in obs.events()
            if e["name"] == "hetero.compose" and
            e["args"].get("cache") == "hit"]
    assert len(hits) == 1

    doc = json.loads(path.read_text())         # Perfetto-loadable shape
    ctrs = doc["otherData"]["metrics"]["counters"]
    assert "hetero.cache_hits" in ctrs and "hetero.search_pruned" in ctrs
    assert {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"} >= {
        "hetero.cache_hits", "hetero.search_pruned"}


def test_compiler_telemetry_flag(table):
    t = gainsight.TASKS[0]
    Compiler().compose(t, space=table)
    assert obs.events() == []                  # default: off
    Compiler(telemetry=True).compose(t, space=table)
    assert {e["name"] for e in obs.events()} >= {"hetero.compose",
                                                 "hetero.search"}
    assert not obs.enabled()                   # scope-local, not sticky


def test_serve_engine_prefill_decode_spans():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.serve.engine import Engine, make_prefill_step

    cfg = reduce_config(get_config("internlm2-1.8b")).replace(num_layers=1)
    lm, _ = make_prefill_step(cfg, max_seq=32)
    params = lm.init(jax.random.key(0))
    eng = Engine(cfg, params, max_seq=32)
    p0 = obs.value("serve.prefill_calls")
    d0 = obs.value("serve.decode_steps")
    h0 = obs.snapshot()["histograms"].get(
        "serve.decode_step_s", {"count": 0})["count"]
    s0 = obs.snapshot()["histograms"].get(
        "serve.sample_s", {"count": 0})["count"]
    with obs.enabled_scope(True):
        eng.generate({"tokens": jnp.zeros((2, 4), jnp.int32)}, steps=3)
    names = [e["name"] for e in obs.events()]
    assert names.count("serve.prefill") == 1
    assert names.count("serve.decode_step") == 3
    assert obs.value("serve.prefill_calls") == p0 + 1
    assert obs.value("serve.decode_steps") == d0 + 3
    hs = obs.snapshot()["histograms"]["serve.decode_step_s"]
    assert hs["count"] == h0 + 3 and hs["min"] > 0
    # cold engine: the first generate() compiles, and the span counts it
    prefill = next(e for e in obs.events() if e["name"] == "serve.prefill")
    assert prefill["args"].get("compiles", 0) >= 1
    # sampling has its own span + histogram: decode_step time must no longer
    # absorb the sampling math or the host sync (the timing-attribution fix)
    assert names.count("serve.sample") == 3
    ss = obs.snapshot()["histograms"]["serve.sample_s"]
    assert ss["count"] == s0 + 3 and ss["min"] > 0
    by_start = sorted((e for e in obs.events()
                       if e["name"] in ("serve.decode_step", "serve.sample")),
                      key=lambda e: e["ts"])
    # the loop samples from the previous logits, then decodes: strict
    # (sample, decode) alternation with disjoint spans — the host sync
    # between them is charged to neither
    for samp, dec in zip(by_start[::2], by_start[1::2]):
        assert (samp["name"], dec["name"]) == ("serve.sample",
                                               "serve.decode_step")
        assert dec["ts"] >= samp["ts"] + samp["dur"]


def test_kernels_dispatch_counter():
    name = "kernels.dispatch.sim_replay.xla"
    n0 = obs.value(name)
    kbackend.get_impl("sim_replay", backend="xla")
    assert obs.value(name) == n0 + 1


def test_catalog_covers_every_emitted_name(table):
    with obs.enabled_scope(True):
        compose(table, gainsight.TASKS[0], refine="simulate")
    for e in obs.events():
        assert catalog.covers(e["name"]), e["name"]
    snap = obs.snapshot()
    for section in ("counters", "gauges", "histograms"):
        for name in snap[section]:
            if name.startswith("t."):          # fixtures from this file
                continue
            assert catalog.covers(name), name
