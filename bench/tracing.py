"""The traced window: the JAX profiler and the program's spans around it,
and the context the per-layer readers take their numbers from.

The device side comes from the profiler's trace (``bench/trace_reduce.py``
reduces it); the host side from the program's ``repro.obs`` spans, which
the harness turns on for the window only. Both are put on one clock by the
``bench.window`` mark, which the harness records in each.
"""
from __future__ import annotations

import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

from bench import trace_reduce

WINDOW = "bench.window"
QUERY = "bench.query"


@dataclass
class Context:
    """What a per-layer reader may read (see ``bench/metrics/``)."""
    queries: int                      # queries completed in the window
    window_s: float                   # host seconds of the window
    compiles: int                     # backend compilations in the window
    spans: List[Dict]                 # obs events inside the window
    device: trace_reduce.Reduced
    cases: list                       # the catalog
    which: List[int]                  # catalog index of each query
    reports: Dict[int, list]          # the reports, by catalog index
    device_kind: str = ""

    @property
    def busy_s(self) -> float:
        return self.device.busy_ns / 1e9

    @property
    def trace_window_s(self) -> float:
        return self.device.window_ns / 1e9

    def span_total_s(self, name: str) -> float:
        return sum(e["dur"] for e in self.spans if e["name"] == name) / 1e6

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [[n, ns / 1e9] for n, ns in
                               self.device.top_programs(10)],
                "idle_gaps": [[label, ns / 1e9] for label, ns in
                              self.device.longest_gaps(10, self.spans)]}


@dataclass
class Tracer:
    out_dir: Path
    _annotation: object = None
    _obs_scope: object = None
    _window_span: object = None
    _events: List[Dict] = field(default_factory=list)

    def start(self) -> None:
        import jax
        from repro import obs
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the host's Python frames are
        opts.host_tracer_level = 1         # not traced: spans name them
        obs.clear()
        self._obs_scope = obs.enabled_scope(True)
        self._obs_scope.__enter__()
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW)
        self._annotation.__enter__()
        self._window_span = obs.span(WINDOW)
        self._window_span.__enter__()

    def stop(self) -> None:
        import jax
        from repro import obs
        self._window_span.__exit__(None, None, None)
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._obs_scope.__exit__(None, None, None)
        self._events = obs.events()
        obs.clear()

    def context(self, **kw) -> Context:
        window = next(e for e in self._events if e["name"] == WINDOW)
        t0, t1 = window["ts"], window["ts"] + window["dur"]
        spans = [e for e in self._events
                 if e["name"] != WINDOW and t0 <= e["ts"] <= t1]
        pb = sorted(self.out_dir.rglob("*.xplane.pb"))
        if not pb:
            raise FileNotFoundError(f"the profiler wrote no .xplane.pb "
                                    f"under {self.out_dir}")
        device = trace_reduce.reduce(trace_reduce.extract(pb[-1]),
                                     WINDOW, window["ts"])
        return Context(spans=spans, device=device, **kw)

    def discard(self) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)


def query_span(name: str):
    """A span around one query of the window (a no-op while untraced)."""
    from repro import obs
    return obs.span(QUERY, case=name)
