"""Reduction of a JAX profiler trace to the device numbers the benchmark
reports.

``extract`` reads an ``.xplane.pb`` into plain event rows
``[plane, line, name, start_ns, duration_ns]``: the device planes' op and
program lines, and the host's ``bench.*`` marks. ``reduce`` turns rows into
a ``Reduced``: the union of the intervals in which an operation ran on each
device (busy time, averaged over the devices), the traced window, the
device time of each program, and the idle gaps. The rows are JSON, so the
reduction is checked on a small recorded trace (``bench/tests/data``).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Row = Tuple[str, str, str, int, int]


def extract(path) -> List[Row]:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    rows: List[Row] = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, PROGRAMS_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    rows.append((plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return rows


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def program_name(name: str) -> str:
    """A program's name without the id the profiler appends."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class Reduced:
    window: Tuple[int, int]               # traced window [start, end) ns
    busy_ns: float                        # op-interval union, device mean
    programs: Dict[str, int]              # device ns by program name
    busy: List[Tuple[int, int]]           # merged busy intervals, device 0
    obs_origin: Tuple[float, int]         # (obs µs, trace ns) of the mark
    n_devices: int

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def program_ns(self, pattern: str) -> int:
        """Device time of the programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(ns for n, ns in self.programs.items() if rx.search(n))

    def top_programs(self, k: int) -> List[Tuple[str, int]]:
        return sorted(self.programs.items(), key=lambda kv: -kv[1])[:k]

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle intervals of device 0 inside the window."""
        out, t = [], self.window[0]
        for s, e in self.busy:
            if s > t:
                out.append((t, min(s, self.window[1])))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return [(s, e) for s, e in out if e > s]

    def longest_gaps(self, k: int, spans: Sequence[Dict]
                     ) -> List[Tuple[str, int]]:
        """The k longest idle gaps, each named by the deepest host span
        (obs events, µs on their own clock) open at the gap's middle."""
        us0, ns0 = self.obs_origin
        placed = [(ns0 + (e["ts"] - us0) * 1e3,
                   ns0 + (e["ts"] + e["dur"] - us0) * 1e3,
                   e.get("depth", 0), e["name"]) for e in spans]
        out = []
        for s, e in sorted(self.gaps(), key=lambda g: g[0] - g[1])[:k]:
            mid = (s + e) / 2
            open_ = [p for p in placed if p[0] <= mid < p[1]]
            label = max(open_, key=lambda p: p[2])[3] if open_ else "host"
            out.append((label, e - s))
        return out


def reduce(rows: Sequence[Row], window_mark: str, window_obs_us: float
           ) -> Reduced:
    """Reduce extracted rows to the window ``window_mark`` names (a host
    annotation, recorded at ``window_obs_us`` on the program's span clock).
    Raises ``ValueError`` when the trace holds no device operation."""
    marks = [r for r in rows if r[2] == window_mark]
    if not marks:
        raise ValueError(f"no {window_mark!r} mark in the trace")
    w0 = marks[0][3]
    window = (w0, w0 + marks[0][4])
    ops: Dict[str, List[Tuple[int, int]]] = {}
    programs: Dict[str, int] = {}
    for plane, line, name, start, dur in rows:
        if not DEVICE_PLANE.match(plane):
            continue
        s, e = max(start, window[0]), min(start + dur, window[1])
        if e <= s:
            continue
        if line == OPS_LINE:
            ops.setdefault(plane, []).append((s, e))
        elif line == PROGRAMS_LINE:
            n = program_name(name)
            programs[n] = programs.get(n, 0) + (e - s)
    if not ops:
        raise ValueError("the trace holds no device operation in the window")
    merged = {p: union(iv) for p, iv in ops.items()}
    busy = sum(sum(e - s for s, e in m) for m in merged.values()) \
        / len(merged)
    first = sorted(merged)[0]
    return Reduced(window=window, busy_ns=busy, programs=programs,
                   busy=merged[first], obs_origin=(window_obs_us, w0),
                   n_devices=len(merged))
