"""RC — recompilation-cavity audit (semantic tier, executes the jit sites).

Every module-level ``jax.jit`` site in core/hetero/sim carries a committed
trace-cache budget here. The checker drives each site through its public
API with the distinct (shape, static-arg) profiles the benchmarks actually
use, measuring ``_cache_size()`` *deltas* (the suite shares one process, so
absolute counts would be polluted by whatever compiled earlier):

  RC01  driving the profiles grew the cache beyond the budget — a
        static-argnum leak or shape churn silently multiplying compiles
  RC02  re-driving the *same* profiles added entries — the cache key is
        unstable (weak-type flip-flop, unhashable static, fresh closures)
  RC03  a module-level jit site exists with no budget entry (AST sweep,
        overlay-aware) — its compile count is unwatched
  RC04  spec rot: a budgeted site no longer resolves, the cache-size API
        is gone, or a driver crashed

``_characterize_jit`` (an lru-cached per-corner factory *inside* a
function) is intentionally out of scope: RC03 only sweeps module-level
sites, which is exactly the set with process-lifetime caches.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, List, Tuple

from repro.analysis import astutil
from repro.analysis.findings import Finding

SCAN_DIRS = ("src/repro/core", "src/repro/hetero", "src/repro/sim")


# ---------------------------------------------------------------------------
# drivers: exercise the public APIs with the benchmark shape profiles
# ---------------------------------------------------------------------------


def _drive_characterize() -> None:
    import jax.numpy as jnp
    from repro.core import characterize as chz
    from repro.core.macro import MacroConfig
    cfgs = [MacroConfig(mem_type="gc_sisi", word_size=16, num_words=16),
            MacroConfig(mem_type="sram6t", word_size=16, num_words=16),
            MacroConfig(mem_type="gc_ossi", word_size=32, num_words=32)]
    v2 = jnp.stack([c.to_vector() for c in cfgs[:2]])
    v3 = jnp.stack([c.to_vector() for c in cfgs])
    chz.characterize_batch(v2)
    chz.characterize_batch(v3)
    chz.characterize_corners(v2, ("nominal", "hot"))
    chz.characterize_corners(v3, ("nominal", "hot"))


def _drive_retention() -> None:
    import jax.numpy as jnp
    from repro.core import bitcells, retention
    full = bitcells.stack_bitcells()
    retention.retention_time_batch(
        full, jnp.zeros(len(bitcells.MEM_TYPE_ORDER), jnp.int32))
    sub = bitcells.stack_bitcells(("gc_sisi", "gc_ossi", "gc_osos"))
    retention.retention_time_batch(sub, jnp.ones(3, jnp.int32))


def _drive_score() -> None:
    import numpy as np
    from repro.hetero import system
    vals = {"area_um2": 100.0, "bits": 1024.0, "p_leak_w": 1e-6,
            "p_refresh_w": 1e-7, "e_read_j": 1e-12, "f_op_hz": 1e9}
    metrics = {k: np.full(8, v, np.float32)
               for k, v in vals.items()}
    for J in (4, 6):
        system.score_grid(metrics, np.zeros((J, 2), np.int64),
                          [1e6, 1e6], [1e8, 1e8])
    for C, J in ((2, 4), (3, 6)):
        system.score_grid_corners([metrics] * C,
                                  np.zeros((J, 2), np.int64),
                                  [1e6, 1e6], [1e8, 1e8])


def _sim_trace(T: int):
    import numpy as np
    from repro.sim.trace import Trace
    S = 2
    return Trace(phase="prefill",
                 t_bin_s=np.full(T, 1e-5),
                 reads=np.ones((S, T)),
                 write_bits=np.full((S, T), 64.0),
                 occupancy=np.full((S, T), 0.5),
                 cap_bits=np.full(S, 1e6),
                 f_req_hz=np.full(S, 1e8),
                 lifetime_s=np.full(S, 1e-2))


def _drive_sim() -> None:
    import numpy as np
    from repro.sim import engine
    vals = {"bits": 4096.0, "word_bits": 32.0, "e_read_j": 1e-12,
            "e_write_j": 2e-12, "f_op_hz": 1e9, "p_leak_w": 1e-6,
            "retention_s": 1e-3}
    cols = {k: np.full(4, v, np.float32) for k, v in vals.items()}
    idx = np.zeros((3, 2), np.int64)
    for T in (8, 16):
        engine.simulate_traces(cols, idx, [_sim_trace(T)], backend="xla")
    engine.simulate_traces(cols, idx, [_sim_trace(8)], backend="interpret")


DRIVERS: Tuple[Callable[[], None], ...] = (
    _drive_characterize, _drive_retention, _drive_score, _drive_sim)


# ---------------------------------------------------------------------------
# budget spec: every module-level jit site in the scanned packages
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RcSite:
    name: str
    rel: str
    attr: str
    budget: int        # max new trace-cache entries the drivers may add


SITES: Tuple[RcSite, ...] = (
    # two batch sizes
    RcSite("characterize_batch", "src/repro/core/characterize.py",
           "characterize_batch", 2),
    # two batch sizes x one stacked-corner shape
    RcSite("characterize_corners_batch", "src/repro/core/characterize.py",
           "characterize_corners_batch", 2),
    # two batch sizes x one corner count
    RcSite("stack_corners", "src/repro/core/characterize.py",
           "_stack_corners", 2),
    # full bitcell menu + a 3-cell subset
    RcSite("retention_time_batch", "src/repro/core/retention.py",
           "retention_time_batch", 2),
    # two composition-grid heights
    RcSite("score_kernel", "src/repro/hetero/system.py", "_score_jit", 2),
    # two (corner-count x grid-height) profiles on the corner-vmapped path
    RcSite("score_kernel_corners", "src/repro/hetero/system.py",
           "_score_corners_jit", 2),
    # two trace bin counts on the vmapped grid path
    RcSite("sim_grid_xla", "src/repro/sim/engine.py", "_sim_grid_xla", 2),
    # the interpret oracle replays J compositions of identical shape: one
    # trace regardless of J
    RcSite("sim_phase_one", "src/repro/sim/engine.py", "_sim_one_jit", 1),
)


def _resolve(site: RcSite):
    import importlib
    module = site.rel[len("src/"):-len(".py")].replace("/", ".")
    return getattr(importlib.import_module(module), site.attr)


def _cache_size(fn) -> int:
    size = getattr(fn, "_cache_size", None)
    if size is None:
        raise AttributeError(
            f"{fn!r} has no _cache_size() — not a jitted callable, or the "
            f"jax cache-introspection API drifted")
    return int(size())


def _anchor(project, rel: str, attr: str) -> Tuple[int, str]:
    mod = project.module(rel)
    if mod is None:
        return 0, ""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == attr
                for t in node.targets):
            return node.lineno, mod.snippet(node.lineno)
    return 0, ""


def audit_sites(sites=None, drivers=None):
    """Measure (first-pass delta, repeat delta) per site. Returns
    ({site.name: (delta1, delta2)}, [(site, error_str)]) — shared by the
    live checker and the analyzer's own tests. Defaults resolve to the
    module-level SITES/DRIVERS at call time (tests monkeypatch them)."""
    sites = SITES if sites is None else sites
    drivers = DRIVERS if drivers is None else drivers
    resolved, broken = {}, []
    for site in sites:
        try:
            fn = _resolve(site)
            _cache_size(fn)
        except Exception as e:
            broken.append((site, f"{type(e).__name__}: {e}"))
            continue
        resolved[site.name] = (site, fn)

    deltas: Dict[str, Tuple[int, int]] = {}
    before = {n: _cache_size(fn) for n, (_, fn) in resolved.items()}
    errors = []
    for drive in drivers:
        try:
            drive()
        except Exception as e:
            errors.append(f"driver {drive.__name__} failed: "
                          f"{type(e).__name__}: {e}")
    mid = {n: _cache_size(fn) for n, (_, fn) in resolved.items()}
    for drive in drivers:
        try:
            drive()
        except Exception:
            pass    # first pass already reported it
    after = {n: _cache_size(fn) for n, (_, fn) in resolved.items()}
    for n in resolved:
        deltas[n] = (mid[n] - before[n], after[n] - mid[n])
    return deltas, broken, errors


def _jit_sites_in_tree(project) -> List[Tuple[str, str, int]]:
    """(rel, name, line) of every module-level binding whose value calls
    jax.jit, plus defs decorated with it."""
    out = []
    for scan in SCAN_DIRS:
        for mod in project.iter_modules(scan):
            aliases = astutil.import_aliases(mod.tree)

            def is_jit(call: ast.AST) -> bool:
                if not isinstance(call, ast.Call):
                    return False
                d = astutil.dotted(call.func)
                if d is None:
                    return False
                head, _, rest = d.partition(".")
                full = aliases.get(head, head) + ("." + rest if rest else "")
                return full == "jax.jit" or full.endswith(".jax.jit")

            for node in mod.tree.body:
                if isinstance(node, ast.Assign) and any(
                        is_jit(c) for c in ast.walk(node.value)):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out.append((mod.rel, t.id, node.lineno))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) and any(
                        is_jit(d) or (astutil.dotted(d) or "").endswith(
                            "jax.jit")
                        for d in node.decorator_list):
                    out.append((mod.rel, node.name, node.lineno))
    return out


def check(project) -> List[Finding]:
    findings: List[Finding] = []

    def emit(rule, rel, line, snippet, msg):
        findings.append(Finding(rule=rule, path=rel, line=line, message=msg,
                                snippet=snippet))

    # RC03: every module-level jit site must be budgeted
    covered = {(s.rel, s.attr) for s in SITES}
    for rel, name, line in _jit_sites_in_tree(project):
        if (rel, name) not in covered:
            mod = project.module(rel)
            emit("RC03", rel, line, mod.snippet(line) if mod else "",
                 f"module-level jit site {name!r} has no RC budget entry — "
                 f"add it to repro.analysis.semantic.rc.SITES")

    deltas, broken, errors = audit_sites()
    for site, why in broken:
        line, snippet = _anchor(project, site.rel, site.attr)
        emit("RC04", site.rel, line, snippet,
             f"{site.name}: budget-spec entry no longer resolves ({why})")
    for why in errors:
        emit("RC04", "src/repro/analysis/semantic/rc.py", 0, "", why)
    for site in SITES:
        if site.name not in deltas:
            continue
        d1, d2 = deltas[site.name]
        line, snippet = _anchor(project, site.rel, site.attr)
        if d1 > site.budget:
            emit("RC01", site.rel, line, snippet,
                 f"{site.name}: driving its shape profiles added {d1} trace "
                 f"cache entr(y/ies), budget {site.budget} — a static-arg "
                 f"or shape leak is multiplying compiles")
        if d2 > 0:
            emit("RC02", site.rel, line, snippet,
                 f"{site.name}: re-driving identical profiles added {d2} "
                 f"more entr(y/ies) — unstable cache key")
    return findings
