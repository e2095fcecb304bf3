"""CK: cache-key completeness.

The repo caches characterization tables, composition reports, and simulated
re-ranks under content fingerprints. PR 5's bug class was *key drift*: a new
policy field (``corners``, ``robust``) that silently did not flow into the
key, so stale cached reports were served for new inputs. This checker pins
the key-construction sites and cross-checks them against the dataclasses
they must fingerprint:

CK01  every field of SelectionPolicy / ComposePolicy / SimPolicy /
      OperatingPoint (and TaskReq, plus MacroConfig vs VEC_FIELDS) must be
      *covered* by its key function — via ``dataclasses.asdict``/``astuple``/
      ``fields`` on the parameter, a direct ``param.field`` access, a
      same-module helper the parameter is passed to, or a method call on the
      parameter (recursed into).
CK02  every parameter of a key function must be read in its body.
CK03  a key function must reference its required ingredients (e.g.
      ``grid_hash`` must call ``corners_fingerprint`` and ``_hash_seed``).
CK04  ``_physics_fingerprint`` must hash (at least) the ``repro.core``
      import closure of ``core/characterize.py``.
CK05  a spec target (file / function / class) no longer exists — the
      checker spec itself rotted and must be updated with the code.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from repro.analysis.astutil import (
    Module, Project, arg_names, classes_of, dataclass_fields, dotted,
    functions_of, import_aliases, methods_of, names_read,
)
from repro.analysis.findings import Finding

# (key-fn file, key-fn qualname, param name, dataclass file, dataclass name)
DATACLASS_SPECS: Tuple[Tuple[str, str, str, str, str], ...] = (
    ("src/repro/hetero/cache.py", "report_key", "policy",
     "src/repro/core/select.py", "SelectionPolicy"),
    ("src/repro/hetero/cache.py", "report_key", "compose_policy",
     "src/repro/hetero/compose.py", "ComposePolicy"),
    ("src/repro/hetero/cache.py", "report_key", "task",
     "src/repro/core/select.py", "TaskReq"),
    ("src/repro/hetero/cache.py", "sim_report_key", "sim_policy",
     "src/repro/sim/engine.py", "SimPolicy"),
    ("src/repro/core/corners.py", "OperatingPoint.fingerprint", "self",
     "src/repro/core/corners.py", "OperatingPoint"),
)

# (key-fn file, key-fn qualname, required ingredient names)
INGREDIENT_SPECS: Tuple[Tuple[str, str, Tuple[str, ...]], ...] = (
    ("src/repro/api.py", "grid_hash",
     ("_hash_seed", "corners_fingerprint", "AXIS_NAMES")),
    ("src/repro/api.py", "DesignTable.grid_hash",
     ("_hash_seed", "corners_fingerprint", "AXIS_NAMES")),
    ("src/repro/api.py", "_hash_seed",
     ("_physics_fingerprint", "_device_stamp")),
    ("src/repro/core/corners.py", "corners_fingerprint", ("fingerprint",)),
    ("src/repro/hetero/cache.py", "report_key", ("_task_fingerprint",)),
)

# every key function: all parameters must be read (CK02)
KEY_FUNCTIONS: Tuple[Tuple[str, str], ...] = (
    ("src/repro/hetero/cache.py", "report_key"),
    ("src/repro/hetero/cache.py", "sim_report_key"),
    ("src/repro/api.py", "grid_hash"),
    ("src/repro/api.py", "DesignTable.grid_hash"),
    ("src/repro/core/corners.py", "OperatingPoint.fingerprint"),
    ("src/repro/core/corners.py", "corners_fingerprint"),
)

# vmap axis spec vs config dataclass (the characterize grid must stack every
# config axis, or a new MacroConfig field silently never varies)
VEC_FIELDS_SPEC = ("src/repro/core/macro.py", "VEC_FIELDS", "MacroConfig")

PHYSICS_FP_SPEC = ("src/repro/api.py", "_physics_fingerprint",
                   "src/repro/core/characterize.py")

_EXPAND_CALLS = {"asdict", "astuple", "fields"}


def _find_fn(mod: Module, qualname: str) -> Optional[ast.AST]:
    if "." in qualname:
        cls_name, meth = qualname.split(".", 1)
        cls = classes_of(mod.tree).get(cls_name)
        if cls is None:
            return None
        return methods_of(cls).get(meth)
    return functions_of(mod.tree).get(qualname)


def _coverage(mod: Module, fn: ast.AST, param: str,
              dc_mod: Module, dc_cls: Optional[ast.ClassDef],
              depth: int = 0) -> Set[str]:
    """Field names of ``param`` provably flowing into the key built by
    ``fn``. The sentinel '*' means full coverage (asdict and friends)."""
    covered: Set[str] = set()
    if depth > 4:
        return covered
    funcs = functions_of(mod.tree)
    dc_methods = methods_of(dc_cls) if dc_cls is not None else {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == param:
            covered.add(node.attr)
        if not isinstance(node, ast.Call):
            continue
        callee = dotted(node.func)
        first_arg_is_param = bool(
            node.args and isinstance(node.args[0], ast.Name)
            and node.args[0].id == param)
        if callee and callee.split(".")[-1] in _EXPAND_CALLS \
                and first_arg_is_param:
            covered.add("*")
            return covered
        if callee == "getattr" and first_arg_is_param and len(node.args) > 1:
            if isinstance(node.args[1], ast.Constant) and \
                    isinstance(node.args[1].value, str):
                covered.add(node.args[1].value)
        # helper(.., param, ..) defined in the same module: recurse with the
        # helper's matching parameter name
        if isinstance(node.func, ast.Name) and node.func.id in funcs:
            helper = funcs[node.func.id]
            hargs = arg_names(helper)
            for i, a in enumerate(node.args):
                if isinstance(a, ast.Name) and a.id == param and i < len(hargs):
                    covered |= _coverage(mod, helper, hargs[i], dc_mod,
                                         dc_cls, depth + 1)
        # param.method(...): recurse into the dataclass method as `self`
        if isinstance(node.func, ast.Attribute) and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id == param and \
                node.func.attr in dc_methods:
            covered |= _coverage(dc_mod, dc_methods[node.func.attr], "self",
                                 dc_mod, dc_cls, depth + 1)
    return covered


def _references(fn: ast.AST, name: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id == name:
            return True
        if isinstance(node, ast.Attribute) and node.attr == name:
            return True
    return False


def _spec_missing(findings: List[Finding], rel: str, what: str) -> None:
    findings.append(Finding("CK05", rel, 0, f"spec target missing: {what}",
                            snippet=what))


def _check_dataclass_specs(project: Project, findings: List[Finding]) -> None:
    for fn_rel, qual, param, dc_rel, dc_name in DATACLASS_SPECS:
        mod = project.module(fn_rel)
        dc_mod = project.module(dc_rel)
        if mod is None:
            _spec_missing(findings, fn_rel, f"file {fn_rel}")
            continue
        if dc_mod is None:
            _spec_missing(findings, dc_rel, f"file {dc_rel}")
            continue
        fn = _find_fn(mod, qual)
        if fn is None:
            _spec_missing(findings, fn_rel, f"function {qual}")
            continue
        dc_cls = classes_of(dc_mod.tree).get(dc_name)
        if dc_cls is None:
            _spec_missing(findings, dc_rel, f"class {dc_name}")
            continue
        fields = dataclass_fields(dc_cls)
        covered = _coverage(mod, fn, param, dc_mod, dc_cls)
        if "*" in covered:
            continue
        for f in fields:
            if f not in covered:
                findings.append(Finding(
                    "CK01", fn_rel, fn.lineno,
                    f"{dc_name}.{f} does not flow into {qual} — a value "
                    f"change would silently hit a stale cache entry",
                    snippet=f"{qual}<-{dc_name}.{f}"))


def _check_ingredients(project: Project, findings: List[Finding]) -> None:
    for fn_rel, qual, ingredients in INGREDIENT_SPECS:
        mod = project.module(fn_rel)
        if mod is None:
            _spec_missing(findings, fn_rel, f"file {fn_rel}")
            continue
        fn = _find_fn(mod, qual)
        if fn is None:
            _spec_missing(findings, fn_rel, f"function {qual}")
            continue
        for ing in ingredients:
            if not _references(fn, ing):
                findings.append(Finding(
                    "CK03", fn_rel, fn.lineno,
                    f"{qual} no longer references required key ingredient "
                    f"{ing!r}", snippet=f"{qual}<-{ing}"))


def _check_params_read(project: Project, findings: List[Finding]) -> None:
    for fn_rel, qual in KEY_FUNCTIONS:
        mod = project.module(fn_rel)
        if mod is None:
            continue    # CK05 already raised by the other passes
        fn = _find_fn(mod, qual)
        if fn is None:
            continue
        read = names_read(ast.Module(body=fn.body, type_ignores=[]))
        for p in arg_names(fn):
            if p.startswith("_"):
                continue
            if p not in read:
                findings.append(Finding(
                    "CK02", fn_rel, fn.lineno,
                    f"parameter {p!r} of key function {qual} is never read "
                    f"— it cannot affect the cache key",
                    snippet=f"{qual}({p})"))


def _check_vec_fields(project: Project, findings: List[Finding]) -> None:
    rel, var, dc_name = VEC_FIELDS_SPEC
    mod = project.module(rel)
    if mod is None:
        _spec_missing(findings, rel, f"file {rel}")
        return
    dc_cls = classes_of(mod.tree).get(dc_name)
    if dc_cls is None:
        _spec_missing(findings, rel, f"class {dc_name}")
        return
    vec_node = None
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == var:
                    vec_node = node
    if vec_node is None:
        _spec_missing(findings, rel, f"assignment {var}")
        return
    listed = {n.value for n in ast.walk(vec_node.value)
              if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    for f in dataclass_fields(dc_cls):
        if f not in listed:
            findings.append(Finding(
                "CK01", rel, vec_node.lineno,
                f"{dc_name}.{f} missing from {var} — the axis would never "
                f"vary in the vmap grid and never enter the grid hash",
                snippet=f"{var}<-{dc_name}.{f}"))


def _module_basename(dotted_name: str) -> Optional[str]:
    parts = dotted_name.split(".")
    if parts[:2] == ["repro", "core"] and len(parts) >= 3:
        return parts[2]
    return None


def _core_import_closure(project: Project, start_rel: str) -> Set[str]:
    """Basenames of repro.core modules transitively imported from start."""
    seen: Set[str] = set()
    queue = [start_rel]
    while queue:
        rel = queue.pop()
        mod = project.module(rel)
        if mod is None:
            continue
        base = rel.rsplit("/", 1)[-1][:-3]
        if base in seen:
            continue
        seen.add(base)
        for node in ast.walk(mod.tree):
            targets = []
            if isinstance(node, ast.Import):
                targets = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module == "repro.core":
                    targets = [f"repro.core.{a.name}" for a in node.names]
                else:
                    targets = [node.module]
            for t in targets:
                b = _module_basename(t)
                if b and b not in seen:
                    queue.append(f"src/repro/core/{b}.py")
    return seen


def _check_physics_fingerprint(project: Project,
                               findings: List[Finding]) -> None:
    api_rel, fp_name, chz_rel = PHYSICS_FP_SPEC
    mod = project.module(api_rel)
    if mod is None:
        _spec_missing(findings, api_rel, f"file {api_rel}")
        return
    fn = functions_of(mod.tree).get(fp_name)
    if fn is None:
        _spec_missing(findings, api_rel, f"function {fp_name}")
        return
    aliases = import_aliases(mod.tree)
    # also pick up imports local to the fingerprint function itself
    aliases.update(import_aliases(ast.Module(body=fn.body, type_ignores=[])))
    hashed: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.For):
            for n in ast.walk(node.iter):
                if isinstance(n, ast.Name) and n.id in aliases:
                    b = _module_basename(aliases[n.id])
                    if b:
                        hashed.add(b)
    closure = _core_import_closure(project, chz_rel)
    for b in sorted(closure - hashed):
        findings.append(Finding(
            "CK04", api_rel, fn.lineno,
            f"repro.core.{b} is in the import closure of characterize but "
            f"is not hashed by {fp_name} — edits there would not invalidate "
            f"cached tables", snippet=f"{fp_name}<-{b}"))


def check(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    _check_dataclass_specs(project, findings)
    _check_ingredients(project, findings)
    _check_params_read(project, findings)
    _check_vec_fields(project, findings)
    _check_physics_fingerprint(project, findings)
    return findings
