"""Pallas-call interception + overlay-aware kernel loading for the PB tier.

``intercept_pallas`` monkeypatches ``jax.experimental.pallas.pallas_call``
with a recorder: instead of lowering a kernel it captures the launch
geometry — grid, BlockSpecs, dimension_semantics, operand/out shapes, and
the call-site file:line — and returns zeros of ``out_shape`` so the wrapper
function completes without executing anything. The PB checker then proves
properties of the captured index maps symbolically.

``load_function`` executes a kernel module's *source* (through the
analyzer's ``Project``, so test overlays apply) into a throwaway namespace:
the PB checker verifies exactly the text under analysis, not whatever is
already imported in ``sys.modules``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class PallasCapture:
    """One intercepted ``pallas_call``: everything PB needs to verify it."""
    kernel_name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]                    # pl.BlockSpec objects
    out_specs: Any                         # pl.BlockSpec (single output)
    out_shapes: List[Tuple[int, ...]]      # flattened out_shape shapes
    operand_shapes: List[Tuple[int, ...]]
    dimension_semantics: Optional[Tuple[str, ...]]
    path: str                              # repo-relative call-site module
    line: int                              # 1-based pallas_call line


def _kernel_name(kernel) -> str:
    inner = getattr(kernel, "func", kernel)      # unwrap functools.partial
    return getattr(inner, "__name__", repr(inner))


def dimension_semantics_of(compiler_params) -> Optional[Tuple[str, ...]]:
    """The ``dimension_semantics`` of a ``pltpu.CompilerParams`` (None when
    no params or no semantics were given)."""
    ds = getattr(compiler_params, "dimension_semantics", None)
    return tuple(ds) if ds is not None else None


def _call_site(root: Path) -> Tuple[str, int]:
    """(repo-relative path, line) of the innermost caller inside ``root``
    that is not part of the analyzer itself."""
    root = Path(root).resolve()
    f = sys._getframe(2)    # skip _call_site and the fake pallas_call
    while f is not None:
        fn = f.f_code.co_filename
        try:
            rel = Path(fn).resolve().relative_to(root).as_posix()
        except ValueError:
            rel = None
        if rel and "repro/analysis/" not in rel:
            return rel, f.f_lineno
        f = f.f_back
    return "", 0


@contextlib.contextmanager
def intercept_pallas(root):
    """Swap ``pl.pallas_call`` for a recorder; yields the capture list."""
    from jax.experimental import pallas as pl

    captures: List[PallasCapture] = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *args, **kwargs):
        site = _call_site(Path(root))
        out_shape = kwargs.get("out_shape", args[0] if args else None)

        def runner(*operands):
            import jax
            import jax.numpy as jnp
            flat, _ = jax.tree_util.tree_flatten(out_shape)
            captures.append(PallasCapture(
                kernel_name=_kernel_name(kernel),
                grid=tuple(int(g) for g in kwargs.get("grid", ())),
                in_specs=list(kwargs.get("in_specs", ())),
                out_specs=kwargs.get("out_specs"),
                out_shapes=[tuple(s.shape) for s in flat],
                operand_shapes=[tuple(o.shape) for o in operands],
                dimension_semantics=dimension_semantics_of(
                    kwargs.get("compiler_params")),
                path=site[0], line=site[1]))
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield captures
    finally:
        pl.pallas_call = real


def load_function(project, rel: str, name: str):
    """Load ``name`` from the (possibly overlaid) source of ``rel`` by
    executing it in a fresh namespace. Returns None when the module or the
    function is missing — the caller reports spec rot."""
    mod = project.module(rel)
    if mod is None:
        return None
    path = str(Path(project.root) / rel)
    ns: Dict[str, Any] = {"__name__": f"_pb_overlay_{Path(rel).stem}",
                          "__file__": path}
    try:
        exec(compile(mod.source, path, "exec"), ns)
    except Exception:
        return None
    return ns.get(name)
