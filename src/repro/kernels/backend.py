"""Kernel backend dispatch: one registry instead of per-call mode strings.

Every compute hot-spot registers up to three implementations:

  "tpu"       — native ``pallas_call`` (requires a TPU device)
  "interpret" — the same Pallas kernel through the interpreter (any device;
                what the test suite exercises)
  "xla"       — the pure jax.numpy oracle from ``kernels/ref.py``

Selection order (``resolve_backend``):

  1. explicit ``backend=`` argument
  2. a ``use_backend(...)`` context override (innermost wins)
  3. ``REPRO_KERNEL_BACKEND`` env var ("tpu" / "interpret" / "xla")
  4. "tpu" when ``jax.default_backend()`` is a TPU, else "xla"

A resolved backend with no registered implementation falls back to "xla",
so ops stay callable on CPU even when only the reference path exists.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Callable, Dict, Optional, Tuple

import jax

BACKENDS = ("tpu", "interpret", "xla")
_FALLBACK = {"tpu": ("tpu", "xla"),
             "interpret": ("interpret", "xla"),
             "xla": ("xla",)}

_REGISTRY: Dict[str, Dict[str, Callable]] = {}

# stack of use_backend() overrides (innermost last); beats the env vars but
# not an explicit backend= argument
_OVERRIDES: list = []


@contextmanager
def use_backend(backend: str):
    """Force ``resolve_backend()`` to ``backend`` inside the block.

    Tests and benchmarks use this to pin every dispatched op (e.g. the
    simulator's interpret-vs-xla equivalence proof) without threading a
    ``backend=`` argument through call stacks or mutating the process env.
    Nested blocks: the innermost wins; an explicit ``backend=`` argument
    still takes precedence.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; valid: {BACKENDS}")
    _OVERRIDES.append(backend)
    try:
        yield
    finally:
        _OVERRIDES.pop()


def register(name: str, **impls: Callable) -> None:
    """Register (or extend) the per-backend implementations of one op."""
    unknown = set(impls) - set(BACKENDS)
    if unknown:
        raise ValueError(
            f"unknown backend(s) {sorted(unknown)} for op {name!r}; "
            f"valid: {BACKENDS}")
    _REGISTRY.setdefault(name, {}).update(impls)


def registered() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def available_backends(name: str) -> Tuple[str, ...]:
    impls = _REGISTRY.get(name, {})
    return tuple(b for b in BACKENDS if b in impls)


def impl_map(name: str) -> Dict[str, Callable]:
    """Copy of one op's backend->implementation mapping. Introspection hook
    for the semantic analyzer (PB profiles every op with a 'tpu' impl) and
    the backend-divergence test sweep; mutating the copy does not touch the
    registry."""
    return dict(_REGISTRY.get(name, {}))


def resolve_backend(explicit: Optional[str] = None) -> str:
    if explicit is not None:
        if explicit not in BACKENDS:
            raise ValueError(f"unknown backend {explicit!r}; valid: {BACKENDS}")
        return explicit
    if _OVERRIDES:
        return _OVERRIDES[-1]
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r} invalid; valid: {BACKENDS}")
        return env
    return "tpu" if jax.default_backend() == "tpu" else "xla"


def get_impl(name: str, backend: Optional[str] = None) -> Callable:
    impls = _REGISTRY.get(name)
    if impls is None:
        raise KeyError(f"no kernel registered under {name!r}; "
                       f"registered: {registered()}")
    resolved = resolve_backend(backend)
    for candidate in _FALLBACK[resolved]:
        if candidate in impls:
            from repro import obs                # lazy: kernels load early
            obs.counter(f"kernels.dispatch.{name}.{candidate}").inc()
            return impls[candidate]
    raise KeyError(f"op {name!r} has no implementation for backend "
                   f"{resolved!r} and no xla fallback")


def dispatch(name: str, *args, backend: Optional[str] = None, **kwargs):
    return get_impl(name, backend)(*args, **kwargs)
