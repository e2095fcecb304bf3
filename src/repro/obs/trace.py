"""Zero-dependency span tracer: wall-clock spans with counter deltas.

``span("hetero.score", J=4096)`` is a context manager that records one
trace event — name, category, start timestamp and duration [µs], nesting
depth, an ``id`` and the ``parent`` span's id, thread id, and arbitrary
JSON-serializable ``args``. When tracing is *disabled* (the default)
``span()`` returns a shared no-op singleton: no allocation, no timestamp
read, no lock — the instrumented hot paths pay one module-global boolean
check.

Contract highlights (docs/OBSERVABILITY.md spells out the full catalog):

- **exception safety**: a span body that raises still closes its event
  (the exception type lands in ``args["error"]``) and the exception
  propagates unchanged — tracing never swallows errors.
- **compile and transfer counts**: every span diffs the always-on
  counters ``jax.compiles`` (programs compiled or loaded from the
  persistent cache, fed by the listener
  ``repro.compile_cache.count_compiles`` registers), ``device.fetches``
  (device-to-host transfers, ``repro.transfer.fetch`` / ``fetch_tree``) and
  ``device.puts`` (host arrays sent to the device, ``repro.transfer.put``)
  across its body; a nonzero delta lands in ``args["compiles"]`` /
  ``args["fetches"]`` / ``args["puts"]``, so a trace shows exactly which
  call paid a compilation or a transfer. Nothing is wrapped — jit
  cache keys and trace counts are untouched.
- **identity**: ``id`` is unique within the process; ``parent`` is the id
  of the enclosing span on the same thread (None at the top), so the
  spans of one query are the descendants of its outermost span.
- **one clock with the device**: while a span is open it also holds a
  ``jax.profiler.TraceAnnotation`` of the same name when jax is already
  imported, so under the JAX profiler the program's spans sit in the
  profiler's own trace beside the device ops.
- **activation**: ``REPRO_TRACE=out.json`` in the environment enables
  tracing at import and writes the Chrome-trace file at process exit;
  ``enabled_scope(True)`` / ``enable()`` do the same programmatically
  (``repro.api.Compiler(telemetry=True)`` wraps its calls in a scope).

Everything here is stdlib-only: no jax import, no numpy — the tracer
itself can never add a jit trace-cache entry (RC budgets) or touch
numerics; jax is only looked up in ``sys.modules``.
"""
from __future__ import annotations

import atexit
import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from repro.obs import metrics

# process epoch: event timestamps are µs since this module was imported
_T0 = time.perf_counter()

_lock = threading.Lock()
_events: List[Dict[str, object]] = []
_enabled = False
_out_path: Optional[str] = None
_tls = threading.local()
_ids = itertools.count(1)

# the always-on counters every enabled span diffs across its body
COMPILES = metrics.counter("jax.compiles")
FETCHES = metrics.counter("device.fetches")
PUTS = metrics.counter("device.puts")


def enabled() -> bool:
    """Is span recording currently on?"""
    return _enabled


def enable(path: Optional[str] = None) -> None:
    """Turn span recording on; ``path`` (optional) is where ``write()`` /
    the atexit flush will put the Chrome-trace file."""
    global _enabled, _out_path
    if path is not None:
        _out_path = str(path)
    _enabled = True


def disable() -> None:
    """Turn span recording off (already-recorded events are kept)."""
    global _enabled
    _enabled = False


@contextmanager
def enabled_scope(on: bool = True):
    """Force tracing on (or off) inside the block, restoring the previous
    state on exit — the scope ``Compiler(telemetry=True)`` uses."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    try:
        yield
    finally:
        _enabled = prev


class _NullSpan:
    """Shared do-nothing span returned while tracing is disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        return self


_NULL = _NullSpan()


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` when jax is already
    imported (never imported from here), else None."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    make = getattr(profiler, "TraceAnnotation", None)
    return make(name) if make is not None else None


class Span:
    """One live span (use via ``span(...)``, not directly)."""
    __slots__ = ("name", "cat", "args", "_t0", "_depth", "_id", "_parent",
                 "_compiles0", "_fetches0", "_puts0", "_annot")

    def __init__(self, name: str, cat: str, args: Dict[str, object]):
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **kw):
        """Attach extra args mid-span (e.g. results known only at the end)."""
        self.args.update(kw)
        return self

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._depth = len(stack)
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._annot = _annotation(self.name)
        if self._annot is not None:
            self._annot.__enter__()
        self._compiles0 = COMPILES.value
        self._fetches0 = FETCHES.value
        self._puts0 = PUTS.value
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        compiles = COMPILES.value - self._compiles0
        fetches = FETCHES.value - self._fetches0
        puts = PUTS.value - self._puts0
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
        _tls.stack.pop()
        args = dict(self.args)
        if compiles:
            args["compiles"] = compiles
        if fetches:
            args["fetches"] = fetches
        if puts:
            args["puts"] = puts
        if exc_type is not None:
            args["error"] = exc_type.__name__
        event = {
            "name": self.name,
            "cat": self.cat,
            "ph": "X",
            "ts": (self._t0 - _T0) * 1e6,       # µs since process epoch
            "dur": (t1 - self._t0) * 1e6,       # µs
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "depth": self._depth,
            "id": self._id,
            "parent": self._parent,
            "args": args,
        }
        with _lock:
            _events.append(event)
        return False                             # never swallow the exception


def span(name: str, cat: str = "repro", **args):
    """Context manager recording one trace event (no-op when disabled)."""
    if not _enabled:
        return _NULL
    return Span(name, cat, args)


def events() -> List[Dict[str, object]]:
    """Snapshot (copy) of every recorded event so far."""
    with _lock:
        return list(_events)


def clear() -> None:
    """Drop all recorded events (the enabled flag is untouched)."""
    with _lock:
        _events.clear()


def write(path: Optional[str] = None) -> Optional[str]:
    """Flush recorded events + the metrics snapshot to ``path`` (or the
    ``REPRO_TRACE``/``enable(path=...)`` destination). Format by suffix:
    ``.jsonl`` → JSON-lines, anything else → Chrome trace-event JSON.
    Returns the path written, or None if there was nowhere to write."""
    from repro.obs import export, metrics
    dest = path or _out_path
    if dest is None:
        return None
    export.write(dest, events(), metrics.REGISTRY.snapshot())
    return dest


def _flush_at_exit() -> None:
    if _out_path is not None and (_events or _enabled):
        try:
            write()
        except Exception:                        # never break interpreter exit
            pass


atexit.register(_flush_at_exit)

_env_path = os.environ.get("REPRO_TRACE")
if _env_path:
    enable(_env_path)
