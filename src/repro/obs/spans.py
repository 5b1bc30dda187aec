"""Span tracer: nested, thread-aware timing spans exportable to the
Chrome/Perfetto trace-event format.

``span(name, **args)`` returns a context manager.  When observability is
disabled it returns a shared no-op object (no allocation, no clock reads),
so instrumented hot paths cost one truthiness check.  When enabled, each
span records wall-clock begin/duration (``perf_counter_ns``) plus the
thread id; nesting falls out of the complete-event ("ph": "X") encoding —
Perfetto reconstructs the stack from containment per thread.

An enabled span also opens a ``jax.profiler.TraceAnnotation`` of the same
name for its extent, so while a ``jax.profiler`` trace runs the spans sit
on its host timeline, on the profiler's clock, beside the device's
operations.  The span's arguments are formatted into the annotation only
while a profiler session records it.

Export with :func:`export_trace`; load the JSON at https://ui.perfetto.dev
or chrome://tracing.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

_ENABLED = False
_EVENTS: List[tuple] = []        # (name, t0_ns, dur_ns, tid, args)
_LOCK = threading.Lock()


class _NullSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


_ANNOTATION = None               # jax.profiler.TraceAnnotation, on first use


def _annotation(name: str, args: Dict[str, object]):
    global _ANNOTATION
    if _ANNOTATION is None:
        # lazy: importing this package must not import jax
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    if args and _ANNOTATION.is_enabled():
        return _ANNOTATION(name, **args)
    return _ANNOTATION(name)


class _Span:
    __slots__ = ("name", "args", "t0", "ann")

    def __init__(self, name: str, args: Dict[str, object]):
        self.name = name
        self.args = args

    def __enter__(self):
        self.ann = _annotation(self.name, self.args)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        self.ann.__exit__(*exc)
        with _LOCK:
            _EVENTS.append((self.name, self.t0, dur,
                            threading.get_ident(), self.args))
        return False


def span(name: str, **args):
    """Open a timing span: ``with obs.span("scan", policy="hms"): ...``.
    No-op (shared singleton) while observability is disabled."""
    if not _ENABLED:
        return _NULL
    return _Span(name, args)


def engine_call(engine: str, fn, args, readback):
    """One call of a jitted engine as three spans: ``engine_dispatch``
    (the call until it returns), ``engine_wait`` (until its outputs are
    ready on the device) and ``engine_readback`` (``readback`` of the
    outputs, their copy to the host).  Returns what ``readback`` does."""
    import jax

    with span("engine_dispatch", engine=engine):
        out = fn(*args)
    with span("engine_wait", engine=engine):
        jax.block_until_ready(out)
    with span("engine_readback", engine=engine):
        return readback(out)


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def events() -> List[tuple]:
    with _LOCK:
        return list(_EVENTS)


def clear_events() -> None:
    with _LOCK:
        _EVENTS.clear()


def export_trace(path: str, *, clear: bool = False) -> str:
    """Write collected spans as Chrome trace-event JSON (complete events,
    microsecond timestamps).  Returns the written path.  ``clear`` drops
    the event buffer after a successful write."""
    if os.path.isdir(path):
        path = os.path.join(path, "trace.json")
    pid = os.getpid()
    with _LOCK:
        evs = list(_EVENTS)
    trace_events = [{
        "name": name,
        "ph": "X",
        "ts": t0 / 1e3,             # ns -> us
        "dur": dur / 1e3,
        "pid": pid,
        "tid": tid % 2**31,
        "args": args,
    } for name, t0, dur, tid, args in evs]
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": trace_events,
                   "displayTimeUnit": "ms"}, f)
    if clear:
        clear_events()
    return path
