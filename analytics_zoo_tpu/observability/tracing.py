"""Span tracing — Dapper-class parent/child spans (Sigelman et al.,
2010) with contextvar propagation, scoped to one process.

`trace(name, **attrs)` opens a span; nested `trace` calls (same thread
or same asyncio task) pick up the enclosing span as parent via a
contextvar.  Crossing an explicit thread/queue boundary (HTTP handler
thread → batcher thread) is done by capturing `current_span()` on the
submitting side and passing it as `trace(..., parent=span)` on the
executing side — contextvars do not flow into pre-existing threads.

Completed spans land in a bounded in-process ring (`recent_spans`,
served by the serving frontend's GET /spans), are recorded as a
duration histogram `span_<name>_seconds` in the global MetricsRegistry,
and are appended to the JSONL event sink when
`OrcaContext.observability_dir` is set.

Every span is also a `jax.profiler.TraceAnnotation` named
``azt:<name>``: while a profiler session is open (an operator's, the
benchmark's — the program opens none) the span lands on the host plane
of the profiler's own trace, on the clock of the device's events, so an
idle gap of the device can be put down to the span that was open in it.
With no session open the annotation records nothing.  `phase(name)` is
that annotation alone, for the phases of a hot loop.
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, List, Optional

from analytics_zoo_tpu.observability.registry import (
    get_registry,
    now,
    sanitize_metric_name,
)

_CURRENT: "ContextVar[Optional[Span]]" = ContextVar(
    "azt_current_span", default=None)

_MAX_SPANS = 2048
_ring_lock = threading.Lock()
_ring: "deque[Dict[str, Any]]" = deque(maxlen=_MAX_SPANS)


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed operation.  Mutable while open (attrs via
    `annotate`); snapshotted into the ring at close."""

    __slots__ = ("name", "span_id", "parent_id", "trace_id", "attrs",
                 "thread", "start_ts", "_t0", "duration_s", "error")

    def __init__(self, name: str, parent: Optional["Span"] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.span_id = _new_id()
        self.parent_id = parent.span_id if parent is not None else None
        self.trace_id = (parent.trace_id if parent is not None
                         else self.span_id)
        self.attrs = dict(attrs or {})
        self.thread = threading.current_thread().name
        self.start_ts = time.time()   # wall clock, for humans/logs
        self._t0 = now()              # monotonic, for the duration
        self.duration_s: Optional[float] = None
        self.error: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "thread": self.thread,
            "start_ts": round(self.start_ts, 6),
            "duration_s": (round(self.duration_s, 9)
                           if self.duration_s is not None else None),
            "attrs": dict(self.attrs),
        }
        if self.error:
            d["error"] = self.error
        return d


#: what every span is called in a profiler trace, before its own name
TRACE_PREFIX = "azt:"
_annotation = None


def phase(name: str):
    """The light form of a span, for a phase of a hot loop (a dozen a
    round): a context manager that is the profiler annotation
    ``azt:<name>`` and nothing else — no `Span`, no ring entry, no
    histogram, no lock, no clock read.  It shows only in a profiler
    trace, never in `recent_spans`."""
    global _annotation
    if _annotation is None:
        # here and not at the top: a host-only consumer of this package
        # imports no jax, and the import initialises no backend
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(TRACE_PREFIX + name)


def current_span() -> Optional[Span]:
    """The innermost open span of this thread/context (None outside any
    `trace` block).  Capture it before handing work to another thread
    and pass it as `trace(..., parent=...)` there."""
    return _CURRENT.get()


def annotate(**attrs) -> None:
    """Attach attributes to the current open span (no-op outside one) —
    how JAX-aware facts (jit compile vs execute, device-put bytes) ride
    on the span that caused them."""
    span = _CURRENT.get()
    if span is not None:
        span.attrs.update(attrs)


_MISSING = object()


@contextmanager
def trace(name: str, parent: Any = _MISSING, record_metric: bool = True,
          **attrs):
    """Open a span for the enclosed block.

    parent: defaults to `current_span()` (contextvar propagation);
        pass an explicit Span (or None for a fresh root) when crossing
        a thread/queue boundary.  Anything exposing `.span_id` and
        `.trace_id` works — notably a remote
        `trace_context.TraceContext` received from another process.
        With no local span open, the ambient remote parent bound via
        `trace_context.bind` (or the TRACEPARENT env var) is used, so
        the first span after a cross-process hop joins the caller's
        trace automatically.
    record_metric: also record the duration into the global registry
        histogram `span_<name>_seconds` (default on).
    Other kwargs become span attributes.
    """
    p = current_span() if parent is _MISSING else parent
    if p is None and parent is _MISSING:
        # call-time import: trace_context imports this module lazily too
        from analytics_zoo_tpu.observability import trace_context
        p = trace_context.remote_parent()
    span = Span(name, parent=p, attrs=attrs)
    token = _CURRENT.set(span)
    try:
        with phase(name):
            yield span
    except BaseException as e:
        span.error = f"{type(e).__name__}: {e}"
        raise
    finally:
        _CURRENT.reset(token)
        span.duration_s = now() - span._t0
        _finish(span, record_metric)


def _finish(span: Span, record_metric: bool) -> None:
    with _ring_lock:
        _ring.append(span.to_dict())
    if record_metric:
        get_registry().histogram(
            "span_" + sanitize_metric_name(span.name) + "_seconds",
            help=f"wall time of {span.name} spans").record(
            span.duration_s)
    # the JSONL sink is configured via OrcaContext.observability_dir;
    # import at call time — events imports this module's ring helpers
    from analytics_zoo_tpu.observability.events import sink_enabled
    if sink_enabled():
        from analytics_zoo_tpu.observability.events import log_event
        log_event("span", _count_metric=False, **span.to_dict())


def recent_spans(n: int = 100) -> List[Dict[str, Any]]:
    """The most recent `n` COMPLETED spans, newest first (what the
    serving GET /spans endpoint returns)."""
    with _ring_lock:
        items = list(_ring)
    return list(reversed(items[-max(0, int(n)):]))


def clear_spans() -> None:
    """Drop the completed-span ring (tests)."""
    with _ring_lock:
        _ring.clear()
