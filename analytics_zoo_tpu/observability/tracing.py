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

While a session records — `enabled()` — and only then, the program
also reads what no reader of the trace can: a thread's own CPU clock.
`mark(name, **counts)` is an instant annotation ``azt:<name>[k=v,…]``;
a `LoopClock` charges a loop thread's CPU to the innermost open
`phase()` in one round of every few and marks that round's sums, so
that a phase's wall in the trace can be set against the CPU the thread
had in it: the rest is the thread standing without the interpreter
lock, or without a core.  `request_clock` and `mark_request` do the
same for the thread that carries a request, one request in every
tenth of a second.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Sequence

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
#: the loop clocks inside a clocked round now, by their thread
_clocked: Dict[int, "LoopClock"] = {}


def _trace_annotation():
    global _annotation
    if _annotation is None:
        # here and not at the top: a host-only consumer of this package
        # imports no jax, and the import initialises no backend
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def enabled() -> bool:
    """Whether a profiler session is recording, so that an annotation
    made now lands in its trace (some 20 ns a call).  A process that
    has not imported jax has no session, and is not made to import it
    (the streaming client in a host-only process)."""
    if _annotation is None and "jax" not in sys.modules:
        return False
    return (_annotation or _trace_annotation()).is_enabled()


def phase(name: str):
    """The light form of a span, for a phase of a hot loop (a dozen a
    round): a context manager that is the profiler annotation
    ``azt:<name>`` and nothing else — no `Span`, no ring entry, no
    histogram, no lock, no clock read.  It shows only in a profiler
    trace, never in `recent_spans`.  On a thread whose `LoopClock` is
    inside a clocked round (a profiler session records) its two ends
    are also that clock's boundaries."""
    span = (_annotation or _trace_annotation())(TRACE_PREFIX + name)
    if _clocked:
        clock = _clocked.get(threading.get_ident())
        if clock is not None:
            return _ClockedPhase(span, clock, name)
    return span


def mark(name: str, **counts: int) -> None:
    """An instant in a profiler trace: the annotation
    ``azt:<name>[k=v,k=v,…]``, opened and closed at once, the counts
    whole numbers in the event's name (a reader of the trace keeps an
    event's name and drops its other fields).  With no session
    recording nothing is formatted."""
    if not enabled():
        return
    stats = ",".join(f"{k}={int(v)}" for k, v in counts.items())
    with _annotation(f"{TRACE_PREFIX}{name}[{stats}]"):
        pass


#: seconds between two requests whose thread's CPU is clocked, for one
#: mark's name: a read is a call into the kernel that keeps the
#: interpreter lock, and a server that ends a hundred requests a
#: second must not pay four hundred of them for it
REQUEST_GAP_S = 0.1
_request_due: Dict[str, float] = {}


def request_clock(name: str) -> Optional[int]:
    """A request begins on this thread.  Its CPU clock now, to be
    taken from the reading at the request's end for the mark `name`
    (``cpu.handler``, ``cpu.client``) — or None: of the requests that
    begin within `REQUEST_GAP_S` of one that was clocked for the name,
    none is.  Asked whether or not a session records: a request may
    well begin before the session that sees it end."""
    t = now()
    if t < _request_due.get(name, 0.0):
        return None
    _request_due[name] = t + REQUEST_GAP_S
    return time.thread_time_ns()


def mark_request(name: str, cpu0: Optional[int], tokens: int) -> None:
    """The request clocked at `cpu0` ends: where a session records,
    the mark ``azt:<name>[tokens=…,cpu=…]``, the tokens it carried and
    the microseconds of this thread's CPU it took."""
    if cpu0 is not None and enabled():
        mark(name, tokens=tokens,
             cpu=(time.thread_time_ns() - cpu0) // 1000)


class LoopClock:
    """A loop thread's own CPU clock (`time.thread_time_ns`) by phase,
    read only while a profiler session records, and then in one round
    of `EVERY`: the observer must not become what is observed (a read
    is a call into the kernel, 0.3 µs on Linux and 6 µs and more in a
    sandbox that traps it, a dozen and a half a round).

    The loop calls `arm()` where a round begins and, if that said yes,
    `mark(name)` once the round's spans are closed.  The first round a
    session records is clocked, and every `EVERY`-th after it; the
    others cost `arm()` and `mark()` a comparison each, but for the one
    before a clocked round, at whose end the clock is read once: a
    clocked stretch runs from the end of the round before it to its
    own (the turn between the two is the round's), the first from its
    own beginning.  Within the stretch every `phase()` the thread opens
    or closes is a boundary: the CPU since the boundary before it goes
    to the bucket of the innermost phase open until then (the clock is
    read only where that bucket changes: a phase inside one of its own
    bucket costs no read).  `phases` names the bucket of a phase
    ``<prefix><key>`` (counts in brackets cut off); a phase in `under`
    gives its bucket to everything opened inside it; any other phase
    of the prefix, and the time no phase is open (between rounds too),
    goes to the last of `buckets`; a phase of another prefix is no
    boundary of this loop's partition and stays with the phase around
    it.  The mark carries the stretch: microseconds of wall, and
    microseconds of this thread's CPU in each bucket.

    The clock belongs to the thread: a reading is only subtracted from
    one the same thread made, so a round on another thread than the
    last starts anew, as the first round of a session does.  What the
    numbers are worth is the platform's: under gVisor the thread clock
    moves in steps of 10 ms (a sampled clock: sums over many stretches
    hold, one stretch's fields do not)."""

    #: of so many rounds one is clocked
    EVERY = 8

    def __init__(self, prefix: str, buckets: Sequence[str],
                 phases: Dict[str, str], under: Dict[str, str]):
        self.prefix = prefix
        self.buckets = tuple(buckets)
        self.phases = dict(phases)
        self.under = dict(under)
        self._rest = self.buckets[-1]
        self._whole = frozenset(self.under.values())
        self._thread: Optional[int] = None
        #: rounds to go before the next clocked one (0: this one)
        self._skip = 0
        self._stack: List[str] = []
        self._spent = dict.fromkeys(self.buckets, 0)
        self._last, self._wall = 0, 0.0

    def bucket(self, name: str, outer: Optional[str] = None) -> str:
        """Where the CPU under phase `name` goes when it is opened
        inside a phase of bucket `outer` (None: inside none)."""
        outer = self._rest if outer is None else outer
        if outer in self._whole or not name.startswith(self.prefix):
            return outer
        key = name[len(self.prefix):].partition("[")[0]
        return self.under.get(key) or self.phases.get(key, self._rest)

    def arm(self) -> bool:
        """A round begins.  Whether a profiler session records: only
        then does the round end in `mark()`, and only in a clocked
        round is a clock read at the round's phases."""
        if not enabled():
            self._thread = None
            return False
        ident = threading.get_ident()
        if ident != self._thread:
            self._thread, self._skip = ident, 0
            self._begin()
        elif not self._skip:
            self._charge(self._rest)
        if not self._skip:
            _clocked[ident] = self
        return True

    def _begin(self) -> None:
        """A clocked stretch begins here."""
        self._spent = dict.fromkeys(self.buckets, 0)
        self._last = time.thread_time_ns()
        self._wall = now()

    def _charge(self, bucket: str) -> None:
        cpu = time.thread_time_ns()
        self._spent[bucket] += cpu - self._last
        self._last = cpu

    def _open(self, name: str) -> None:
        stack = self._stack
        outer = stack[-1] if stack else self._rest
        bucket = self.bucket(name, outer)
        if bucket != outer:
            self._charge(outer)
        stack.append(bucket)

    def _close(self) -> None:
        stack = self._stack
        bucket = stack.pop()
        if bucket != (stack[-1] if stack else self._rest):
            self._charge(bucket)

    def mark(self, name: str) -> None:
        """The round armed is over.  A clocked one leaves its mark,
        ``azt:<name>[…]``, and no boundary of this thread's is the
        clock's until the next clocked round."""
        if self._skip:
            self._skip -= 1
            if not self._skip:
                self._begin()
            return
        _clocked.pop(self._thread, None)
        self._charge(self._rest)
        wall = now()
        mark(name, wall=(wall - self._wall) * 1e6,
             **{b: ns // 1000 for b, ns in self._spent.items()})
        # where every round is clocked the next stretch begins here
        self._spent = dict.fromkeys(self.buckets, 0)
        self._wall = wall
        self._skip = self.EVERY - 1


class _ClockedPhase:
    """A phase on a thread whose clock is armed: the annotation, opened
    inside the clock's boundary and closed before it."""

    __slots__ = ("_span", "_clock", "_name")

    def __init__(self, span, clock: LoopClock, name: str):
        self._span, self._clock, self._name = span, clock, name

    def __enter__(self):
        self._clock._open(self._name)
        return self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._clock._close()


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
