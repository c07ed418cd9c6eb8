"""The program's own spans in a profiler trace, and the device's idle
time put down to them.

`analytics_zoo_tpu.observability.tracing` writes every span into the
profiler's trace as a host event named ``azt:<name>``, on the clock of
the device's events.  The generation engine's loop is one thread, so
its spans (``azt:generation.*``) nest; every Python thread's line has
one name and `Trace.from_xplane` merges them, so the loop's spans are
told from the handlers' (``azt:serving.*``) by name.

At each instant the loop is in one phase: the innermost open span.
The first device's idle intervals (the complement of the union of its
operations' intervals, between its first and its last) are intersected
with the phases exactly — a gap that lies under three phases is cut
into three, never given whole to the one that covers most of it.

A program without the spans, or a trace in which no device ran
anything, reads None everywhere: a share is never 0 for want of
events."""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmarks.harness.trace_reduce import DEVICE_PLANE, Trace

PREFIX = "azt:"
ENGINE = PREFIX + "generation."
DECODE = re.compile(r"^azt:generation\.decode\[l=(\d+),w=(\d+)\]$")

#: the innermost open span -> the metric `serve_idle.<phase>` it is
#: charged to.  Anything under a prefill is prefill's; a decode round's
#: own uncovered microseconds (the step record's begin, the fault
#: point) go with its dispatch; `wait` and `housekeeping` lie outside
#: any round and are left to `off_round`
LEAF_PHASE = {"round": "schedule", "admit": "schedule",
              "capacity": "schedule", "stage": "dispatch",
              "dispatch": "dispatch", "decode": "dispatch",
              "spec_verify": "dispatch", "fetch": "fetch",
              "account": "account", "emit": "emit"}
PHASES = ("schedule", "prefill_host", "dispatch", "fetch", "account",
          "emit")


class Span(NamedTuple):
    name: str                 # without the prefix and the counts
    start: int                # ns
    end: int
    parents: Tuple[str, ...]  # outermost first


def short(name: str) -> str:
    """"azt:generation.decode[l=32,w=0]" -> "decode"."""
    return name[len(ENGINE):].split("[")[0]


def host_events(trace: Trace, prefix: str) -> List[Tuple[str, int, int]]:
    """(name, start, end) of the host events whose name starts with
    `prefix`, by start, the longer first where two start together."""
    found = [(n, s, s + d) for plane, lines in trace.planes.items()
             if not DEVICE_PLANE.match(plane)
             for events in lines.values() for n, s, d in events
             if n.startswith(prefix)]
    return sorted(found, key=lambda e: (e[1], -e[2]))


def engine_spans(trace: Trace) -> List[Span]:
    """The engine loop's spans, each with the spans open around it."""
    out, stack = [], []
    for name, start, end in host_events(trace, ENGINE):
        while stack and stack[-1].end <= start:
            stack.pop()
        if stack:               # a child ends with its parent at most
            end = min(end, stack[-1].end)
        span = Span(short(name), start, end,
                    tuple(s.name for s in stack))
        out.append(span)
        stack.append(span)
    return out


def phase_of(span: Span) -> Optional[str]:
    if "prefill" in span.parents or span.name == "prefill":
        return "prefill_host"
    return LEAF_PHASE.get(span.name)


def phase_segments(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """[start, end) pieces of the loop's time with the phase of the
    innermost span open in each; pieces in no phase are left out."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    cursor = 0

    def emit(until: int) -> None:
        phase = phase_of(stack[-1])
        if phase is not None and until > cursor:
            out.append((cursor, until, phase))

    for span in spans + [Span("", 1 << 62, 1 << 62, ())]:
        while stack and stack[-1].end <= span.start:
            emit(stack[-1].end)
            cursor = max(cursor, stack.pop().end)
        if stack:
            emit(span.start)
        cursor = span.start
        stack.append(span)
    return out


def idle_intervals(trace: Trace) -> List[Tuple[int, int]]:
    """The first device's gaps between its operations."""
    if not trace.devices:
        return []
    busy = trace._intervals[trace.devices[0]]   # what `busy_s` sums
    return [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])]


def overlap_by_phase(gaps: List[Tuple[int, int]],
                     segments: List[Tuple[int, int, str]]
                     ) -> Dict[str, int]:
    """Nanoseconds of `gaps` under each phase; both lists are sorted
    and neither overlaps itself."""
    total = dict.fromkeys(PHASES, 0)
    i = 0
    for start, end, phase in segments:
        while i < len(gaps) and gaps[i][1] <= start:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < end:
            total[phase] += min(end, gaps[j][1]) - max(start, gaps[j][0])
            j += 1
    return total


def serve_idle(ctx: Dict) -> Optional[Dict[str, float]]:
    """The seven shares of the traced window, in %: the device's idle
    time under each phase of the engine's loop, and `off_round`, the
    rest of `device_idle_share.serve` (no work, housekeeping, the head
    and the tail of the trace).  They add up to that share."""
    trace = ctx["trace"]
    if "serve_idle" not in ctx:          # seven readers, one reduction
        spans = engine_spans(trace)
        idle = trace.idle_share
        if not spans or idle is None:
            ctx["serve_idle"] = None
        else:
            ns = overlap_by_phase(idle_intervals(trace),
                                  phase_segments(spans))
            shares = {p: 100.0 * v / 1e9 / trace.window_s
                      for p, v in ns.items()}
            shares["off_round"] = idle - sum(shares.values())
            ctx["serve_idle"] = shares
    return ctx["serve_idle"]


def serve_idle_share(ctx: Dict, phase: str) -> Optional[float]:
    shares = serve_idle(ctx)
    return None if shares is None else shares[phase]


def decode_counts(ctx: Dict, which: int) -> Optional[float]:
    """The mean of a count that rides in the decode spans' names:
    0 the lanes in the dispatch, 1 the requests waiting at its start."""
    found = [DECODE.match(n) for n, _, _ in host_events(ctx["trace"],
                                                         ENGINE + "decode[")]
    values = [int(m.group(which + 1)) for m in found if m]
    return sum(values) / len(values) if values else None


def prefill_time_share(ctx: Dict) -> Optional[float]:
    """Wall of the prefill spans over wall of the rounds they are in,
    in %."""
    spans = engine_spans(ctx["trace"])
    rounds = sum(s.end - s.start for s in spans if s.name == "round")
    prefill = sum(s.end - s.start for s in spans
                  if s.name == "prefill" and "round" in s.parents)
    return 100.0 * prefill / rounds if rounds else None


def train_input_wait_ms(ctx: Dict) -> Optional[float]:
    """Host time in `spmd.input_wait` a traced step: the loop waiting
    for its next batch (the pop that finds the epoch at its end is in
    the sum and is no step)."""
    spans = host_events(ctx["trace"], PREFIX + "spmd.")
    steps = sum(n == PREFIX + "spmd.step" for n, _, _ in spans)
    waits = [e - s for n, s, e in spans if n == PREFIX + "spmd.input_wait"]
    if not steps or not waits:
        return None
    return sum(waits) / 1e6 / steps
