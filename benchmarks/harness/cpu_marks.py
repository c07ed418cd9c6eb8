"""The program's CPU marks in a profiler trace: whose turn it was on
the interpreter lock.

While a profiler session records, the program reads what nothing
outside it can, a thread's own CPU clock, and leaves the sums in the
trace as instant host events (`observability/tracing.py`: `mark`,
`LoopClock`), the numbers in the event's name as the decode span's
counts are:

``azt:cpu.loop[wall=..,schedule=..,prefill_host=..,dispatch=..,fetch=..,
account=..,emit=..,off_round=..]``, behind one ``azt:generation.round``
of every few (the program reads its clock in no other: the reads are
not free): microseconds of wall from the end of the round before that
one to the mark, and microseconds of the loop thread's CPU in that
stretch by the phase that was innermost (the seven names and the rule
of `serve_idle.*`).  The first mark of a session covers its own round
alone.  A mark's stretch is its start less its `wall` to its start;
the stretches may lie apart or end to end.

``azt:cpu.handler[tokens=..,cpu=..]`` and ``azt:cpu.client[..]``, one a
request that ended in the session: the tokens it carried and the
microseconds of CPU its handler (its streaming client) thread took
from its start to its end.

The wall of a phase comes from the spans that were there before the
marks (`span_metrics.phase_segments`), cut to the marks' stretches;
the CPU from the marks.  Wall less CPU, over the phases in which the
loop does not wait for the device's results or for work, is the time
it stood runnable without the lock or without a core, or blocked in
an enqueue on a device that was a round behind.

A trace with no such mark — a program from before them — reads None
everywhere."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmarks.harness.span_metrics import (
    PHASES,
    PREFIX,
    engine_spans,
    host_events,
    phase_segments,
)

MARKS = PREFIX + "cpu."
FIELDS = re.compile(r"\[([^\]]*)\]$")
CPU_PHASES = PHASES + ("off_round",)
#: the phases in which wall less CPU is a wait for the lock or a core
#: (or, on a device that is behind, for room in its queue): not
#: `fetch`, which waits for the device's results, nor `off_round`,
#: which sleeps and waits for work
RUNNABLE = tuple(p for p in PHASES if p != "fetch")


def marks(trace, kind: str) -> List[Tuple[int, Dict[str, int]]]:
    """(start ns, fields) of the ``azt:cpu.<kind>[...]`` events, by
    start."""
    out = []
    for name, start, _ in host_events(trace, MARKS + kind + "["):
        found = FIELDS.search(name)
        if found:
            out.append((start, {k: int(v) for k, v in (
                pair.split("=") for pair in found.group(1).split(",")
                if pair)}))
    return out


def per_token(trace, kind: str) -> Optional[float]:
    """Microseconds of CPU a token over the ``cpu.<kind>`` marks."""
    fields = [f for _, f in marks(trace, kind)]
    tokens = sum(f["tokens"] for f in fields)
    return sum(f["cpu"] for f in fields) / tokens if tokens else None


def loop(trace) -> Optional[Dict[str, float]]:
    """The loop thread's clocked round from its marks, in milliseconds
    a round: `round` (wall), `cpu.<phase>` for the seven phases,
    `wall.<phase>` for the six that have spans, and `off_cpu` (the
    runnable phases' wall from their spans less their CPU)."""
    found = marks(trace, "loop")
    if not found:
        return None
    n = len(found)
    out = {"rounds": float(n),
           "round": sum(f["wall"] for _, f in found) / 1e3 / n}
    for phase in CPU_PHASES:
        out["cpu." + phase] = sum(f[phase] for _, f in found) / 1e3 / n
    stretches = [(at - f["wall"] * 1000, at) for at, f in found]
    wall = dict.fromkeys(PHASES, 0)
    for start, end, phase in phase_segments(engine_spans(trace)):
        wall[phase] += sum(max(0, min(end, b) - max(start, a))
                           for a, b in stretches)
    for phase in PHASES:
        out["wall." + phase] = wall[phase] / 1e6 / n
    out["off_cpu"] = sum(out["wall." + p] - out["cpu." + p]
                         for p in RUNNABLE)
    return out


def reduced(ctx: Dict) -> Dict[str, Optional[float]]:
    """The eleven metrics' reduction, once a run: eleven readers."""
    if "cpu_marks" not in ctx:
        trace = ctx["trace"]
        ctx["cpu_marks"] = dict(
            loop(trace) or {},
            handler_us_per_token=per_token(trace, "handler"),
            client_us_per_token=per_token(trace, "client"))
    return ctx["cpu_marks"]


def read(ctx: Dict, key: str) -> Optional[float]:
    return reduced(ctx).get(key)
