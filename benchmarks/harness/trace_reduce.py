"""From a profiler trace to numbers: device busy time, a program's and a
kernel's device time, the operations that took most of it, and the
longest idle gaps with what the host was doing in them.

The profiler's `.xplane.pb` holds planes (one per device, one for the
host), each with lines (on a TPU plane: "XLA Modules", one event per
execution of a compiled program; "XLA Ops", one event per operation of
it, kept under its short name (`short_op`); on the host plane one line per thread), each with events (name,
start and duration in nanoseconds on one clock).  `Trace` keeps just
that, so a small recorded cut (a JSON file of the same three levels)
reduces by the same code as a whole trace, which is what the test
checks by hand.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices used; a time is never a sum of
overlapping events."""

from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"
#: host events that say nothing about what the host was doing
HOST_NOISE = re.compile(r"^(\$|Thread|ThreadPool|tf_|process_)")


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    out: List[List[int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


#: operations that only hold others (their children are events of the
#: same line): counted as busy time, never listed as where it went
CONTAINERS = ("while", "conditional", "call")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(text: str) -> str:
    """An operation's event name — the whole HLO instruction, thousands
    of characters for a fused loop — as "<name> <opcode> <first output
    shape>", with " tpu_custom_call" behind a Mosaic kernel:
    "%copy.31 = bf16[12,2,32784,12,64]{4,3,2,1,0:T(8,128)} copy(...)"
    -> "copy.31 copy bf16[12,2,32784,12,64]".  A name that is no HLO
    instruction is kept as it is."""
    head, sep, rest = text.partition(" = ")
    if not sep or not head.startswith("%"):
        return text[:120]
    rest = rest.lstrip()
    if rest.startswith("("):            # a tuple type: skip to its end
        depth, end = 0, len(rest)
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i + 1
                break
        shape = rest[1:end - 1].split(", ")[0]
        after = rest[end:].lstrip()
    else:
        shape, _, after = rest.partition(" ")
    opcode = after.split("(")[0].strip()
    shape = _LAYOUT.sub("", shape).split(":")[0]
    out = f"{head[1:]} {opcode} {shape}"[:120]
    return out + " tpu_custom_call" if KERNEL_TARGET in text else out


def opcode(short: str) -> str:
    parts = short.split(" ")
    return parts[1] if len(parts) > 1 else ""


def program_name(event_name: str) -> str:
    """"jit_decode(1234567)" -> "jit_decode"."""
    return event_name.split("(")[0]


class Trace:
    def __init__(self, planes: Dict[str, Dict[str, List[Event]]],
                 chips: int, window_s: Optional[float] = None):
        self.planes = planes
        devices = sorted((int(m.group(1)), name) for name in planes
                         for m in [DEVICE_PLANE.match(name)] if m)
        busy = [(name, self._busy(name)) for _, name in devices]
        # the devices that ran something, at most as many as were used
        busy = [b for b in busy if b[1]][:chips] or busy[:chips]
        self.devices = [name for name, _ in busy]
        self._intervals = dict(busy)
        self.chips = chips
        spans = [iv for ivs in self._intervals.values() for iv in ivs]
        self.first_ns = min((a for a, _ in spans), default=0)
        self.last_ns = max((b for _, b in spans), default=0)
        #: the traced window: what the tracer's own clock says, else the
        #: span of the device's events
        self.window_s = (float(window_s) if window_s is not None
                         else (self.last_ns - self.first_ns) / 1e9)

    # -- reading ---------------------------------------------------------

    @classmethod
    def from_xplane(cls, path: str, chips: int,
                    window_s: Optional[float] = None) -> "Trace":
        from jax.profiler import ProfileData
        planes: Dict[str, Dict[str, List[Event]]] = {}
        for plane in ProfileData.from_file(path).planes:
            lines = planes.setdefault(plane.name, {})
            for line in plane.lines:
                name = short_op if line.name == OPS else str
                lines.setdefault(line.name, []).extend(
                    (name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
        return cls(planes, chips, window_s)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            cut = json.load(f)
        planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
                  for p, lines in cut["planes"].items()}
        return cls(planes, cut["chips"], cut.get("window_s"))

    def cut(self, start_ns: int, end_ns: int, host_lines: int = 4) -> dict:
        """The events that begin in [start_ns, end_ns): the device
        planes whole, of the host plane the busiest threads — a small
        recorded trace for the reduction's test."""
        planes = {}
        for name, lines in self.planes.items():
            kept = {l: [list(e) for e in evs if start_ns <= e[1] < end_ns]
                    for l, evs in lines.items()}
            kept = {l: evs for l, evs in kept.items() if evs}
            if not DEVICE_PLANE.match(name):
                busiest = sorted(kept, key=lambda l: -len(kept[l]))
                kept = {l: kept[l] for l in busiest[:host_lines]}
            if kept:
                planes[name] = kept
        return {"chips": self.chips, "window_s": (end_ns - start_ns) / 1e9,
                "planes": planes}

    # -- the device ------------------------------------------------------

    def _line(self, plane: str, line: str) -> List[Event]:
        return self.planes.get(plane, {}).get(line, [])

    def _busy(self, plane: str) -> List[Tuple[int, int]]:
        events = self._line(plane, OPS) or self._line(plane, MODULES)
        return union([(s, s + d) for _, s, d in events if d > 0])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        total = sum(b - a for ivs in self._intervals.values()
                    for a, b in ivs)
        return total / 1e9 / len(self.devices)

    @property
    def idle_share(self) -> Optional[float]:
        """1 - busy over the window, in %; None where nothing ran."""
        if not self.busy_s or not self.window_s:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def _total(self, line: str, match) -> Tuple[int, float]:
        """(events, device seconds) a chip of `line`'s events whose name
        `match` accepts."""
        durations = [dur for plane in self.devices
                     for name, _, dur in self._line(plane, line)
                     if match(name)]
        k = max(len(self.devices), 1)
        return len(durations) // k, sum(durations) / 1e9 / k

    def program(self, name: str) -> Tuple[int, float]:
        """(executions, device seconds a chip) of the compiled program
        `name` ("jit_decode"), from the modules line."""
        return self._total(MODULES, lambda ev: program_name(ev) == name)

    def ops(self, pattern: str) -> Tuple[int, float]:
        """(events, device seconds a chip) of the operations whose short
        name matches `pattern`, from the ops line."""
        return self._total(OPS, re.compile(pattern).search)

    # -- where the time goes ---------------------------------------------

    def device_ops(self, top: int = 10) -> List[List]:
        """The operations that took most device time on the first
        device, by their short names; a loop is not listed, what runs
        inside it is."""
        total: Dict[str, int] = defaultdict(int)
        for plane in self.devices[:1]:
            for name, _, dur in self._line(plane, OPS):
                if opcode(name) not in CONTAINERS:
                    total[name] += dur
        names = sorted(total, key=total.get, reverse=True)[:top]
        return [[n, total[n] / 1e9] for n in names]

    def idle_gaps(self, top: int = 10, longest: int = 400) -> List[List]:
        """The first device's longest idle gaps, summed by what the host
        was doing in them: the host event that covers most of the gap
        (the innermost where several do), `unattributed` where no host
        line shows anything."""
        if not self.devices:
            return []
        ivs = self._intervals[self.devices[0]]
        gaps = sorted(((b2 - a1, a1, b2) for (_, a1), (b2, _)
                       in zip(ivs, ivs[1:])), reverse=True)[:longest]
        host = [(n, s, s + d) for plane, lines in self.planes.items()
                if not DEVICE_PLANE.match(plane)
                for evs in lines.values() for n, s, d in evs
                if d > 0 and not HOST_NOISE.match(n)]
        host.sort(key=lambda e: e[1])
        starts = [e[1] for e in host]
        total: Dict[str, int] = defaultdict(int)
        for length, a, b in gaps:
            best, cover, width = "unattributed", 0, None
            hi = bisect.bisect_right(starts, b)
            for n, s, e in host[max(0, hi - 2000):hi]:
                c = min(e, b) - max(s, a)
                if c <= 0:
                    continue
                # most of the gap; the shorter event where two tie
                if c > cover or (c == cover and e - s < width):
                    best, cover, width = n, c, e - s
            total[best] += length
        names = sorted(total, key=total.get, reverse=True)[:top]
        return [[n, total[n] / 1e9] for n in names]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}


def reduce_file(path: Optional[str], chips: int,
                window_s: Optional[float] = None) -> Trace:
    if path is None:
        raise RuntimeError("the profiler left no trace to reduce")
    return Trace.from_xplane(path, chips, window_s)
