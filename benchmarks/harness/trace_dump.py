"""Look at a trace by hand: planes, lines, the names that take the
time, and the first events of each line.

    python benchmarks/harness/trace_dump.py <file.xplane.pb> [out.json]
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict


def summarise(path: str, top: int = 25, first: int = 12) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            total = defaultdict(float)
            count = defaultdict(int)
            head, n, t_min, t_max = [], 0, None, None
            for ev in line.events:
                n += 1
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                t_min = ev.start_ns if t_min is None else min(t_min,
                                                              ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                t_max = end if t_max is None else max(t_max, end)
                if len(head) < first:
                    head.append([ev.name, ev.start_ns, ev.duration_ns,
                                 {k: str(v)[:80] for k, v in ev.stats}])
            names = sorted(total, key=total.get, reverse=True)[:top]
            lines[line.name] = dict(
                events=n, span_ns=[t_min, t_max],
                top=[[k, count[k], total[k]] for k in names], first=head)
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    summary = summarise(sys.argv[1])
    text = json.dumps(summary, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
