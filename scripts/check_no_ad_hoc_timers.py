#!/usr/bin/env python
"""Lint: no ad-hoc stopwatches outside the observability layer.

The unified observability layer (analytics_zoo_tpu/observability/) owns
the instrumentation clock (`observability.now`), the metric histograms,
and span timing.  Before it existed, the repo grew three divergent
timing implementations; this check keeps a fourth from sprouting: any
`perf_counter` reference inside the `analytics_zoo_tpu` package outside
`observability/registry.py` — the single module that DEFINES the
sanctioned clock — fails the build (use `observability.now`, a
registry `Histogram.time()`, a `Timer.timing(...)` block, or a
`trace(...)` span instead).  Since the goodput/flight-recorder/
watchdog modules landed, the rest of `observability/` is held to the
same rule as everyone else.  `tests/` are exempt —
external stopwatches measuring the system from outside are the point
there.

Run directly (`python scripts/check_no_ad_hoc_timers.py`) or via the
tier-1 wrapper `tests/test_no_ad_hoc_timers.py`.  Exit code 0 = clean.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "analytics_zoo_tpu")
#: the ONE file allowed to touch the raw clock: it defines
#: `observability.now` for everyone else (including the other
#: observability modules — goodput, watchdog, flight recorder)
ALLOWED_FILE = os.path.join(PACKAGE, "observability", "registry.py")

#: matches both `time.perf_counter()` and a bare `perf_counter` import
PATTERN = re.compile(r"perf_counter")


def find_violations():
    violations = []
    for dirpath, _dirnames, filenames in os.walk(PACKAGE):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            if path == ALLOWED_FILE:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if PATTERN.search(line):
                        violations.append(
                            (os.path.relpath(path, REPO), lineno,
                             line.rstrip()))
    return violations


def main() -> int:
    violations = find_violations()
    if not violations:
        print("check_no_ad_hoc_timers: clean")
        return 0
    print("check_no_ad_hoc_timers: ad-hoc perf_counter call sites "
          "outside analytics_zoo_tpu/observability/ (use "
          "observability.now / Histogram.time / Timer.timing / trace):",
          file=sys.stderr)
    for path, lineno, line in violations:
        print(f"  {path}:{lineno}: {line}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
