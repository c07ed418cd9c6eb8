"""Tier-1 wiring for scripts/check_no_ad_hoc_timers.py: the build goes
red if a new `perf_counter` stopwatch appears in the package outside
analytics_zoo_tpu/observability/ (tests are exempt)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "scripts", "check_no_ad_hoc_timers.py")


def test_no_ad_hoc_timers():
    proc = subprocess.run([sys.executable, SCRIPT],
                          capture_output=True, text=True)
    assert proc.returncode == 0, (
        "ad-hoc perf_counter call sites crept in:\n" + proc.stderr)


def test_lint_detects_violation():
    """Guard against the checker silently scanning the wrong tree: the
    live tree is clean AND the pattern matches the forbidden idioms."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("azt_timer_lint",
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the live tree is clean ...
    assert mod.find_violations() == []
    # ... and the pattern really matches the forbidden idioms
    assert mod.PATTERN.search("t0 = time.perf_counter()")
    assert mod.PATTERN.search("from time import perf_counter")
    assert not mod.PATTERN.search("t0 = observability.now()")
