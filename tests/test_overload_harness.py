"""Open-loop overload harness unit tests (docs/streaming.md "Overload
harness"): seeded arrival traces are deterministic and statistically
sane, and `run_open_loop` classifies/score outcomes correctly against
a synthetic submit function — the full against-a-live-server run is
slow and not tier-1."""

import numpy as np
import pytest

from analytics_zoo_tpu.serving.streaming import (bursty_trace,
                                                 poisson_trace,
                                                 run_open_loop)


def test_poisson_trace_deterministic_and_calibrated():
    a = poisson_trace(200.0, 5.0, seed=42)
    b = poisson_trace(200.0, 5.0, seed=42)
    assert a == b                       # same seed, same trace
    assert a != poisson_trace(200.0, 5.0, seed=43)
    assert all(0 <= t < 5.0 for t in a)
    assert a == sorted(a)
    # mean rate within 10% of nominal at ~1000 arrivals
    assert len(a) == pytest.approx(1000, rel=0.1)
    gaps = np.diff([0.0] + a)
    assert float(np.mean(gaps)) == pytest.approx(1 / 200.0, rel=0.1)


def test_bursty_trace_deterministic_and_burstier():
    a = bursty_trace(200.0, 5.0, seed=7, burstiness=4.0)
    assert a == bursty_trace(200.0, 5.0, seed=7, burstiness=4.0)
    assert a == sorted(a) and all(0 <= t < 5.0 for t in a)
    # any ONE seed's count swings wildly (that is the burstiness);
    # the mean over seeds still tracks the nominal rate
    mean = np.mean([len(bursty_trace(200.0, 5.0, seed=s,
                                     burstiness=4.0))
                    for s in range(12)])
    assert mean == pytest.approx(1000, rel=0.2)
    # per-window counts vary far more than Poisson's (the point of the
    # Gamma modulation): compare coefficient of variation of 0.5 s
    # window counts
    def cv(trace):
        counts = np.histogram(trace, bins=10, range=(0, 5.0))[0]
        return float(np.std(counts) / max(np.mean(counts), 1e-9))

    assert cv(a) > 2 * cv(poisson_trace(200.0, 5.0, seed=7))
    with pytest.raises(ValueError):
        bursty_trace(10.0, 1.0, burstiness=0.0)


def test_empty_and_degenerate_traces():
    assert poisson_trace(0.0, 5.0) == []
    assert poisson_trace(10.0, 0.0) == []
    assert bursty_trace(0.0, 5.0) == []
    rep = run_open_loop(lambda i: {"status": "ok"}, [], slo_s=1.0)
    assert rep["offered"] == 0 and rep["attainment_admitted"] == 1.0


def test_run_open_loop_classifies_and_scores():
    """Synthetic stack: every 3rd request shed (with Retry-After),
    every 7th errors, the rest admitted — half in SLO."""
    slow = set(range(0, 100, 2))

    def submit(i):
        if i % 3 == 0:
            return {"status": "shed", "retry_after": True,
                    "e2e_s": 0.001}
        if i % 7 == 0:
            raise RuntimeError("replica died")
        return {"status": "ok", "e2e_s": 0.5 if i in slow else 0.01}

    arrivals = [i * 1e-4 for i in range(100)]
    rep = run_open_loop(submit, arrivals, slo_s=0.1, max_workers=32)
    shed = {i for i in range(100) if i % 3 == 0}
    errs = {i for i in range(100) if i % 7 == 0} - shed
    ok = set(range(100)) - shed - errs
    assert rep["offered"] == 100
    assert rep["shed"] == len(shed)
    assert rep["shed_with_retry_after"] == len(shed)
    assert rep["shed_rate"] == pytest.approx(len(shed) / 100)
    assert rep["admitted"] == len(ok) + len(errs)
    assert rep["completed_ok"] == len(ok)
    # sheds come back promptly — time-to-shed is the injected 1 ms
    assert rep["time_to_shed_p50_s"] == pytest.approx(0.001)
    in_slo = sum(1 for i in ok if i not in slow)
    assert rep["attainment_admitted"] == pytest.approx(
        in_slo / rep["admitted"])
    # per-request results pass through (errors carry the message)
    bad = [r for r in rep["results"] if r["status"] == "error"]
    assert len(bad) == len(errs)
    assert all("replica died" in r["error"] for r in bad)


def test_run_open_loop_is_open_loop():
    """A stalled server must not throttle later arrivals: 20 arrivals
    in 0.2 s against a 0.25 s-per-request submit still all fire, and
    scheduling fidelity is reported."""
    import threading
    import time

    fired = []
    lock = threading.Lock()

    def submit(i):
        with lock:
            fired.append((i, time.monotonic()))
        time.sleep(0.25)
        return {"status": "ok"}

    arrivals = [i * 0.01 for i in range(20)]
    t0 = time.monotonic()
    rep = run_open_loop(submit, arrivals, slo_s=10.0, max_workers=32)
    assert rep["offered"] == rep["admitted"] == 20
    # closed-loop would take 20 x 0.25 = 5 s; open-loop overlaps
    assert time.monotonic() - t0 < 2.5
    assert rep["start_lag_p99_s"] < 0.5
