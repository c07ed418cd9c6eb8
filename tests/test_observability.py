"""Unified observability layer: metrics registry + Prometheus
exposition, span tracing (incl. the cross-thread batcher hop), the
serving /metrics, /spans and /stats endpoints end-to-end, estimator
epoch/step spans, and the JSONL structured-event sink."""

import json
import threading
from urllib.request import urlopen

import numpy as np
import pytest

from analytics_zoo_tpu.observability import (
    Histogram,
    MetricsRegistry,
    clear_spans,
    current_span,
    log_event,
    parse_prometheus_text,
    recent_spans,
    trace,
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("req_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    live = reg.gauge("live_depth", fn=lambda: 42)
    assert live.value == 42
    h = reg.histogram("lat_seconds")
    h.record(0.5, count=10)
    assert h.calls == 1 and h.records == 10
    # get-or-create: same name -> same instance; type clash raises
    assert reg.counter("req_total") is c
    with pytest.raises(TypeError):
        reg.gauge("req_total")


def test_histogram_nearest_rank_pinned():
    """Regression for the Timer percentile semantics: nearest-rank
    (ceil(p*n)-1) on a known 10-sample reservoir, plus the empty and
    single-sample edge cases."""
    h = Histogram("h")
    for ms in range(1, 11):                   # 1..10 ms
        h.record(ms / 1e3)
    assert h.quantile(0.50) == pytest.approx(5e-3)   # 5th of 10
    assert h.quantile(0.90) == pytest.approx(9e-3)   # 9th, not the max
    assert h.quantile(0.99) == pytest.approx(10e-3)
    row = h.summary_row()
    assert (row["p50_ms"], row["p90_ms"], row["p99_ms"],
            row["max_ms"]) == (5.0, 9.0, 10.0, 10.0)
    # empty reservoir: quantiles are 0.0, not an exception
    empty = Histogram("e")
    assert empty.quantile(0.5) == 0.0
    r = empty.summary_row()
    assert r["calls"] == 0 and r["p99_ms"] == 0.0
    assert r["records_per_s"] == 0.0
    # single sample: every percentile is that sample
    one = Histogram("o")
    one.record(7e-3)
    assert one.quantile(0.5) == one.quantile(0.99) == \
        pytest.approx(7e-3)


def test_timer_adapter_pinned_percentiles_and_stable_order():
    """serving.timer.Timer stays API-compatible over the registry:
    same nearest-rank numbers, stable (sorted) summary key order."""
    from analytics_zoo_tpu.serving.timer import Timer
    t = Timer()
    for name in ("zeta", "alpha", "mid"):     # insertion != sorted
        for ms in range(1, 11):
            t.record(name, ms / 1e3)
    s = t.summary()
    assert list(s) == ["alpha", "mid", "zeta"]
    assert s["alpha"]["p50_ms"] == 5.0
    assert s["alpha"]["p90_ms"] == 9.0
    assert s["alpha"]["p99_ms"] == 10.0
    assert s["alpha"]["max_ms"] == 10.0
    assert s["alpha"]["calls"] == 10
    # two Timers over private registries do not bleed into each other
    t2 = Timer()
    t2.record("alpha", 1.0)
    assert t2.summary()["alpha"]["calls"] == 1
    assert t.summary()["alpha"]["calls"] == 10


def test_timer_timing_context_manager():
    from analytics_zoo_tpu.serving.timer import Timer
    t = Timer()
    with t.timing("op", count=3):
        pass
    row = t.summary()["op"]
    assert row["calls"] == 1 and row["records"] == 3
    assert row["max_ms"] >= 0


def test_histogram_time_records_on_exception():
    """Regression (PR 4 satellite): a raising body must still
    contribute its elapsed time — a table that silently dropped every
    failing step would overstate health."""
    h = Histogram("h")
    with pytest.raises(RuntimeError):
        with h.time():
            raise RuntimeError("body died")
    assert h.calls == 1 and h.records == 1
    assert h.total > 0
    # and the exception itself propagated untouched (not swallowed)
    with h.time():
        pass
    assert h.calls == 2


def test_gauge_min_max_tracking():
    """Written gauges track the extremes ever observed (what the
    goodput tables use for best/worst step); callback gauges do not
    (their reads are not observed)."""
    reg = MetricsRegistry()
    g = reg.gauge("step_s")
    import math
    assert math.isnan(g.min) and math.isnan(g.max)   # before any write
    g.set(3.0)
    g.set(0.5)
    g.set(9.0)
    g.inc(1.0)           # 10.0
    g.dec(4.0)           # 6.0
    assert g.min == 0.5
    assert g.max == 10.0
    assert g.value == 6.0
    live = reg.gauge("cb", fn=lambda: 42)
    assert live.value == 42
    assert math.isnan(live.min) and math.isnan(live.max)


def test_step_clock_partition_invariant():
    """Fenced bucket totals sum to the fenced wall by construction —
    the invariant the goodput tables rest on."""
    from analytics_zoo_tpu.observability.goodput import StepClock
    clock = StepClock("unit_clock", registry=MetricsRegistry())
    for fence in (True, True, False):
        rec = clock.begin(force_fence=fence)
        rec.lap("host_input")
        rec.lap(None)
        if rec.fenced:
            rec.lap("device_compute")
        rec.end()
    t = clock.table()
    assert t["fenced_steps"] == 2
    # the exact partition lives on the unrounded clock state; the
    # table's values are rounded to 1e-6 s, so its sum only matches to
    # rounding granularity (these steps are only microseconds long)
    assert sum(clock.buckets.values()) == pytest.approx(
        clock.fenced_wall_s, rel=1e-9, abs=1e-12)
    assert sum(t["buckets_s"].values()) == pytest.approx(
        t["fenced_wall_s"], abs=len(t["buckets_s"]) * 1e-6)
    # a cold step's device wait folds into the compile bucket
    rec = clock.begin(force_fence=True)
    rec.cold = True
    rec.lap("device_compute")
    rec.end()
    assert clock.buckets["compile"] > 0


def test_prometheus_text_roundtrip():
    reg = MetricsRegistry()
    reg.counter("requests_total", help="reqs").inc(7)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("predict_seconds")
    for ms in range(1, 11):
        h.record(ms / 1e3, count=2)
    text = reg.prometheus_text()
    assert "# TYPE requests_total counter" in text
    assert 'predict_seconds{quantile="0.5"} 0.005' in text
    assert "predict_seconds_count 10" in text
    assert "predict_seconds_records 20" in text
    parsed = parse_prometheus_text(text)
    assert parsed["requests_total"]["value"] == 7
    assert parsed["queue_depth"]["value"] == 3
    assert parsed["predict_seconds"]["quantiles"][0.5] == \
        pytest.approx(5e-3)
    assert parsed["predict_seconds"]["count"] == 10


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_nested_spans_same_thread():
    clear_spans()
    with trace("outer", kind="t") as outer:
        assert current_span() is outer
        with trace("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
    assert current_span() is None
    spans = recent_spans(2)
    names = {s["name"] for s in spans}
    assert names == {"outer", "inner"}
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["outer"]["attrs"]["kind"] == "t"
    assert by_name["outer"]["duration_s"] >= 0


def test_span_error_recorded():
    clear_spans()
    with pytest.raises(RuntimeError):
        with trace("boom"):
            raise RuntimeError("nope")
    (span,) = recent_spans(1)
    assert "RuntimeError" in span["error"]


def test_cross_thread_parent_explicit():
    """contextvars do not flow into a pre-existing worker thread; the
    handoff is capture-current + explicit parent= (what the serving
    batcher does)."""
    clear_spans()
    seen = {}

    def worker(parent):
        # the contextvar did NOT follow us here
        seen["inherited"] = current_span()
        with trace("child_in_thread", parent=parent) as ch:
            seen["child"] = ch

    with trace("request") as req:
        t = threading.Thread(target=worker, args=(req,))
        t.start()
        t.join()
    assert seen["inherited"] is None
    assert seen["child"].parent_id == req.span_id
    assert seen["child"].trace_id == req.trace_id
    assert seen["child"].thread != req.thread


# ---------------------------------------------------------------------------
# JSONL structured-event sink
# ---------------------------------------------------------------------------

def test_log_event_jsonl_sink(tmp_path):
    from analytics_zoo_tpu.common.context import OrcaContext
    from analytics_zoo_tpu.observability import close_sink, get_registry
    before = get_registry().counter("events_total").value
    OrcaContext.observability_dir = str(tmp_path / "obs")
    try:
        log_event("unit_test", answer=42, arr=np.float32(1.5))
        with trace("sinked_span"):
            pass
        close_sink()
        lines = [json.loads(x) for x in
                 (tmp_path / "obs" / "events.jsonl").read_text()
                 .splitlines()]
    finally:
        OrcaContext.observability_dir = None
        close_sink()
    kinds = [r["kind"] for r in lines]
    assert "unit_test" in kinds and "span" in kinds
    ev = next(r for r in lines if r["kind"] == "unit_test")
    assert ev["answer"] == 42 and ev["arr"] == 1.5 and "ts" in ev
    sp = next(r for r in lines if r["kind"] == "span")
    assert sp["name"] == "sinked_span"
    assert get_registry().counter("events_total").value > before
    # no sink configured -> still counted, nothing written
    log_event("unsinked")
    assert not (tmp_path / "unsinked").exists()


# ---------------------------------------------------------------------------
# estimator + engine spans
# ---------------------------------------------------------------------------

def test_estimator_fit_emits_epoch_and_step_spans():
    import flax.linen as nn

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.orca.learn import Estimator

    init_orca_context(cluster_mode="local")

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    est = Estimator.from_flax(Tiny(), loss="mse", optimizer="sgd",
                              learning_rate=1e-2)
    clear_spans()
    est.fit({"x": x, "y": y}, epochs=2, batch_size=8)
    spans = recent_spans(500)
    fit = [s for s in spans if s["name"] == "estimator.fit"]
    epochs = [s for s in spans if s["name"] == "estimator.epoch"]
    steps = [s for s in spans if s["name"] == "spmd.step"]
    assert len(fit) == 1 and fit[0]["attrs"]["epochs"] == 2
    assert len(epochs) == 2
    # epoch spans are children of the fit span; step spans are
    # children of an epoch span (contextvar propagation on one thread)
    assert all(e["parent_id"] == fit[0]["span_id"] for e in epochs)
    epoch_ids = {e["span_id"] for e in epochs}
    assert steps and all(s["parent_id"] in epoch_ids for s in steps)
    # 32 rows / batch 8 = 4 steps/epoch, 2 epochs, monotonically
    # increasing global step attrs
    train_steps = [s["attrs"]["step"] for s in steps
                   if s["attrs"].get("train")]
    train_steps.reverse()                      # recent_spans is newest-first
    assert train_steps == list(range(1, 9))
    # the first dispatch is flagged as the compiling one
    cold = [s for s in steps if s["attrs"].get("jit_cold")]
    assert len(cold) == 1 and cold[0]["attrs"]["step"] == 1


def test_device_put_bytes_counted():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.observability import get_registry
    from analytics_zoo_tpu.parallel.sharding import shard_batch

    init_orca_context(cluster_mode="local")
    c = get_registry().counter("jax_device_put_bytes_total")
    before = c.value
    batch = {"features": (np.zeros((8, 4), np.float32),),
             "labels": (), "mask": np.ones(8, np.float32)}
    shard_batch(batch)
    assert c.value >= before + 8 * 4 * 4 + 8 * 4


# ---------------------------------------------------------------------------
# serving endpoints end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_server():
    import flax.linen as nn
    import jax

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.serving import InferenceModel, ServingServer

    init_orca_context(cluster_mode="local")

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    m = M()
    x = np.ones((1, 8), np.float32)
    params = jax.device_get(m.init(jax.random.PRNGKey(0), x))["params"]
    im = InferenceModel().load_flax(m, params)
    srv = ServingServer(im, port=0, max_batch_size=8,
                        batch_timeout_ms=3).start()
    yield srv
    srv.stop()


def _get(srv, path):
    return urlopen(f"http://{srv.host}:{srv.port}{path}",
                   timeout=30).read().decode()


def test_metrics_endpoint_prometheus_e2e(obs_server):
    from analytics_zoo_tpu.serving import InputQueue
    x = np.ones((4, 8), np.float32)
    InputQueue(obs_server.host, obs_server.port).predict(x, batched=True)
    text = _get(obs_server, "/metrics")
    parsed = parse_prometheus_text(text)
    # per-op latency summaries with quantiles (the regime decomposition)
    for op in ("serving_queue_wait_seconds", "serving_predict_seconds",
               "serving_batch_assemble_seconds"):
        assert parsed[op]["type"] == "summary"
        assert 0.5 in parsed[op]["quantiles"]
        assert parsed[op]["count"] >= 1
    # counters + live gauges
    assert parsed["serving_requests_total"]["value"] >= 1
    assert parsed["serving_records_served_total"]["value"] >= 4
    assert parsed["serving_batches_total"]["value"] >= 1
    assert parsed["serving_queue_depth"]["type"] == "gauge"
    assert parsed["serving_replicas"]["value"] == 1
    # process-global registry is merged into the same exposition
    # (span histograms from this process's other subsystems)
    assert any(k.startswith("span_") for k in parsed)


def test_stats_endpoint_json(obs_server):
    from analytics_zoo_tpu.serving import InputQueue
    x = np.ones((4, 8), np.float32)
    InputQueue(obs_server.host, obs_server.port).predict(x, batched=True)
    stats = json.loads(_get(obs_server, "/stats"))
    assert stats["records_served"] >= 4
    assert stats["batches_run"] >= 1
    assert stats["queue_depth"] >= 0
    assert stats["replicas"] == 1
    t = stats["timers"]
    assert t["predict"]["calls"] >= 1
    assert t["predict"]["records"] >= 4
    assert t["predict"]["p50_ms"] >= 0
    assert list(t) == sorted(t)


def test_spans_endpoint_and_cross_thread_batch_parent(obs_server):
    from analytics_zoo_tpu.serving import InputQueue
    clear_spans()
    x = np.ones((2, 8), np.float32)
    InputQueue(obs_server.host, obs_server.port).predict(x, batched=True)
    payload = json.loads(_get(obs_server, "/spans?n=50"))
    spans = payload["spans"]
    req = [s for s in spans if s["name"] == "serving.http_request"]
    runs = [s for s in spans if s["name"] == "serving.run_batch"]
    assert req and runs
    # the batch ran on the batcher thread but links to the HTTP
    # handler thread's request span (explicit cross-thread parent)
    run = runs[0]
    parents = {s["span_id"]: s for s in req}
    assert run["parent_id"] in parents
    assert run["thread"] != parents[run["parent_id"]]["thread"]
    assert run["trace_id"] == parents[run["parent_id"]]["trace_id"]
    assert run["attrs"]["records"] >= 2


def test_goodput_endpoint(obs_server):
    """GET /goodput serves the step-time breakdown tables; the spmd
    clocks exist process-wide once any engine ran (other tests in this
    session), so assert shape not specific clocks."""
    payload = json.loads(_get(obs_server, "/goodput"))
    assert "goodput_ratio" in payload
    for name, table in payload["clocks"].items():
        assert set(table["buckets_s"]) == {
            "compile", "host_input", "device_compute",
            "blocked_collective", "checkpoint", "overhead"}, name
        assert table["steps"] >= table["fenced_steps"] >= 0
    # the aggregate gauge rides /metrics too
    parsed = parse_prometheus_text(_get(obs_server, "/metrics"))
    assert "goodput_ratio" in parsed


def test_http_404_counted(obs_server):
    import urllib.error
    before = obs_server.registry.counter(
        "serving_http_errors_total").value
    with pytest.raises(urllib.error.HTTPError):
        _get(obs_server, "/definitely-not-a-route")
    after = obs_server.registry.counter(
        "serving_http_errors_total").value
    assert after == before + 1


def test_healthz_still_works(obs_server):
    payload = json.loads(_get(obs_server, "/healthz"))
    assert payload["status"] == "ok"
