"""Fleet telemetry plane (observability/trace_context.py,
telemetry_spool.py, fleet.py): cross-process trace propagation on the
X-Request-Id machinery, durable crash-safe telemetry spooling, and the
aggregated fleet view — including the acceptance e2e: a stream-ingested
generation request whose serving replica dies mid-decode carries ONE
trace id across three processes, and a SIGKILL'd process's spooled
exposition is harvested with its counters intact."""

import importlib.util
import json
import os
import select
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability import (
    get_registry,
    recent_spans,
    trace,
    trace_context,
)
from analytics_zoo_tpu.observability.fleet import (
    FleetAggregator,
    labeled_prometheus_text,
)
from analytics_zoo_tpu.observability.registry import (
    MetricsRegistry,
    parse_prometheus_text,
)
from analytics_zoo_tpu.observability.telemetry_spool import (
    TelemetrySpool,
    get_spool,
    maybe_spool,
    read_snapshots,
    reset_spools,
)
from analytics_zoo_tpu.observability.trace_context import (
    TraceContext,
    parse_traceparent,
)
from analytics_zoo_tpu.resilience.retry import RetryPolicy
from analytics_zoo_tpu.serving.distributed import ReplicaRouter
from analytics_zoo_tpu.serving.generation import CausalLM, GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 31

CTX = TraceContext("deadbeefcafe0001", "0123456789abcdef", 1)


@pytest.fixture()
def spool_dir(tmp_path):
    """observability_dir pointed at a fresh tmp dir, spool cache
    cleared both sides."""
    prev = OrcaContext.observability_dir
    OrcaContext.observability_dir = str(tmp_path / "obs")
    reset_spools()
    yield str(tmp_path / "obs")
    OrcaContext.observability_dir = prev
    reset_spools()


@pytest.fixture(scope="module")
def lm():
    model = CausalLM(vocab=VOCAB, hidden_size=16, n_head=2, n_block=1,
                     intermediate_size=32, max_position_len=128)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


# ----------------------------------------------------------------------
# trace context: parse / format / ambient parenting / carriers
# ----------------------------------------------------------------------

def test_parse_format_roundtrip():
    assert CTX.traceparent() == "00-deadbeefcafe0001-0123456789abcdef-01"
    back = parse_traceparent(CTX.traceparent())
    assert back == CTX
    # 32-hex trace ids from external W3C producers parse too
    ext = parse_traceparent("00-" + "ab" * 16 + "-1234567812345678-00")
    assert ext is not None and len(ext.trace_id) == 32


@pytest.mark.parametrize("bad", [
    None, 17, "", "garbage",
    "00-deadbeefcafe0001-0123456789abcdef",          # 3 parts
    "ff-deadbeefcafe0001-0123456789abcdef-01",       # version ff
    "00-0000000000000000-0123456789abcdef-01",       # all-zero trace
    "00-deadbeefcafe0001-0000000000000000-01",       # all-zero span
    "00-deadbeefcafe000x-0123456789abcdef-01",       # non-hex
    "00-deadbeef-0123456789abcdef-01",               # short trace
    "00-deadbeefcafe0001-0123456789abcdef-1",        # short flags
])
def test_parse_rejects_malformed(bad):
    assert parse_traceparent(bad) is None


def test_bind_makes_remote_parent_ambient():
    """A span opened under bind() joins the remote trace with no
    explicit parent plumbing; outside bind it is a fresh root."""
    with trace_context.bind(CTX):
        with trace("fleet.test.bound") as sp:
            assert sp.trace_id == CTX.trace_id
            assert sp.parent_id == CTX.span_id
            # downstream propagation: the open local span wins
            here = trace_context.current_trace_context()
            assert here.trace_id == CTX.trace_id
            assert here.span_id == sp.span_id
    with trace("fleet.test.unbound") as sp:
        assert sp.trace_id == sp.span_id != CTX.trace_id


def test_header_and_record_and_env_carriers():
    with trace_context.bind(CTX):
        headers = trace_context.inject_headers({})
        assert headers["traceparent"] == CTX.traceparent()
        assert trace_context.extract_headers(headers) == CTX
        # record envelope: stamped once, never overwritten
        doc = {"uri": "r1"}
        trace_context.inject_record(doc)
        assert doc["traceparent"] == CTX.traceparent()
        other = TraceContext("feedfacefeedface", "1111111111111111")
        trace_context.inject_record(doc, other)
        assert doc["traceparent"] == CTX.traceparent()
        assert trace_context.extract_record(doc) == CTX
        # env: env_bound exports and restores os.environ
        prev = os.environ.get("TRACEPARENT")
        with trace_context.env_bound():
            assert os.environ["TRACEPARENT"] == CTX.traceparent()
            env = trace_context.inject_env({})
            assert trace_context.from_env(env) == CTX
        assert os.environ.get("TRACEPARENT") == prev
    assert trace_context.extract_headers({}) is None
    assert trace_context.extract_record({"uri": "x"}) is None


def test_install_from_env_process_default():
    """A process launched with TRACEPARENT joins the trace on its first
    root span (lazy install)."""
    prev_default = trace_context._PROCESS_DEFAULT
    prev_checked = trace_context._ENV_CHECKED
    try:
        got = trace_context.install_from_env(
            {"TRACEPARENT": CTX.traceparent()})
        assert got == CTX
        assert trace_context.remote_parent() == CTX
        with trace("fleet.test.env_child") as sp:
            assert sp.trace_id == CTX.trace_id
            assert sp.parent_id == CTX.span_id
    finally:
        trace_context._PROCESS_DEFAULT = prev_default
        trace_context._ENV_CHECKED = prev_checked


# ----------------------------------------------------------------------
# durable telemetry spooling
# ----------------------------------------------------------------------

def test_spool_disabled_without_observability_dir():
    prev = OrcaContext.observability_dir
    OrcaContext.observability_dir = None
    reset_spools()
    try:
        assert get_spool("nobody") is None
        assert maybe_spool("nobody") is False
    finally:
        OrcaContext.observability_dir = prev
        reset_spools()


def test_spool_write_crash_safe_interval_gated(spool_dir):
    get_registry().counter("fleet_test_ops_total").inc(7)
    sp = get_spool("unit-proc")
    assert sp is not None
    assert sp.write()
    # commit idiom: the tmp staging file never survives a commit
    assert os.path.exists(sp.path)
    assert not os.path.exists(sp.path + ".tmp")
    docs = read_snapshots()
    assert len(docs) == 1
    doc = docs[0]
    assert doc["proc"] == "unit-proc" and doc["pid"] == os.getpid()
    assert "fleet_test_ops_total 7" in doc["exposition"]
    assert "slo" in doc and "spans" in doc and "requests" in doc
    # retention is exactly one file: a second write replaces in place
    seq = doc["seq"]
    assert sp.write()
    docs = read_snapshots()
    assert len(docs) == 1 and docs[0]["seq"] == seq + 1
    # time gate: an immediate maybe_write is a no-op
    assert sp.maybe_write() is False


def test_spool_bounded_by_max_bytes(spool_dir):
    for i in range(64):
        with trace("fleet.test.filler", i=i, pad="x" * 200):
            pass
    sp = TelemetrySpool("bounded", registries=(), max_bytes=4096)
    doc = sp.snapshot_doc()
    n0 = len(doc["spans"])
    assert len(json.dumps(doc, default=str).encode()) > 4096, \
        "scenario too small"
    blob = sp._encode_bounded(doc)
    bounded = json.loads(blob)
    assert bounded["truncated"] is True
    assert len(bounded["spans"]) < n0
    # the exposition is never trimmed, even when the tails hit zero
    assert bounded["exposition"] == doc["exposition"]


def test_read_snapshots_skips_garbage(spool_dir):
    sp = get_spool("good")
    assert sp.write()
    bad_dir = os.path.join(spool_dir, "telemetry", "torn")
    os.makedirs(bad_dir)
    with open(os.path.join(bad_dir, "snapshot.json"), "w") as f:
        f.write('{"proc": "torn", "pid"')
    assert [d["proc"] for d in read_snapshots()] == ["good"]


# ----------------------------------------------------------------------
# fleet aggregation: exact counter sums, labeled gauges
# ----------------------------------------------------------------------

def _write_fake_snapshot(spool_dir, proc, pid, exposition):
    pdir = os.path.join(spool_dir, "telemetry", proc)
    os.makedirs(pdir, exist_ok=True)
    with open(os.path.join(pdir, "snapshot.json"), "w") as f:
        json.dump({"proc": proc, "pid": pid, "seq": 1,
                   "wall_ts": time.time(), "exposition": exposition,
                   "spans": [], "requests": [], "slo": None}, f)


def test_fleet_counter_sums_are_exact(spool_dir):
    local = MetricsRegistry()
    local.counter("fleet_test_sum_total").inc(10)
    local.gauge("fleet_test_depth").set(3)
    _write_fake_snapshot(
        spool_dir, "worker-a", os.getpid() + 1,
        "# TYPE fleet_test_sum_total counter\nfleet_test_sum_total 5\n"
        "# TYPE fleet_test_depth gauge\nfleet_test_depth 8\n")
    _write_fake_snapshot(
        spool_dir, "worker-b", os.getpid() + 2,
        "# TYPE fleet_test_sum_total counter\nfleet_test_sum_total 2\n")
    agg = FleetAggregator(local_registries=(local,), local_name="here")
    text = agg.fleet_prometheus_text()
    parsed = parse_prometheus_text(text)
    # counters summed into ONE unlabeled row: 10 + 5 + 2, exactly
    assert parsed["fleet_test_sum_total"]["value"] == 17
    # gauges are per-source labeled rows, never averaged
    assert 'fleet_test_depth{source="here"} 3' in text
    assert 'fleet_test_depth{source="spool:worker-a"} 8' in text
    # a snapshot written by THIS process is skipped (live covers it)
    _write_fake_snapshot(
        spool_dir, "self", os.getpid(),
        "# TYPE fleet_test_sum_total counter\nfleet_test_sum_total 99\n")
    text = agg.fleet_prometheus_text()
    assert parse_prometheus_text(text)["fleet_test_sum_total"]["value"] \
        == 17
    assert get_registry().gauge("fleet_spooled_sources").value == 2


def test_spool_maybe_write_race_collapses_to_one(spool_dir):
    """N threads hitting maybe_write() at the same instant must
    collapse to AT MOST one write per interval (the gate re-checks
    under the lock), and a concurrent fleet harvest never sees torn
    snapshots or inexact counter sums."""
    import threading
    prev = OrcaContext.telemetry_spool_interval_s
    # (an interval five times what a loaded suite may keep a thread
    # off its core between the barrier and its call: at 0.01 a thread
    # that late wrote again, rightly, and the round counted two)
    OrcaContext.telemetry_spool_interval_s = 0.05
    local = MetricsRegistry()
    c = local.counter("fleet_race_total")
    c.inc(7)
    try:
        sp = TelemetrySpool("hammer", registries=(local,))
        agg = FleetAggregator(local_registries=(local,),
                              local_name="here")
        n_threads, n_rounds = 8, 20
        barrier = threading.Barrier(n_threads)
        results = [[] for _ in range(n_threads)]
        errors = []

        def worker(slot):
            try:
                for _ in range(n_rounds):
                    barrier.wait(timeout=30)
                    results[slot].append(bool(sp.maybe_write()))
                    time.sleep(0.06)        # next round is due again
            except Exception as e:          # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        # harvest while the hammering runs: parses clean, sums exact
        for _ in range(n_rounds):
            text = agg.fleet_prometheus_text()
            assert parse_prometheus_text(text)[
                "fleet_race_total"]["value"] == 7
            for doc in read_snapshots():
                assert doc["proc"] == "hammer"   # valid JSON, whole
            time.sleep(0.07)                     # let each round be due
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        per_round = [sum(results[s][r] for s in range(n_threads))
                     for r in range(n_rounds)]
        assert max(per_round) <= 1, \
            f"racing threads wrote {max(per_round)}x in one interval"
        assert sum(per_round) >= 2, "the spool never wrote at all"
    finally:
        OrcaContext.telemetry_spool_interval_s = prev


def test_labeled_prometheus_text_folds_labels():
    text = ("# TYPE x_total counter\nx_total 4\n"
            '# TYPE y summary\ny{quantile="0.5"} 1.5\ny_count 2\n')
    out = labeled_prometheus_text(text, {"replica": "replica-0"})
    assert 'x_total{replica="replica-0"} 4' in out
    assert 'y{quantile="0.5",replica="replica-0"} 1.5' in out
    assert 'y_count{replica="replica-0"} 2' in out
    assert labeled_prometheus_text(text, {}) == text


# ----------------------------------------------------------------------
# retry attempts: one trace, linked spans
# ----------------------------------------------------------------------

def test_retry_attempts_are_linked_spans():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("flap")
        return "ok"

    policy = RetryPolicy(max_attempts=3, backoff_s=0.0,
                         name="fleet_test_retry")
    with trace("fleet.test.op") as op:
        assert policy.run(flaky, retryable=(OSError,)) == "ok"
    attempts = [s for s in recent_spans(64)
                if s["name"] == "retry.attempt"
                and s["attrs"].get("policy") == "fleet_test_retry"]
    attempts.sort(key=lambda s: s["attrs"]["attempt"])
    assert [s["attrs"]["attempt"] for s in attempts] == [1, 2, 3]
    # all three attempts live in the ENCLOSING trace...
    assert {s["trace_id"] for s in attempts} == {op.trace_id}
    # ...and each retry links the attempt it retries
    assert "prev_span_id" not in attempts[0]["attrs"]
    assert attempts[1]["attrs"]["prev_span_id"] == attempts[0]["span_id"]
    assert attempts[2]["attrs"]["prev_span_id"] == attempts[1]["span_id"]


# ----------------------------------------------------------------------
# routed server: /metrics fleet folding + traceparent echo
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def routed_server(lm):
    from analytics_zoo_tpu.serving import ServingServer
    model, params = lm
    router = ReplicaRouter.build(model, params, n_replicas=2,
                                 warmup=False, max_slots=2,
                                 block_size=8, max_context=32)
    srv = ServingServer(router=router).start()
    yield srv, router
    srv.stop()
    router.stop()


def _get(srv, path):
    return urllib.request.urlopen(
        f"http://{srv.host}:{srv.port}{path}", timeout=10).read().decode()


def test_routed_metrics_fold_replica_registries(routed_server):
    """Plain /metrics on a routed server must not be fleet-blind:
    replica registries ride along with a replica label; ?fleet=0 opts
    out; ?fleet=1 serves the aggregated view."""
    srv, router = routed_server
    text = _get(srv, "/metrics")
    assert 'replica="replica-0"' in text
    assert 'replica="replica-1"' in text
    assert 'generation_tokens_total{replica="replica-0"}' in text
    plain = _get(srv, "/metrics?fleet=0")
    assert 'replica="replica-0"' not in plain
    # a probe counter only the replica registries own pins sum
    # exactness end to end through the HTTP fleet view
    for k, r in enumerate(router.replicas):
        r.engine.registry.counter("fleet_probe_total").inc(3 + k)
    fleet = _get(srv, "/metrics?fleet=1")
    assert fleet.startswith("# fleet:")
    assert 'source="replica-0"' in fleet
    assert parse_prometheus_text(fleet)["fleet_probe_total"][
        "value"] == 7


def test_generate_echoes_traceparent(routed_server):
    """POST /generate parents its span under the caller's traceparent
    and echoes its own context back; the client surfaces it."""
    from analytics_zoo_tpu.serving import InputQueue

    srv, _router = routed_server
    iq = InputQueue(srv.host, srv.port)
    with trace_context.bind(CTX):
        toks = iq.generate_tokens([1, 2, 3], max_new_tokens=2)
    assert len(toks) == 2
    echoed = parse_traceparent(iq.last_traceparent)
    assert echoed is not None
    assert echoed.trace_id == CTX.trace_id
    assert echoed.span_id != CTX.span_id, "server must mint its own span"
    # the handler's span closes just after the last chunk is written;
    # give the ring a moment
    spans = []
    deadline = time.monotonic() + 5
    while not spans and time.monotonic() < deadline:
        spans = [s for s in recent_spans(64)
                 if s["name"] == "serving.generate"
                 and s["trace_id"] == CTX.trace_id]
        if not spans:
            time.sleep(0.02)
    assert spans and spans[0]["parent_id"] == CTX.span_id


def test_stats_and_timeline_serve_fleet_views(routed_server, spool_dir):
    srv, _router = routed_server
    stats = json.loads(_get(srv, "/stats"))
    assert "fleet" in stats
    assert stats["fleet"]["fleet"]["sources"] >= 3   # local + 2 replicas
    doc = json.loads(_get(srv, "/timeline?fleet=1"))
    assert doc["otherData"]["fleet"] is True
    assert len(doc["otherData"]["sources"]) >= 3


# ----------------------------------------------------------------------
# router requeue: a linked span in the same trace
# ----------------------------------------------------------------------

def test_requeue_span_links_dead_attempt(lm):
    model, params = lm
    engines = [GenerationEngine(model, params, max_slots=2,
                                block_size=8, max_context=64,
                                registry=MetricsRegistry())
               for _ in range(2)]
    router = ReplicaRouter(engines).ensure_started()
    prev = OrcaContext.fault_plan
    OrcaContext.fault_plan = {"faults": [
        {"site": "generation.decode", "at": 3,
         "action": "poison_request", "request_id": "fleet-victim"}]}
    try:
        with trace_context.bind(CTX):
            rs = router.submit([3, 1, 4, 1, 5], max_new_tokens=8,
                               request_id="fleet-victim")
            toks = rs.tokens()
    finally:
        OrcaContext.fault_plan = prev
        router.stop()
    assert len(toks) == 8
    assert len(rs._dispatch_spans) == 2, "dispatch + requeue"
    spans = {s["span_id"]: s for s in recent_spans(128)}
    dispatch = spans[rs._dispatch_spans[0]]
    requeue = spans[rs._dispatch_spans[1]]
    assert dispatch["name"] == "router.dispatch"
    assert requeue["name"] == "router.requeue"
    # same trace (the caller's!), new span, explicit link to the dead
    # attempt plus the attempt number
    assert dispatch["trace_id"] == requeue["trace_id"] == CTX.trace_id
    assert requeue["attrs"]["link_span_id"] == dispatch["span_id"]
    assert requeue["attrs"]["attempt"] == 2
    assert requeue["attrs"]["failed_replica"] == dispatch["attrs"]["replica"]


# ----------------------------------------------------------------------
# the acceptance e2e: one trace across three processes, a SIGKILL'd
# worker's telemetry harvested
# ----------------------------------------------------------------------

_CLIENT_CODE = """
import json, os, time, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from analytics_zoo_tpu.common.context import OrcaContext
OrcaContext.observability_dir = {obs!r}
from analytics_zoo_tpu.observability import get_registry, trace, trace_context
from analytics_zoo_tpu.observability.telemetry_spool import get_spool
get_registry().counter("e2e_child_ops_total").inc()
with trace("e2e.client", role="client"):
    hdrs = trace_context.inject_headers({{"Content-Type": "application/json"}})
    body = json.dumps({{"uri": "e2e-1", "tokens": [3, 1, 4, 1, 5],
                        "max_new_tokens": 6}}).encode()
    req = urllib.request.Request(
        "http://{host}:{port}/streams/jobs/enqueue", data=body,
        headers=hdrs)
    resp = json.loads(urllib.request.urlopen(req, timeout=15).read())
assert get_spool("e2e-client").write()
print("READY", resp["record_id"], flush=True)
time.sleep(120)
"""

_RESULT_CODE = """
import json, os, time, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from analytics_zoo_tpu.common.context import OrcaContext
OrcaContext.observability_dir = {obs!r}
from analytics_zoo_tpu.observability import get_registry, trace, trace_context
from analytics_zoo_tpu.observability.telemetry_spool import get_spool
doc = None
deadline = time.time() + 60
while doc is None and time.time() < deadline:
    body = json.dumps({{"group": "sink", "consumer": "s0",
                        "max_records": 1, "block_s": 1.0}}).encode()
    req = urllib.request.Request(
        "http://{host}:{port}/streams/outs/dequeue", data=body,
        headers={{"Content-Type": "application/json"}})
    recs = json.loads(urllib.request.urlopen(req, timeout=35).read())["records"]
    if recs:
        doc = recs[0]["doc"]
assert doc is not None, "no result record"
ctx = trace_context.extract_record(doc)
assert ctx is not None, "result record lost its traceparent"
with trace_context.bind(ctx):
    with trace("e2e.result", role="result"):
        get_registry().counter("e2e_child_ops_total").inc()
assert get_spool("e2e-result").write()
print("READY", ctx.trace_id, flush=True)
time.sleep(120)
"""


def _spawn(code, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.Popen([sys.executable, "-c", code], cwd=ROOT,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _wait_ready(proc, timeout=90.0):
    """First stdout line, or fail with the child's stderr.  Reads the
    raw fd: select on the buffered TextIOWrapper would stall once data
    sits in the Python-side buffer."""
    deadline = time.monotonic() + timeout
    fd = proc.stdout.fileno()
    buf = b""
    while time.monotonic() < deadline:
        if b"\n" in buf:
            return buf.split(b"\n", 1)[0].decode()
        if proc.poll() is not None:
            raise AssertionError(
                f"child died rc={proc.returncode}: {proc.stderr.read()}")
        r, _, _ = select.select([fd], [], [], 0.25)
        if r:
            buf += os.read(fd, 4096)
    raise AssertionError(f"child never signalled READY (got {buf!r})")


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "azt_timeline_lint",
        os.path.join(ROOT, "scripts", "check_timeline_schema.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_e2e_one_trace_three_processes_sigkill_harvest(lm, tmp_path):
    """The acceptance shape: client process -> stream -> consumer ->
    router -> replica (killed mid-decode, requeued) -> result process.
    One trace id end to end; both child processes are SIGKILL'd after
    spooling and their telemetry is harvested into the fleet view; the
    decode program never recompiles with everything armed."""
    from analytics_zoo_tpu.serving import ServingServer
    from analytics_zoo_tpu.serving.streaming import StreamHub

    model, params = lm
    obs = str(tmp_path / "obs")
    prev_dir = OrcaContext.observability_dir
    prev_fault = OrcaContext.fault_plan
    prev_interval = OrcaContext.telemetry_spool_interval_s
    OrcaContext.observability_dir = obs
    OrcaContext.telemetry_spool_interval_s = 0.1
    reset_spools()

    hub = StreamHub(str(tmp_path / "hub"), max_backlog=16)
    jobs, outs = hub.get("jobs"), hub.get("outs")
    engines = [GenerationEngine(model, params, max_slots=2,
                                block_size=8, max_context=64,
                                registry=MetricsRegistry())
               for _ in range(2)]
    router = ReplicaRouter(engines).ensure_started()
    srv = ServingServer(router=router, stream_hub=hub).start()
    client = result = cons = None
    try:
        # the first record of a fresh stream is id 1: poison its third
        # decode round on whichever replica serves it
        OrcaContext.fault_plan = {"faults": [
            {"site": "generation.decode", "at": 3,
             "action": "poison_request", "request_id": "strm-jobs-1"}]}
        cons = router.consume_stream(jobs, out_stream=outs,
                                     group="generate", consumer="g0",
                                     poll_s=0.02)
        client = _spawn(
            _CLIENT_CODE.format(obs=obs, host=srv.host, port=srv.port),
            extra_env={"TRACEPARENT": CTX.traceparent()})
        ready = _wait_ready(client)
        assert ready.split()[1] == "1"
        client.send_signal(signal.SIGKILL)

        deadline = time.monotonic() + 90
        while outs.log.last_id < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert outs.log.last_id >= 1, "generation result never landed"

        result = _spawn(
            _RESULT_CODE.format(obs=obs, host=srv.host, port=srv.port))
        ready = _wait_ready(result)
        assert ready.split()[1] == CTX.trace_id
        result.send_signal(signal.SIGKILL)
        cons.stop()

        # --- one trace id, end to end, across the requeue ------------
        mine = [s for s in recent_spans(256)
                if s["trace_id"] == CTX.trace_id]
        names = {s["name"] for s in mine}
        assert {"stream.consume", "router.dispatch",
                "router.requeue"} <= names, names
        requeue = next(s for s in mine if s["name"] == "router.requeue")
        dispatch = next(s for s in mine if s["name"] == "router.dispatch")
        assert requeue["attrs"]["link_span_id"] == dispatch["span_id"]
        assert router._c_requeues.value >= 1

        # --- the SIGKILL'd processes' telemetry survived --------------
        docs = {d["proc"]: d for d in read_snapshots(obs)}
        assert {"e2e-client", "e2e-result"} <= set(docs)
        pids = {os.getpid()} | {docs[p]["pid"]
                                for p in ("e2e-client", "e2e-result")}
        assert len(pids) == 3, "trace must span three distinct processes"
        for proc in ("e2e-client", "e2e-result"):
            assert "e2e_child_ops_total 1" in docs[proc]["exposition"]
            assert any(s["trace_id"] == CTX.trace_id
                       for s in docs[proc]["spans"]), proc

        # --- fleet harvest: counters intact, one merged timeline ------
        fleet = srv.fleet().fleet_prometheus_text()
        assert parse_prometheus_text(fleet)["e2e_child_ops_total"][
            "value"] == 2, "spooled counters must sum into the fleet"
        doc = srv.fleet().fleet_timeline()
        mod = _load_validator()
        errors = mod.validate_timeline(doc)
        assert errors == [], "\n".join(errors)
        meta_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("ph") == "M"
                     and e.get("name") == "process_name"}
        assert len(meta_pids) >= 3
        flow = [e for e in doc["traceEvents"]
                if e.get("ph") in ("s", "t", "f")
                and e.get("name") == f"trace:{CTX.trace_id[:8]}"]
        flow_pids = {e["pid"] for e in flow}
        assert len(flow_pids) >= 2, "flow must stitch across pids"
        assert {"s", "f"} <= {e["ph"] for e in flow}

        # --- zero-recompile with the whole plane armed ----------------
        for e in engines:
            assert e.decode_compile_count == 1, \
                "decode recompiled with tracing + spooling armed"
        # replica loops spooled under their replica names
        assert {"replica-0", "replica-1"} <= set(docs)
    finally:
        for p in (client, result):
            if p is not None and p.poll() is None:
                p.kill()
            if p is not None:
                p.wait(timeout=10)
                p.stdout.close()
                p.stderr.close()
        if cons is not None:
            cons.stop()
        OrcaContext.fault_plan = prev_fault
        OrcaContext.observability_dir = prev_dir
        OrcaContext.telemetry_spool_interval_s = prev_interval
        reset_spools()
        srv.stop()
        router.stop()
        hub.close()


# ----------------------------------------------------------------------
# tail-exemplar crash-safety: SIGKILL mid-decode, forensics survive
# ----------------------------------------------------------------------

_EXEMPLAR_CODE = """
import os, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from analytics_zoo_tpu.common.context import OrcaContext
OrcaContext.observability_dir = {obs!r}
OrcaContext.slo_targets = {{"e2e_s": 1e-4}}
import jax, jax.numpy as jnp
from analytics_zoo_tpu.observability.exemplars import get_exemplar_store
from analytics_zoo_tpu.observability.telemetry_spool import get_spool
from analytics_zoo_tpu.serving.generation import CausalLM, GenerationEngine
model = CausalLM(vocab=31, hidden_size=16, n_head=2, n_block=1,
                 intermediate_size=32, max_position_len=128)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    jnp.arange(8)[None])["params"]
eng = GenerationEngine(model, params, max_slots=2, block_size=8,
                       max_context=96)
s = eng.submit([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=4,
               request_id="victim-done")
eng.run_until_idle()
assert len(s.tokens()) == 4
doc = get_exemplar_store().get("victim-done")
assert doc is not None, "finished request was not exemplared"
assert doc["reason"] == "slo_violation", doc["reason"]
# a second request is mid-decode when the SIGKILL lands
eng.submit([2, 7, 1, 8], max_new_tokens=64, request_id="victim-live")
for _ in range(3):
    eng.step()
assert get_spool("victim-replica").write()
print("READY victim-done", flush=True)
time.sleep(120)
"""


@pytest.mark.slow   # spawns a JAX child process (~20s cold compile)
def test_sigkill_mid_decode_exemplar_survives_via_spool(tmp_path):
    """Satellite of the blame plane: a replica process finishes one
    SLO-violating request (captured as a tail exemplar), spools, and is
    SIGKILL'd mid-decode of a second request.  The exemplar — full
    phase ledger attached — survives on disk and merges into the fleet
    /blame view; the in-flight victim's lifecycle record survives too."""
    obs = str(tmp_path / "obs")
    child = _spawn(_EXEMPLAR_CODE.format(obs=obs))
    try:
        ready = _wait_ready(child, timeout=240.0)
        assert ready.split()[1] == "victim-done"
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=10)

        docs = {d["proc"]: d for d in read_snapshots(obs)}
        assert "victim-replica" in docs
        doc = docs["victim-replica"]
        ex = {e["request_id"]: e for e in doc["exemplars"]}
        assert "victim-done" in ex
        led = ex["victim-done"]["ledger"]
        assert led["additive_ok"] is True
        assert led["phases"]["decode_active"] > 0.0
        assert ex["victim-done"]["violations"] == ["e2e_s"]
        # the mid-decode victim's record rode the same commit
        live = {r["request_id"]: r for r in doc["requests"]}
        assert live["victim-live"]["status"] in ("queued", "running")

        # fleet /blame: counters sum from the dead replica's spool,
        # its exemplar is harvested and fetchable by id
        from analytics_zoo_tpu.observability.blame import (
            reset_blame_tracker,
        )
        from analytics_zoo_tpu.observability.exemplars import (
            reset_exemplar_store,
        )
        reset_blame_tracker()
        reset_exemplar_store()
        agg = FleetAggregator(local_registries=(MetricsRegistry(),),
                              observability_dir=obs,
                              include_spooled=True)
        fb = agg.fleet_blame()
        assert fb["counters"]["blame_requests_total"] >= 1.0
        assert fb["counters"]["blame_decode_active_seconds_total"] > 0.0
        rows = {r["request_id"]: r for r in fb["exemplars"]}
        assert rows["victim-done"]["source"] == "spool:victim-replica"
        fetched = agg.fleet_exemplar("victim-done")
        assert fetched is not None
        assert fetched["source"] == "spool:victim-replica"
        assert fetched["ledger"]["e2e_s"] == led["e2e_s"]
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=10)
        child.stdout.close()
        child.stderr.close()


# ----------------------------------------------------------------------
# knobs
# ----------------------------------------------------------------------

def test_spool_knobs_validate():
    assert OrcaContext.telemetry_spool_interval_s == 1.0
    assert OrcaContext.telemetry_spool_max_bytes == 1024 * 1024
    with pytest.raises(ValueError):
        OrcaContext.telemetry_spool_interval_s = -1
    with pytest.raises(ValueError):
        OrcaContext.telemetry_spool_max_bytes = 16
