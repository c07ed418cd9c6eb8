"""The bridge from the program's spans to the profiler's trace
(`observability/tracing.py`): under a real `jax.profiler` session on
the CPU, with the benchmark's `Tracer` and its options, every span of
PERF.md's span table shows as a host event ``azt:<name>``, children
inside their parents, the counts in the decode span's name; the light
phases leave the ring behind `GET /spans` alone; what is served does
not depend on a session being open; and the program opens none itself.
"""

import contextlib
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from analytics_zoo_tpu.observability import goodput, request_log, tracing
from analytics_zoo_tpu.serving.generation import (
    CausalLM, GenerationEngine, lane_state)
from analytics_zoo_tpu.serving.generation import engine as engine_module
from benchmarks.harness import cpu_marks as bench_marks
from benchmarks.harness import span_metrics
from benchmarks.harness.trace_reduce import Trace
from benchmarks.harness.tracing import Tracer

START_TRACE = jax.profiler.start_trace
PROMPTS = [([3, 9, 27, 20, 11], 4), ([5, 7, 11, 13, 17, 19, 23], 6),
           ([2, 4, 8, 16, 32, 3, 6, 12, 24, 48], 8)]
LEAVES = {"stage", "dispatch", "fetch", "account", "emit"}


@pytest.fixture(scope="module", autouse=True)
def no_session_but_the_tests():
    """`jax.profiler.start_trace` raises for the whole module: the
    program never opens a profiler session.  `session()` puts the real
    one back for the one call that opens the test's own."""
    patch = pytest.MonkeyPatch()

    def poisoned(*args, **kwargs):
        raise AssertionError("the program opened a profiler session")

    patch.setattr(jax.profiler, "start_trace", poisoned)
    yield
    patch.undo()


@contextlib.contextmanager
def session():
    tracer = Tracer()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax.profiler, "start_trace", START_TRACE)
        tracer.start()
    try:
        yield tracer
    finally:
        if tracer.t_stop is None:
            tracer.stop()
        tracer.remove()


def reduced(tracer) -> Trace:
    tracer.stop()
    return Trace.from_xplane(tracer.path(), 1,
                             tracer.t_stop - tracer.t_start)


def azt(trace, prefix=""):
    return span_metrics.host_events(trace, span_metrics.PREFIX + prefix)


def assert_nested(events):
    """Spans of one thread: each lies inside the one open around it, or
    begins after it has ended."""
    stack = []
    for name, start, end in events:
        while stack and stack[-1][2] <= start:
            stack.pop()
        if stack:
            assert end <= stack[-1][2], (name, stack[-1][0])
        stack.append((name, start, end))


@pytest.fixture(scope="module")
def engine():
    model = CausalLM(vocab=61, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=128)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64)
    eng.warmup()
    yield eng
    eng.stop()


def serve(engine, temperature=0.0):
    """The three requests queued, then the loop started: one admission,
    three prefills, and decode rounds of 3, 3, 3, 2, 2, 1, 1 lanes.
    Returns the tokens and the lanes of every decode dispatch, counted
    from the lane rows the jitted program was given, its patch applied."""
    lanes, real = [], engine._decode_jit

    def counted(params, kv, scale, state, patch):
        rows = lane_state.patched(state["rows"], patch)
        lanes.append(int(np.asarray(rows[:, lane_state.ACTIVE]).sum()))
        return real(params, kv, scale, state, patch)

    engine._decode_jit = counted
    engine._lanes.restore(np.asarray(jax.random.PRNGKey(7)))
    try:
        streams = [engine.submit(p, max_new_tokens=n,
                                 temperature=temperature)
                   for p, n in PROMPTS]
        engine.ensure_started()
        tokens = [s.tokens() for s in streams]
        time.sleep(0.12)            # the idle loop: two waits of 50 ms
    finally:
        engine.stop()
        engine._decode_jit = real
    return tokens, lanes


def test_engine_spans_land_in_a_profiler_trace(engine):
    plain, plain_lanes = serve(engine)
    tracing.clear_spans()
    with session() as tracer:
        tokens, lanes = serve(engine)
        trace = reduced(tracer)
    assert tokens == plain and lanes == plain_lanes
    assert [len(t) for t in tokens] == [4, 6, 8]
    assert lanes == [3, 3, 3, 2, 2, 1, 1]

    events = azt(trace, "generation.")
    names = {span_metrics.short(n) for n, _, _ in events}
    assert names == LEAVES | {"round", "admit", "capacity", "prefill",
                              "decode", "wait", "housekeeping"}
    assert_nested(events)

    spans = span_metrics.engine_spans(trace)
    for span in spans:
        if span.name in LEAVES:
            # the last round of all is collected with none to enqueue
            assert span.parents[-1] in ("prefill", "decode", "round"), span
            assert span.parents[0] == "round"
        elif span.name in ("admit", "capacity", "decode"):
            assert span.parents == ("round",), span
        elif span.name == "prefill":
            # enqueued in a round, collected inside its decode span
            assert span.parents in (("round",), ("round", "decode")), span
        else:
            assert span.parents == (), span
    # one decode span a dispatch, with the lanes and the queue in its name
    found = [span_metrics.DECODE.match(n) for n, _, _ in events
             if n.startswith("azt:generation.decode")]
    assert all(found)
    assert [int(m.group(1)) for m in found] == lanes
    assert {int(m.group(2)) for m in found} == {0}
    # a prefill is two spans, its enqueue and its collection; a round's
    # decode span enqueues one step and collects the one before it, so
    # the first collects none and the last is collected outside any:
    # one stage, dispatch, fetch and emit a dispatch, two accounts (the
    # planes' writes, then the goodput commit)
    count = {}
    for span in spans:
        if span.name in LEAVES:
            key = (span.parents[-1], span.name)
            count[key] = count.get(key, 0) + 1
    assert sum(s.name == "prefill" for s in spans) == 2 * 3
    assert sum(s.parents[-1:] == ("decode",) and s.name == "prefill"
               for s in spans) == 3
    rounds = len(lanes)
    assert count == {
        ("prefill", "stage"): 3, ("prefill", "dispatch"): 3,
        ("prefill", "fetch"): 3, ("prefill", "emit"): 3,
        ("prefill", "account"): 2 * 3,
        ("decode", "stage"): rounds, ("decode", "dispatch"): rounds,
        ("decode", "fetch"): rounds - 1, ("decode", "emit"): rounds - 1,
        ("decode", "account"): 2 * rounds - 1,
        ("round", "fetch"): 1, ("round", "emit"): 1,
        ("round", "account"): 2}

    # the ring behind GET /spans holds no phase
    assert not [s for s in tracing.recent_spans(4096)
                if s["name"].startswith(("generation.", "azt:"))]

    ctx = {"trace": trace}
    assert span_metrics.decode_counts(ctx, 0) == pytest.approx(15 / 7)
    assert span_metrics.decode_counts(ctx, 1) == 0.0
    assert 0 < span_metrics.prefill_time_share(ctx) < 100
    # no device plane on the CPU: no share of its idle time
    assert span_metrics.serve_idle(ctx) is None


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_a_decode_round_launches_one_program(engine, temperature):
    """Greedy or sampled, with a patch of lane rows or without: the
    only program a decode round runs is `decode` — the key is split
    inside it (no `_threefry_split`, no `_unstack`), and the lane rows
    are advanced by it.  The name is what the benchmark's readers find
    the step by."""
    streams = [engine.submit(p, max_new_tokens=12, temperature=temperature)
               for p, _ in PROMPTS]
    engine.step()                   # the prefills and a first decode
    with session() as tracer:
        for _ in range(6):          # lanes cross block boundaries in it
            engine.step()
        trace = reduced(tracer)
    engine.run_until_idle()
    assert [len(s.tokens()) for s in streams] == [12, 12, 12]
    rounds = [n for n, _, _ in azt(trace, "generation.decode")]
    assert rounds == ["azt:generation.decode[l=3,w=0]"] * 6
    programs = {n for n, _, _ in
                span_metrics.host_events(trace, "PjitFunction(")}
    assert programs == {"PjitFunction(decode)"}
    assert engine.decode_compile_count == 1


def test_sampled_tokens_do_not_depend_on_a_session(engine):
    plain, _ = serve(engine, temperature=0.9)
    with session():
        traced, _ = serve(engine, temperature=0.9)
    assert traced == plain


def test_each_requests_log_keeps_its_order(engine, monkeypatch):
    """Accounting for every lane, then emission for every lane: a
    request still sees its decode round before the round's token, and
    its finish last."""
    calls = []
    log = request_log.get_request_log()
    for kind in ("decode_round", "token", "finish"):
        real = getattr(log, kind)
        monkeypatch.setattr(
            log, kind, lambda rid, *a, _k=kind, _r=real, **kw: (
                calls.append((rid, _k[0])), _r(rid, *a, **kw))[1])
    tokens, lanes = serve(engine)
    by_request = {}
    for rid, kind in calls:
        by_request[rid] = by_request.get(rid, "") + kind
    assert len(by_request) == 3
    # the prefill's token, then (round, token) a decode round, then finish
    assert sorted(by_request.values(), key=len) == [
        "t" + "dt" * (n - 1) + "f" for n in (4, 6, 8)]
    # and within one round, every lane's accounting before any emission
    order = "".join(kind for _, kind in calls)
    assert re.search(r"ddd" + "ttt", order)


def test_fit_spans_land_in_a_profiler_trace():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.common.context import OrcaContext
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context(cluster_mode="local")
    rng = np.random.default_rng(0)
    u, i = rng.integers(1, 21, 96), rng.integers(1, 11, 96)
    data = {"x": [u, i], "y": ((u + i) % 2).astype(np.int32)}
    est = Estimator.from_flax(NeuralCF(user_count=20, item_count=10),
                              loss="sparse_categorical_crossentropy",
                              optimizer="adam", learning_rate=5e-3)
    every = OrcaContext.goodput_sample_every
    OrcaContext.goodput_sample_every = 1      # every step fenced
    try:
        est.fit(data, epochs=1, batch_size=32)        # compiles
        tracing.clear_spans()
        with session() as tracer:
            est.fit(data, epochs=1, batch_size=32)
            trace = reduced(tracer)
    finally:
        OrcaContext.goodput_sample_every = every
    events = azt(trace)
    count = {}
    for name, _, _ in events:
        count[name[4:]] = count.get(name[4:], 0) + 1
    assert count == {"estimator.fit": 1, "estimator.epoch": 1,
                     "spmd.input_wait": 4, "spmd.step": 3,
                     "spmd.stage_next": 3, "spmd.fence": 3,
                     "spmd.account": 6, "spmd.epoch_end": 1}
    assert_nested(events)
    fit = next(e for e in events if e[0] == "azt:estimator.fit")
    assert all(fit[1] <= s and e <= fit[2] for _, s, e in events)
    # whole spans are in the ring as before, the phases are not
    ring = {s["name"] for s in tracing.recent_spans(4096)}
    assert {"estimator.fit", "estimator.epoch", "spmd.step"} <= ring
    assert not ring & {"spmd.input_wait", "spmd.stage_next", "spmd.fence",
                       "spmd.account", "spmd.epoch_end"}
    wait = span_metrics.train_input_wait_ms({"trace": trace})
    by_hand = sum(e - s for n, s, e in events
                  if n == "azt:spmd.input_wait") / 1e6 / 3
    assert wait == pytest.approx(by_hand) and wait > 0


def test_a_phase_is_the_annotation_and_nothing_else():
    tracing.clear_spans()
    before = set(m for m in goodput.get_registry().snapshot())
    with tracing.phase("generation.dispatch") as span:
        assert tracing.current_span() is None
    assert isinstance(span, jax.profiler.TraceAnnotation)
    assert tracing.recent_spans() == []
    assert set(goodput.get_registry().snapshot()) == before


def test_a_step_records_phase_is_its_lap():
    """One list of boundaries: the phase's end is the lap's, whatever
    ran between two phases goes to the later one, as between laps."""
    clock = goodput.StepClock("bridge_test")
    rec = clock.begin(force_fence=True)
    with rec.phase("generation.stage", "host_input"):
        time.sleep(0.002)
    time.sleep(0.002)                   # between phases
    with rec.phase("generation.dispatch"):
        pass
    with rec.phase("generation.fetch", "device_compute"):
        time.sleep(0.002)
    rec.end()
    table = clock.table()
    assert table["fenced_steps"] == 1
    buckets = table["buckets_s"]
    assert buckets["host_input"] >= 0.002
    assert buckets["device_compute"] >= 0.002
    assert buckets["overhead"] >= 0.002       # the gap and the dispatch
    assert sum(buckets.values()) == pytest.approx(table["fenced_wall_s"],
                                                  abs=2e-6)


# --- the CPU marks: whose turn on the interpreter lock --------------------

def cpu_marks(trace, kind):
    """(start, fields) of the `cpu.<kind>` marks, by start, as the
    benchmark parses them."""
    return bench_marks.marks(trace, kind)


def one_request(engine, n=5):
    """A request through the HTTP server and the streaming client;
    the loop thread runs the rounds."""
    from analytics_zoo_tpu.serving import InputQueue, ServingServer
    server = ServingServer(generation_engine=engine).start()
    try:
        client = InputQueue(server.host, server.port)
        assert len(client.generate_tokens(PROMPTS[0][0],
                                          max_new_tokens=n)) == n
    finally:
        server.stop()
        engine.stop()


def test_without_a_session_no_clock_is_read_in_a_round(engine, monkeypatch):
    """No profiler session: the rounds read no CPU clock, arm no clock
    and make no mark, whatever thread steps; a request costs its
    handler and its client one clock read each, at its start."""
    reads, made = [], []
    real_clock, real_mark = time.thread_time_ns, tracing.mark
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append(1) or real_clock())
    monkeypatch.setattr(tracing, "mark", lambda name, **kw: (
        made.append(name), real_mark(name, **kw))[1])
    monkeypatch.setattr(tracing, "_request_due", {})
    assert not tracing.enabled()
    streams = [engine.submit(p, max_new_tokens=n) for p, n in PROMPTS]
    engine.run_until_idle()                 # the caller's thread
    assert [len(s.tokens()) for s in streams] == [4, 6, 8]
    serve(engine)                           # the loop's thread
    assert reads == [] and made == [] and not tracing._clocked
    one_request(engine)
    assert len(reads) == 2 and made == [] and not tracing._clocked
    # and a request that begins within a tenth of a second of one
    # that was clocked costs neither a read
    monkeypatch.setattr(tracing, "REQUEST_GAP_S", 3600.0)
    tracing._request_due.clear()
    one_request(engine)
    one_request(engine)
    assert len(reads) == 4 and made == []


def test_one_round_of_every_few_is_clocked_and_marked(engine, monkeypatch):
    """Under a CPU clock that moves a millisecond a read: a bucket is
    charged 1,000 µs for every time the clock changed hands out of
    it, whatever the platform's real clock is worth."""
    reads = []
    monkeypatch.setattr(
        time, "thread_time_ns",
        lambda: reads.append(1) or len(reads) * 1_000_000)
    monkeypatch.setattr(engine._cpu, "EVERY", 3, raising=False)
    streams = [engine.submit(p, max_new_tokens=12)
               for p, _ in PROMPTS[:2]]
    engine.step()               # no session yet: nothing to reach back to
    time.sleep(0.3)
    assert reads == []
    in_round = []
    with session() as tracer:
        streams.append(engine.submit(PROMPTS[2][0], max_new_tokens=12))
        for _ in range(7):
            before = len(reads)
            engine.step()
            in_round.append(len(reads) - before)
        trace = reduced(tracer)
    engine.run_until_idle()
    assert [len(s.tokens()) for s in streams] == [12, 12, 12]
    rounds = azt(trace, "generation.round")
    marks = cpu_marks(trace, "loop")
    assert len(rounds) == 7 and len(marks) == 3
    # rounds 0, 3 and 6 are clocked; the round before a clocked one
    # reads the clock once, at its end; the others never
    assert in_round[1::3] == [0, 0] and in_round[2::3] == [1, 1]
    assert all(n > 6 for n in in_round[0::3])
    buckets = engine_module.CPU_BUCKETS
    for i, (at, fields) in zip((0, 3, 6), marks):
        # behind its round and before the next: under no phase
        assert rounds[i][2] <= at
        assert i == 6 or at < rounds[i + 1][1]
        assert list(fields) == ["wall", *buckets]
        # every read of the stretch but its first charged one bucket
        stretch = in_round[i] + (in_round[i - 1] if i else 0)
        assert sum(fields[b] for b in buckets) == (stretch - 1) * 1000
        # this thread's clock changed hands out of every phase a
        # decode round has, and out of a prefill where there was one
        assert all(fields[b] >= 1000 for b in buckets
                   if b != "prefill_host")
        assert (fields["prefill_host"] >= 1000) == (i == 0)
    # the first mark covers its own round, not the 0.3 s before the
    # session; a later one reaches back to the end of the round before
    assert marks[0][1]["wall"] < 300_000
    for i, (at, fields) in zip((3, 6), marks[1:]):
        assert fields["wall"] == pytest.approx(
            (at - rounds[i - 1][2]) / 1e3, rel=0.05, abs=500)


def test_the_loop_threads_rounds_and_a_request_leave_their_marks(
        engine, monkeypatch):
    monkeypatch.setattr(tracing, "_request_due", {})
    with session() as tracer:
        one_request(engine)
        trace = reduced(tracer)
    rounds = azt(trace, "generation.round")
    marks = cpu_marks(trace, "loop")
    assert len(marks) == -(-len(rounds) // engine._cpu.EVERY) > 0
    buckets = engine_module.CPU_BUCKETS
    for (at, fields), clocked in zip(marks,
                                     rounds[::engine._cpu.EVERY]):
        assert clocked[2] <= at
        assert all(v >= 0 for v in fields.values())
        # a thread has no more CPU than wall, but for a tick of its
        # clock (10 ms where the platform samples it)
        assert sum(fields[b] for b in buckets) \
            <= fields["wall"] * 1.02 + 10_000
    # the benchmark's reduction reads the program's own marks
    read = bench_marks.reduced({"trace": trace})
    assert read["rounds"] == len(marks) and read["round"] > 0
    for kind in ("handler", "client"):
        (_, fields), = cpu_marks(trace, kind)
        assert fields["tokens"] == 5 and fields["cpu"] >= 0, kind
        assert read[kind + "_us_per_token"] == fields["cpu"] / 5
    # the marks are no span of the loop's: its readers see none
    assert not [s for s in span_metrics.engine_spans(trace)
                if "cpu" in s.name]


def test_one_request_in_a_tenth_of_a_second_is_clocked(monkeypatch):
    """A name's clock is due again `REQUEST_GAP_S` after the request
    it was last read for began, each name on its own; a request that
    was not clocked leaves no mark, nor does one without a session."""
    t, made = [100.0], []
    monkeypatch.setattr(tracing, "_request_due", {})
    monkeypatch.setattr(tracing, "now", lambda: t[0])
    monkeypatch.setattr(tracing, "mark",
                        lambda name, **kw: made.append((name, kw)))
    assert tracing.request_clock("cpu.handler") is not None
    assert tracing.request_clock("cpu.client") is not None
    t[0] += tracing.REQUEST_GAP_S / 2
    assert tracing.request_clock("cpu.handler") is None
    t[0] += tracing.REQUEST_GAP_S / 2
    cpu0 = tracing.request_clock("cpu.handler")
    assert cpu0 is not None
    assert tracing.request_clock("cpu.handler") is None
    tracing.mark_request("cpu.handler", cpu0, 7)      # no session
    monkeypatch.setattr(tracing, "enabled", lambda: True)
    tracing.mark_request("cpu.handler", None, 7)      # not clocked
    assert made == []
    tracing.mark_request("cpu.handler", cpu0, 7)
    (name, fields), = made
    assert name == "cpu.handler" and fields["tokens"] == 7
    assert fields["cpu"] >= 0


def test_the_loops_cpu_goes_where_its_idle_time_goes():
    """The program's rule for a phase's CPU is the benchmark's rule
    for the device's idle time under the same spans: every span of
    PERF.md's table under every chain of parents a round has."""
    clock = tracing.LoopClock("generation.", engine_module.CPU_BUCKETS,
                              engine_module.CPU_PHASE,
                              engine_module.CPU_UNDER)

    def program(chain):
        bucket = None
        for name in chain:
            bucket = clock.bucket("generation." + name, bucket)
        return bucket

    def reader(chain):
        span = span_metrics.Span(chain[-1].split("[")[0], 0, 0,
                                 tuple(chain[:-1]))
        return span_metrics.phase_of(span) or "off_round"

    names = sorted(set(span_metrics.LEAF_PHASE)
                   | {"prefill", "wait", "housekeeping",
                      "decode[l=3,w=0]"})
    chains = [parents + (name,) for name in names for parents in (
        (), ("round",), ("round", "decode"), ("round", "prefill"),
        ("round", "decode", "prefill"), ("round", "spec_verify"))]
    assert ({c: program(c) for c in chains}, engine_module.CPU_BUCKETS,
            program(("round", "dispatch")) and
            clock.bucket("serving.run_batch", "dispatch")) == (
        {c: reader(c) for c in chains},
        span_metrics.PHASES + ("off_round",), "dispatch")


def test_the_annotation_is_made_in_one_place():
    made = []
    for path in glob.glob(os.path.join(ROOT, "analytics_zoo_tpu", "**",
                                       "*.py"), recursive=True):
        with open(path) as f:
            if "TraceAnnotation" in f.read():
                made.append(os.path.relpath(path, ROOT))
    assert made == ["analytics_zoo_tpu/observability/tracing.py"]


# --- PERF.md's span table and the code name the same spans ---------------

SPAN_CALL = re.compile(
    r"(?:\btrace|\bphase|\bmark(?:_request)?)\(\s*f?[\"']([A-Za-z0-9_.]+)")


def spans_in_the_code():
    names = set()
    for path in glob.glob(os.path.join(ROOT, "analytics_zoo_tpu", "**",
                                       "*.py"), recursive=True):
        with open(path) as f:
            names |= set(SPAN_CALL.findall(f.read()))
    return names


def spans_in_perf_md():
    """The names in backticks in the first column of the table under
    the heading "Spans and counts", a count in brackets cut off."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    section = text.split("### Spans and counts", 1)[1].split("\n#", 1)[0]
    rows = [r for r in section.splitlines() if r.startswith("| `")]
    assert rows, "PERF.md: no span table under 'Spans and counts'"
    return {name.split("[")[0] for row in rows
            for name in re.findall(r"`([^`]+)`", row.split("|")[1])}


def test_perf_md_names_every_span_and_no_other():
    code, doc = spans_in_the_code(), spans_in_perf_md()
    assert {"generation.round", "generation.decode", "spmd.input_wait",
            "estimator.fit", "serving.http_request", "cpu.loop",
            "cpu.handler", "cpu.client"} <= code
    assert code - doc == set(), "spans of the code PERF.md does not name"
    assert doc - code == set(), "spans PERF.md names that no code opens"
