"""Profiling plane (observability/profiling.py): abstract signatures
and the compile-forensics differ, the dispatch ledger (instrument /
record_work / budgets), MFU math against the analytic FLOPs models,
the `recompile_storm` alert under poisoned-clock replay, the fully
armed engine composition keeping ``decode_compile_count == 1`` with
the ledger live, and the export surfaces: GET /dispatch, the /stats
block, timeline pid 8, and flight-bundle embedding.

TP is the one axis absent from the composition test here — the host
KV tier is OFF under tensor parallelism, so the two cannot share one
engine; the tp × (prefix × chunked × int8 × speculation) composition
is pinned by tests/test_distributed_serving.py instead.
"""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability import history, profiling
from analytics_zoo_tpu.observability.alerts import (
    AlertEngine,
    builtin_rules,
)
from analytics_zoo_tpu.observability.profiling import (
    DISPATCH_FAMILIES,
    CausalLMFlops,
    abstract_signature,
    diff_signatures,
    train_step_flops,
)
from analytics_zoo_tpu.observability.registry import get_registry

T0 = 1_700_000_000.0


@pytest.fixture(autouse=True)
def clean_ledger():
    """The ledger is process-global (every engine in the session feeds
    it); each test here asserts exact counts, so both sides reset."""
    profiling.reset_profiling()
    yield
    profiling.reset_profiling()


@pytest.fixture(scope="module")
def lm():
    from analytics_zoo_tpu.serving.generation import CausalLM
    model = CausalLM(vocab=31, hidden_size=16, n_head=2, n_block=1,
                     intermediate_size=32, max_position_len=128)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


# ----------------------------------------------------------------------
# abstract signatures + the differ
# ----------------------------------------------------------------------

def test_abstract_signature_paths_and_leaves():
    sig = abstract_signature(
        ({"w": jnp.zeros((2, 3), jnp.float32)},
         jnp.zeros((4,), jnp.int32), 7, 0.5, "greedy"),
        argnames=("params", "tokens", "k", "temp", "mode"))
    m = dict(sig)
    assert m["params['w']"] == ("array", (2, 3), "float32")
    assert m["tokens"] == ("array", (4,), "int32")
    # python scalars abstract by TYPE only — changing the value of a
    # weak-typed scalar does not fork a jit cache entry
    assert m["k"] == ("py", "int")
    assert m["temp"] == ("py", "float")
    assert m["mode"] == ("static", "'greedy'")


def test_diff_names_exact_changed_added_removed_leaves():
    old = abstract_signature(
        (jnp.zeros((1, 16), jnp.int32), jnp.zeros((8,), jnp.float32)),
        argnames=("tokens", "scale"))
    new = abstract_signature(
        (jnp.zeros((1, 32), jnp.int32), jnp.zeros((8,), jnp.float16)),
        argnames=("tokens", "scale"))
    d = {e["path"]: e for e in diff_signatures(old, new)}
    assert d["tokens"] == {"path": "tokens", "old": "int32[1,16]",
                           "new": "int32[1,32]"}
    assert d["scale"] == {"path": "scale", "old": "float32[8]",
                          "new": "float16[8]"}
    # added / removed leaves carry None on the missing side
    grown = old + (("extra", ("array", (2,), "int8")),)
    add = diff_signatures(old, grown)
    assert add == [{"path": "extra", "old": None, "new": "int8[2]"}]
    rem = diff_signatures(grown, old)
    assert rem == [{"path": "extra", "old": "int8[2]", "new": None}]
    assert diff_signatures(old, old) == []


def test_unknown_family_rejected():
    with pytest.raises(ValueError, match="unknown dispatch family"):
        profiling.instrument("mystery", lambda x: x)
    with pytest.raises(ValueError):
        profiling.record_work("mystery", 0.1)
    assert "decode" in DISPATCH_FAMILIES


# ----------------------------------------------------------------------
# induced recompile: the forensics log names the exact leaf
# ----------------------------------------------------------------------

def test_induced_recompile_event_names_the_exact_leaf():
    """A novel decode-shaped signature produces a compile event whose
    diff names the changed leaf — path, old shape/dtype, new
    shape/dtype — with the callsite and a positive compile wall."""
    jfn = jax.jit(lambda tokens: tokens * 2)
    fn = profiling.instrument("decode", jfn, argnames=("tokens",))
    profiling.declare_expected("decode", 1)
    fn(jnp.zeros((4,), jnp.int32))
    fn(jnp.zeros((4,), jnp.int32))          # warm: same signature
    events = profiling.compile_events()
    assert len(events) == 1 and "diff" not in events[0]
    snap = profiling.ledger_snapshot()["families"]["decode"]
    assert snap["calls"] == 2 and snap["compile_count"] == 1
    assert snap["over_budget"] is False
    # the wrapper keeps the REAL jit cache visible to the pins
    assert fn._cache_size() == 1

    fn(jnp.zeros((5,), jnp.int32))          # the induced recompile
    events = profiling.compile_events()
    assert len(events) == 2
    ev = events[-1]
    assert ev["family"] == "decode" and ev["n"] == 2
    assert ev["compile_s"] > 0.0
    assert "test_profiling.py" in ev["callsite"]
    assert ev["diff"] == [{"path": "tokens", "old": "int32[4]",
                           "new": "int32[5]"}]
    assert fn._cache_size() == 2
    snap = profiling.ledger_snapshot()["families"]["decode"]
    assert snap["over_budget"] is True      # budget was 1 variant
    assert snap["signatures"] == 2
    # arg bytes accrued per call from the signature's array leaves
    assert snap["bytes_total"] == 4 * 4 + 4 * 4 + 5 * 4


def test_weak_scalar_value_change_is_not_a_compile():
    """Python-scalar args abstract by type: new VALUES of weak-typed
    scalars neither fork the real jit cache nor the forensics log."""
    jfn = jax.jit(lambda x, t: x * t)
    fn = profiling.instrument("decode", jfn, argnames=("x", "t"))
    fn(jnp.zeros((2,), jnp.float32), 0.5)
    fn(jnp.zeros((2,), jnp.float32), 0.9)
    assert fn._cache_size() == 1
    assert len(profiling.compile_events()) == 1


# ----------------------------------------------------------------------
# a signature is derived for a call that compiled, and for no other
# ----------------------------------------------------------------------

@pytest.fixture
def derivations(monkeypatch):
    """How often the wrapper walked its arguments' leaves."""
    calls = []
    real = profiling.abstract_signature

    def counting(args, argnames=None):
        calls.append(len(args))
        return real(args, argnames)

    monkeypatch.setattr(profiling, "abstract_signature", counting)
    return calls


def tree(n, width=4):
    return {f"w{i}": jnp.zeros((width,), jnp.float32) for i in range(n)}


@pytest.mark.parametrize("warm_calls", [1, 7])
def test_signature_derived_once_a_compiled_variant(derivations,
                                                   warm_calls):
    """A warm call of a jitted family costs two reads of the jit cache's
    size and two counters, however many leaves its arguments have; each
    variant that compiles is described once."""
    fn = profiling.instrument(
        "decode", jax.jit(lambda params, x: x + params["w0"].sum()),
        argnames=("params", "x"))
    params = tree(150)
    for _ in range(1 + warm_calls):
        fn(params, jnp.zeros((4,), jnp.float32))
    assert len(derivations) == 1
    fn(params, jnp.zeros((5,), jnp.float32))     # a second variant
    for _ in range(warm_calls):
        fn(params, jnp.zeros((5,), jnp.float32))
        fn(params, jnp.zeros((4,), jnp.float32))  # and the first, warm
    assert len(derivations) == 2
    events = profiling.compile_events()
    assert [e["n"] for e in events] == [1, 2]
    assert events[1]["diff"] == [{"path": "x", "old": "float32[4]",
                                  "new": "float32[5]"}]
    snap = profiling.ledger_snapshot()["families"]["decode"]
    assert snap["calls"] == 2 + 3 * warm_calls
    assert snap["compile_count"] == 2 and snap["signatures"] == 2
    assert fn._cache_size() == 2


@pytest.mark.parametrize("counter", [
    "dispatch_calls_total", "dispatch_decode_calls_total",
    "compile_events_total", "compile_seconds_total"])
def test_ledger_counters_read_as_before(derivations, counter):
    """Every call counts; only the compiling ones add compile events
    and compile seconds (what the benchmark's `window_compile_s.*`
    reads: 0 over a window of warm calls)."""
    reg = get_registry()
    fn = profiling.instrument("decode", jax.jit(lambda x: x * 2),
                              argnames=("x",))
    fn(jnp.zeros((3,), jnp.int32))
    before = reg.counter(counter).value
    for _ in range(5):
        fn(jnp.zeros((3,), jnp.int32))
    grew = reg.counter(counter).value - before
    assert grew == (5 if "calls" in counter else 0)
    fn(jnp.zeros((6,), jnp.int32))
    assert reg.counter(counter).value - before - grew > 0


@pytest.mark.parametrize("second", ["same_shapes", "other_shapes"])
def test_second_wrapper_of_a_family(derivations, second):
    """Two engines in one process wrap one family each: the second's
    first call compiles ITS jit, so it is described; the family logs a
    compile event only for a signature it has not seen."""
    one = profiling.instrument("decode", jax.jit(lambda x: x + 1),
                               argnames=("x",))
    two = profiling.instrument("decode", jax.jit(lambda x: x + 2),
                               argnames=("x",))
    one(jnp.zeros((4,), jnp.int32))
    two(jnp.zeros((4 if second == "same_shapes" else 8,), jnp.int32))
    two(jnp.zeros((4 if second == "same_shapes" else 8,), jnp.int32))
    assert len(derivations) == 2
    assert len(profiling.compile_events()) == (
        1 if second == "same_shapes" else 2)
    snap = profiling.ledger_snapshot()["families"]["decode"]
    assert snap["calls"] == 3
    assert snap["bytes_total"] == (48 if second == "same_shapes" else 80)
    assert one._cache_size() == 1 and two._cache_size() == 1


def test_a_callable_without_a_jit_cache_is_described_every_call(
        derivations):
    """Nothing cheaper to ask than the arguments themselves."""
    fn = profiling.instrument("decode", lambda x: x, argnames=("x",))
    assert not hasattr(fn, "_cache_size")
    for n in (4, 4, 5):
        fn(np.zeros((n,), np.int32))
    assert len(derivations) == 3
    assert len(profiling.compile_events()) == 2


# ----------------------------------------------------------------------
# MFU accounting
# ----------------------------------------------------------------------

def test_record_work_mfu_and_metrics():
    prev = OrcaContext.hardware_peak_flops
    OrcaContext.hardware_peak_flops = 1000.0
    try:
        reg = get_registry()
        c0 = reg.counter("model_flops_total").value
        profiling.record_work("decode", 2.0, tokens=10, flops=1000.0)
        snap = profiling.ledger_snapshot()
        # 1000 FLOPs over 2 s against a 1000 FLOP/s peak = 0.5
        assert snap["mfu"]["decode"] == 0.5
        assert snap["mfu"]["overall"] == 0.5
        assert snap["peak_flops"] == 1000.0
        fam = snap["families"]["decode"]
        assert fam["tokens_total"] == 10 and fam["wall_s"] == 2.0
        assert fam["model_flops_total"] == 1000.0
        assert reg.metrics()["mfu_decode"].value == 0.5
        assert reg.counter("model_flops_total").value == c0 + 1000.0
        # prefill MFU spans both prefill families' flops AND wall
        profiling.record_work("prefill", 1.0, tokens=4, flops=250.0)
        profiling.record_work("chunk_prefill", 1.0, tokens=4,
                              flops=250.0)
        assert profiling.ledger_snapshot()["mfu"]["prefill"] == 0.25
        # zero-flops families contribute no wall to the overall ratio
        profiling.record_work("copy_block", 100.0)
        assert profiling.ledger_snapshot()["mfu"]["overall"] == 0.375
    finally:
        OrcaContext.hardware_peak_flops = prev


def test_peak_flops_knob_validation_and_default():
    prev = OrcaContext.hardware_peak_flops
    try:
        OrcaContext.hardware_peak_flops = None
        assert profiling.peak_flops() == profiling.DEFAULT_PEAK_FLOPS
        OrcaContext.hardware_peak_flops = 275e12
        assert profiling.peak_flops() == 275e12
        with pytest.raises(ValueError):
            OrcaContext.hardware_peak_flops = -1.0
    finally:
        OrcaContext.hardware_peak_flops = prev


def test_causal_lm_flops_closed_form():
    f = CausalLMFlops(vocab=10, hidden_size=4, n_block=2,
                      intermediate_size=8)
    H, I, V = 4, 8, 10
    per_tok = 2 * (2 * H * 3 * H + 2 * H * H + 2 * H * I + 2 * I * H) \
        + 2 * H * V
    assert f.matmul_per_token == per_tok
    # one token at context 1: matmul + one attention read
    assert f.prefill(1) == per_tok + 2 * 4.0 * 1 * H
    assert f.prefill(0) == 0.0 and f.decode(0, 99.0) == 0.0
    # chunked prefill is exactly additive: chunk boundaries never
    # change the total (the invariant chunk accounting relies on)
    assert f.prefill(8) == f.prefill(4) + f.prefill(4, ctx_start=4)
    # a width-1 verify row IS a decode step
    assert f.verify(3, 1, 20.0) == f.decode(3, 20.0)
    assert f.decode(2, 16.0) == 2 * (per_tok + 2 * 4.0 * 16.0 * H)

    from analytics_zoo_tpu.serving.generation import CausalLM
    m = CausalLM(vocab=10, hidden_size=4, n_head=2, n_block=2,
                 intermediate_size=8, max_position_len=32)
    assert CausalLMFlops.from_model(m).matmul_per_token == per_tok


def test_train_step_flops_6p_2p():
    assert train_step_flops(1000, 32) == 6.0 * 1000 * 32
    assert train_step_flops(1000, 32, train=False) == 2.0 * 1000 * 32


# ----------------------------------------------------------------------
# recompile_storm: deterministic fire/resolve under poisoned clocks
# ----------------------------------------------------------------------

def _storm_samples():
    """compile_events_total ramping 1/s for 25 s (slope 1.0 ≫ 0.2),
    then flat for 45 s (trailing-window slope decays through the 0.05
    clear line)."""
    vals = [float(min(i, 24)) for i in range(70)]
    return [{"ts": T0 + i, "proc": "p0", "seq": i + 1,
             "counters": {"compile_events_total": v}, "gauges": {}}
            for i, v in enumerate(vals)]


def test_recompile_storm_fires_and_resolves_replay_deterministic(
        monkeypatch):
    samples = _storm_samples()

    def boom(*_a, **_k):
        raise AssertionError("clock read inside the evaluation path")
    monkeypatch.setattr(time, "time", boom)
    monkeypatch.setattr(time, "monotonic", boom)
    monkeypatch.setattr(time, "perf_counter", boom)
    outs = []
    for _ in range(2):
        verdict = AlertEngine(builtin_rules()).evaluate(samples)
        outs.append(json.dumps(verdict, sort_keys=True))
    assert outs[0] == outs[1], "replay must be byte-identical"
    storm = [e for e in json.loads(outs[0])["events"]
             if e["rule"] == "recompile_storm"]
    assert [e["state"] for e in storm] == ["firing", "resolved"]
    fired, resolved = storm
    assert fired["severity"] == "page"
    assert fired["value"] > 0.2            # the compiles/s slope
    assert resolved["ts"] > fired["ts"]


def test_recompile_storm_ignores_warmup_burst():
    """A one-shot warmup burst (an engine compiling its two cold
    programs at startup, then steady zero) never pages — the step's
    least-squares slope decays through min_slope before for_s is up."""
    vals = [0.0] + [2.0] * 69
    samples = [{"ts": T0 + i, "proc": "p0", "seq": i + 1,
                "counters": {"compile_events_total": v}, "gauges": {}}
               for i, v in enumerate(vals)]
    events = AlertEngine(builtin_rules()).evaluate(samples)["events"]
    assert not [e for e in events if e["rule"] == "recompile_storm"]


# ----------------------------------------------------------------------
# the fully armed composition: ledger + everything, one decode program
# ----------------------------------------------------------------------

def test_fully_armed_composition_decode_compiles_once(lm, tmp_path):
    """prefix caching × chunked prefill × int8 KV × speculation × host
    KV tier × SLO judging × watchdog × history recorder × dispatch
    ledger × blame plane × tail exemplars: the decode pin holds, the
    ledger agrees with it, and the compile budget is respected (tp
    rides in tests/test_distributed_serving.py — host tier is off
    under tp)."""
    from analytics_zoo_tpu.observability import blame
    from analytics_zoo_tpu.observability.exemplars import (
        reset_exemplar_store,
    )
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    model, params = lm
    tracker = blame.reset_blame_tracker()
    reset_exemplar_store()
    base_violations = tracker._c_violations.value
    prev_slo = OrcaContext.slo_targets
    prev_wd = OrcaContext.watchdog_deadline_s
    prev_mem = OrcaContext.memory_sample_interval_s
    prev_dir = OrcaContext.observability_dir
    prev_int = OrcaContext.metrics_history_interval_s
    OrcaContext.slo_targets = {"ttft_s": 60.0, "e2e_s": 600.0}
    OrcaContext.watchdog_deadline_s = 600.0
    OrcaContext.memory_sample_interval_s = 0.0
    OrcaContext.observability_dir = str(tmp_path / "obs")
    OrcaContext.metrics_history_interval_s = 0.05
    history.reset_recorder()
    try:
        engine = GenerationEngine(model, params, max_slots=4,
                                  block_size=8, max_context=64,
                                  cache_dtype=jnp.float16,
                                  kv_quantization="int8",
                                  prefix_caching=True,
                                  chunked_prefill=True,
                                  speculative_decoding=True,
                                  speculative_k=4,
                                  kv_host_tier=1 << 20)
        engine.warmup()
        assert engine.watchdog is not None
        rng = np.random.default_rng(7)
        shared = list(rng.integers(0, 31, 16))
        streams = [engine.submit(
            shared + list(rng.integers(0, 31, 1 + j)),
            max_new_tokens=5) for j in range(5)]
        engine.run_until_idle()
        assert all(len(s.tokens()) == 5 for s in streams)
        assert engine.decode_compile_count == 1, \
            "decode recompiled with the full stack + ledger armed"
        snap = profiling.ledger_snapshot()
        fams = snap["families"]
        assert fams["decode"]["compile_count"] == 1
        assert fams["decode"]["over_budget"] is False
        assert fams["chunk_prefill"]["over_budget"] is False
        assert fams["decode"]["calls"] >= 1
        assert fams["decode"]["tokens_total"] >= 1
        assert fams["decode"]["model_flops_total"] > 0.0
        assert snap["mfu"]["decode"] > 0.0
        # every compiled program this engine built is in the forensics
        # log with its signature; nothing diffed for decode
        dec = [e for e in snap["compile_events"]
               if e["family"] == "decode"]
        assert len(dec) == 1 and "diff" not in dec[0]
        # the blame plane rode the whole composed run: every finished
        # request got an additive ledger, the tail got exemplared, and
        # none of it cost a recompile (the pin above)
        payload = blame.blame_payload()
        assert payload["requests_in_window"] == 5
        assert tracker._c_violations.value == base_violations
        assert payload["dominant_tail_phase"] is not None
        from analytics_zoo_tpu.observability.exemplars import (
            get_exemplar_store,
        )
        assert get_exemplar_store().count() >= 1
    finally:
        OrcaContext.slo_targets = prev_slo
        OrcaContext.watchdog_deadline_s = prev_wd
        OrcaContext.memory_sample_interval_s = prev_mem
        OrcaContext.observability_dir = prev_dir
        OrcaContext.metrics_history_interval_s = prev_int
        history.reset_recorder()


# ----------------------------------------------------------------------
# export surfaces: /dispatch, /stats, timeline pid 8, flight bundles
# ----------------------------------------------------------------------

def _get(srv, path):
    try:
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}{path}", timeout=30) as r:
            return r.read().decode()
    except urllib.error.HTTPError as e:
        return e.read().decode()


def test_dispatch_endpoint_and_stats_block(lm):
    from analytics_zoo_tpu.serving import ServingServer
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64)
    srv = None
    try:
        # the server owns the engine loop thread; tokens() blocks on it
        srv = ServingServer(generation_engine=engine).start()
        s = engine.submit([3, 1, 4, 1, 5], max_new_tokens=4)
        assert len(s.tokens()) == 4
        body = json.loads(_get(srv, "/dispatch"))
        assert body["peak_flops"] == profiling.peak_flops()
        assert body["families"]["decode"]["calls"] >= 1
        assert body["families"]["prefill"]["compile_count"] >= 1
        assert body["compile_events"][0]["signature"]
        assert body["compile_events_total"] >= 2   # prefill + decode
        assert body["compile_seconds_total"] > 0.0
        stats = json.loads(_get(srv, "/stats"))
        assert "decode" in stats["dispatch"]["families"]
        # the heavyweight event log stays off the /stats summary
        assert "compile_events" not in stats["dispatch"]
    finally:
        if srv is not None:
            srv.stop()


def test_timeline_pid8_dispatch_track():
    from analytics_zoo_tpu.observability import timeline
    jfn = profiling.instrument("decode", jax.jit(lambda x: x + 1),
                               argnames=("x",))
    jfn(jnp.zeros((3,), jnp.int32))
    jfn(jnp.zeros((4,), jnp.int32))         # → a diffed compile event
    profiling.record_work("decode", 0.01, tokens=3)
    doc = timeline.export_timeline()
    ev = doc["traceEvents"]
    names = {e["name"] for e in ev if e.get("ph") == "M"
             and e["name"] == "process_name"
             and e["pid"] == timeline.PID_DISPATCH}
    assert names, "pid 8 (dispatch) missing its process_name meta"
    slices = [e for e in ev if e.get("cat") == "dispatch"
              and e.get("ph") == "X"]
    assert any(e["name"] == "decode" and e["pid"] == timeline.PID_DISPATCH
               for e in slices)
    compiles = [e for e in ev if e.get("cat") == "dispatch"
                and e.get("ph") == "i" and e["name"] == "compile"]
    assert compiles, "compile instants missing from the track"
    assert any("x: int32[3] -> int32[4]" in e["args"].get("diff", "")
               for e in compiles)


def test_flight_bundle_embeds_dispatch_and_compile_events(tmp_path):
    from analytics_zoo_tpu.observability import flight_recorder
    prev_dir = OrcaContext.observability_dir
    OrcaContext.observability_dir = str(tmp_path / "obs")
    try:
        jfn = profiling.instrument("decode", jax.jit(lambda x: x + 1),
                                   argnames=("x",))
        jfn(jnp.zeros((3,), jnp.int32))
        profiling.record_work("decode", 0.02, tokens=1, flops=10.0)
        path = flight_recorder.dump("profiling-test")
        assert path is not None
        bundle = json.load(open(path))
        assert bundle["dispatch"]["families"]["decode"]["calls"] == 1
        assert "compile_events" not in bundle["dispatch"]
        assert bundle["compile_events"][0]["family"] == "decode"
        # an empty ledger embeds an empty block, not a crash
        profiling.reset_profiling()
        bundle2 = json.load(open(flight_recorder.dump("empty")))
        assert bundle2["dispatch"] == {}
        assert bundle2["compile_events"] == []
    finally:
        OrcaContext.observability_dir = prev_dir


def test_recompile_breadcrumb_lands_on_flight_ring():
    from analytics_zoo_tpu.observability import flight_recorder
    flight_recorder.clear_ring()
    jfn = profiling.instrument("decode", jax.jit(lambda x: x * 1),
                               argnames=("x",))
    jfn(jnp.zeros((3,), jnp.int32))
    jfn(jnp.zeros((6,), jnp.int32))
    crumbs = [e for e in flight_recorder.ring_contents()
              if e["kind"] == "compile"]
    assert len(crumbs) == 1, "only the SECOND program leaves a crumb"
    assert crumbs[0]["path"] == "x"
    assert crumbs[0]["old"] == "int32[3]"
    assert crumbs[0]["new"] == "int32[6]"


def test_estimator_train_step_feeds_the_ledger():
    """The SPMD engine's fenced step samples land under train_step
    with 6·P-per-token FLOPs — MFU > 0 after a short fit."""
    import flax.linen as nn

    from analytics_zoo_tpu.orca.learn import Estimator

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    est = Estimator.from_flax(Tiny(), loss="mse", optimizer="sgd",
                              learning_rate=1e-2)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64, 1)).astype(np.float32)
    est.fit({"x": x, "y": y}, epochs=2, batch_size=8)
    snap = profiling.ledger_snapshot()["families"]
    assert "train_step" in snap
    ts = snap["train_step"]
    assert ts["calls"] >= 1 and ts["compile_count"] >= 1
    assert ts["model_flops_total"] > 0.0 and ts["wall_s"] > 0.0
    assert ts["tokens_total"] > 0
    # MFU is computed live against the knob: a CPU-tiny model rounds
    # to 0 against the default 1 TFLOP/s, so read it against 1 FLOP/s
    prev = OrcaContext.hardware_peak_flops
    OrcaContext.hardware_peak_flops = 1.0
    try:
        assert profiling.ledger_snapshot()["mfu"]["overall"] > 0.0
    finally:
        OrcaContext.hardware_peak_flops = prev
