"""Distributed serving tests (serving/distributed/): tensor-parallel
decode parity on a virtual CPU mesh, sharded-pool composition with
int8 KV + prefix caching, generated-suffix prefix commits on finish,
and the replica router — least-loaded admission through ServingServer,
drain → 503 + Retry-After, death-requeue with a sticky request id, and
the zero-recompile contract with the whole stack armed."""

import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.distributed import (
    ReplicaRouter,
    TensorParallelPlacement,
)
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    GenerationEngine,
)

VOCAB = 61


@pytest.fixture(scope="module", autouse=True)
def tp_mesh():
    """Module-wide dp x tp mesh (8 virtual CPU devices -> 4 x 2); the
    tensor-parallel engines shard over its "tp" axis, the plain ones
    ignore it."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    stop_orca_context()
    mesh = init_orca_context(cluster_mode="local",
                             mesh_shape={"tp": 2})
    yield mesh
    stop_orca_context()


@pytest.fixture(scope="module")
def lm():
    model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=256)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


def _assert_greedy(model, params, prompt, out):
    """`out` must be the greedy full-recompute decode of `prompt`
    (teacher forcing over the completed sequence — see
    tests/test_generation.py)."""
    assert out, "no tokens generated"
    seq = list(prompt) + list(out)
    logits, _, _ = model.apply(
        {"params": params}, jnp.asarray(seq)[None],
        jnp.arange(len(seq))[None], token_mask=jnp.ones((1, len(seq))))
    want = np.argmax(np.asarray(logits[0]), axis=-1)
    for i, tok in enumerate(out):
        assert tok == want[len(prompt) + i - 1], (
            f"token {i}: engine {tok} != full-recompute "
            f"{want[len(prompt) + i - 1]}")


def _run(engine, prompts, max_new=10):
    streams = [engine.submit(p, max_new_tokens=max_new,
                             temperature=0.0) for p in prompts]
    engine.run_until_idle()
    return [s.tokens() for s in streams]


# ----------------------------------------------------------------------
# tensor-parallel decode
# ----------------------------------------------------------------------

def test_tp_decode_bit_identical_to_single_device(lm):
    """The acceptance gate: tp=2 greedy decode must match the
    single-device engine token-for-token, with exactly one compiled
    decode program and the params/pool actually sharded."""
    model, params = lm
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, VOCAB, n)) for n in (9, 6, 13)]

    ref = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64, registry=MetricsRegistry())
    want = _run(ref, prompts)

    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64, tensor_parallel=2,
                           registry=MetricsRegistry())
    assert eng.tensor_parallel == 2
    spec = str(eng.params["block_0_qkv"]["kernel"].sharding.spec)
    assert "tp" in spec, f"qkv kernel not column-sharded: {spec}"
    # vocab 61 is odd: lm_head must DEGRADE to replicated, not fail
    head = str(eng.params["lm_head"]["kernel"].sharding.spec)
    assert "tp" not in head, f"non-divisible vocab head sharded: {head}"
    assert "tp" in str(eng.cache.kv.sharding.spec)

    got = _run(eng, prompts)
    assert got == want, "tp=2 diverged from the single-device engine"
    assert eng.decode_compile_count == 1
    # the explicit collective: gathered pool matches the replicated
    # pool's geometry (and the per-shard residency math holds)
    gathered = eng._tp.gather_kv_heads(eng.cache.kv)
    assert gathered.shape == ref.cache.kv.shape
    assert (eng._tp.per_device_kv_bytes(eng.cache)
            == eng.cache.kv.nbytes // 2)
    for p, o in zip(prompts, got):
        _assert_greedy(model, params, p, o)


def test_tp_placement_validates_geometry(lm):
    import types
    model, params = lm
    with pytest.raises(ValueError, match="degree must be >= 2"):
        TensorParallelPlacement.build(1, model)
    with pytest.raises(ValueError, match="'tp' axis"):
        TensorParallelPlacement.build(4, model)   # mesh axis is 2
    with pytest.raises(ValueError, match="not divisible"):
        TensorParallelPlacement.build(
            2, types.SimpleNamespace(n_head=3))


def test_tp_composes_with_int8_and_prefix_cache(lm):
    """paged + int8 KV + prefix cache + chunked prefill under tp=2:
    sharded pool, replicated scales, greedy output still exact, one
    decode program, and the radix tree still hits."""
    model, params = lm
    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64, tensor_parallel=2,
                           cache_dtype=jnp.float16,
                           kv_quantization="int8",
                           prefix_caching=True, chunked_prefill=True,
                           registry=MetricsRegistry())
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, VOCAB, 16))
    p1 = shared + list(rng.integers(0, VOCAB, 3))
    p2 = shared + list(rng.integers(0, VOCAB, 5))
    (o1,) = _run(eng, [p1], max_new=6)
    (o2,) = _run(eng, [p2], max_new=6)
    _assert_greedy(model, params, p1, o1)
    _assert_greedy(model, params, p2, o2)
    assert eng.decode_compile_count == 1
    assert eng.prefix_cache.hit_rate() > 0
    assert "tp" in str(eng.cache.kv.sharding.spec)
    # int8 scale vectors replicate (their amax crosses the head shard)
    assert "tp" not in str(eng.cache.kv_scale.sharding.spec)


# ----------------------------------------------------------------------
# satellite: generated-suffix commit on finish
# ----------------------------------------------------------------------

def test_finished_generation_commits_suffix_blocks(lm):
    """Two-turn conversation: turn 2's prompt extends turn 1's
    prompt+OUTPUT, so the lookup must hit the blocks covering the
    generated suffix — not just the prompt — proving _finish publishes
    them (block size 8: turn 1 covers 31 committed tokens -> 3 full
    blocks = 24 hit tokens on turn 2)."""
    model, params = lm
    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64, prefix_caching=True,
                           chunked_prefill=True,
                           registry=MetricsRegistry())
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(0, VOCAB, 16))
    (turn1,) = _run(eng, [prompt], max_new=16)
    _assert_greedy(model, params, prompt, turn1)

    before = eng.prefix_cache._c_hit_tokens.value
    prompt2 = prompt + turn1 + list(rng.integers(0, VOCAB, 2))
    (turn2,) = _run(eng, [prompt2], max_new=4)
    hit = eng.prefix_cache._c_hit_tokens.value - before
    assert hit >= 24, (
        f"turn 2 hit only {hit} tokens — the generated suffix was "
        "not committed on finish")
    _assert_greedy(model, params, prompt2, turn2)


# ----------------------------------------------------------------------
# replica router
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def router(lm):
    model, params = lm
    r = ReplicaRouter.build(model, params, n_replicas=2, warmup=False,
                            max_slots=4, block_size=8, max_context=64)
    yield r
    r.stop()


@pytest.fixture(scope="module")
def server(router):
    from analytics_zoo_tpu.serving import ServingServer
    srv = ServingServer(router=router).start()
    yield srv
    srv.stop()


def test_router_requires_distinct_registries(lm):
    model, params = lm
    reg = MetricsRegistry()
    engines = [GenerationEngine(model, params, max_slots=2,
                                block_size=8, max_context=64,
                                registry=reg) for _ in range(2)]
    with pytest.raises(ValueError, match="own MetricsRegistry"):
        ReplicaRouter(engines)
    for e in engines:
        e.stop()


@pytest.mark.slow   # ~11s warm (PR 19 budget trim): sibling tier-1
# coverage: test_replica_death_mid_stream_requeues_once and
# test_all_draining_sheds_503_with_retry_after keep the router serve
# path in the gate, and test_router_zero_recompile_fully_armed keeps
# routed generation end-to-end; the load-spread statistics move out.
def test_router_serves_and_spreads_load(lm, router, server):
    from analytics_zoo_tpu.serving import InputQueue
    from urllib.request import urlopen

    model, params = lm
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, VOCAB, 5 + j)) for j in range(6)]
    outs = {}

    def go(j):
        iq = InputQueue(server.host, server.port)
        outs[j] = (prompts[j],
                   iq.generate_tokens(prompts[j], max_new_tokens=6))

    threads = [threading.Thread(target=go, args=(j,))
               for j in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for p, o in outs.values():
        _assert_greedy(model, params, p, o)

    stats = json.loads(urlopen(
        f"http://{server.host}:{server.port}/stats",
        timeout=10).read())
    rows = stats["router"]["replicas"]
    assert [r["replica"] for r in rows] == ["replica-0", "replica-1"]
    assert all(r["state"] == "active" for r in rows)
    assert sum(r["served"] for r in rows) >= 6
    # least-loaded + round-robin tie-break: an idle fleet must not
    # pile everything onto replica-0
    assert all(r["served"] > 0 for r in rows), rows
    assert stats["replicas"] == 2
    text = urlopen(f"http://{server.host}:{server.port}/metrics",
                   timeout=10).read().decode()
    for key in ("router_requests_total", "router_healthy_replicas",
                "replica_replica_0_served_total"):
        assert key in text, key


def test_all_draining_sheds_503_with_retry_after(router, server):
    from urllib.error import HTTPError
    from urllib.request import Request, urlopen

    router.drain()
    try:
        req = Request(
            f"http://{server.host}:{server.port}/generate",
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(HTTPError) as exc:
            urlopen(req, timeout=10)
        assert exc.value.code == 503
        assert float(exc.value.headers["Retry-After"]) > 0
        body = json.loads(exc.value.read())
        assert body["retry_after_s"] > 0
        assert "no active replica" in body["error"]
    finally:
        router.undrain()
    assert all(r.state == "active" for r in router.replicas)


def test_replica_death_mid_stream_requeues_once(lm, router, server):
    """A poisoned decode evicts the request with an ``error:`` reason
    on its serving replica; the RouterStream must continue it on the
    OTHER replica under the same request id, and the shared retry
    ledger must tick."""
    model, params = lm
    rng = np.random.default_rng(13)
    prompt = list(rng.integers(0, VOCAB, 9))
    retries = get_registry().counter("resilience_retries_total").value
    requeues = router._c_requeues.value
    prev = OrcaContext.fault_plan
    OrcaContext.fault_plan = {"faults": [
        {"site": "generation.decode", "at": 3,
         "action": "poison_request", "request_id": "victim-rq"}]}
    try:
        rs = router.submit(prompt, max_new_tokens=8,
                           request_id="victim-rq")
        first = rs.replica_name
        toks = rs.tokens()
    finally:
        OrcaContext.fault_plan = prev
    assert rs.request_id == "victim-rq"
    assert rs.replica_name != first, "not moved off the dead leg"
    assert rs.finish_reason == "length"
    _assert_greedy(model, params, prompt, toks)
    assert len(toks) == 8
    assert router._c_requeues.value == requeues + 1
    assert (get_registry().counter("resilience_retries_total").value
            == retries + 1)


def test_router_zero_recompile_fully_armed(lm, tmp_path):
    """decode_compiles == 1 PER REPLICA with router + tp=2 + prefix
    cache + chunked prefill + int8 KV + SLO targets + shedder +
    watchdog + metrics-history recorder/alert engine all armed — and
    it STAYS 1 when the durable-stream consumer path feeds the same
    router (the fully-loaded acceptance gate, streaming included)."""
    from analytics_zoo_tpu.observability import history
    model, params = lm
    prev_slo = OrcaContext.slo_targets
    prev_shed = OrcaContext.slo_shed_attainment
    prev_wd = OrcaContext.watchdog_deadline_s
    prev_mem = OrcaContext.memory_sample_interval_s
    prev_obs = OrcaContext.observability_dir
    prev_hist = OrcaContext.metrics_history_interval_s
    OrcaContext.slo_targets = {"ttft_s": 60.0, "e2e_s": 600.0}
    OrcaContext.slo_shed_attainment = 0.05
    OrcaContext.watchdog_deadline_s = 600.0
    OrcaContext.memory_sample_interval_s = 0.0
    OrcaContext.observability_dir = str(tmp_path / "obs")
    OrcaContext.metrics_history_interval_s = 0.05
    history.reset_recorder()
    try:
        engines = [
            GenerationEngine(model, params, max_slots=4, block_size=8,
                             max_context=64, tensor_parallel=2,
                             cache_dtype=jnp.float16,
                             kv_quantization="int8",
                             prefix_caching=True, chunked_prefill=True,
                             registry=MetricsRegistry())
            for _ in range(2)]
        r = ReplicaRouter(engines)
        rng = np.random.default_rng(17)
        streams = [r.submit(list(rng.integers(0, VOCAB, 8 + j)),
                            max_new_tokens=4)
                   for j in range(4)]
        r.run_until_idle()
        assert all(len(s.tokens()) == 4 for s in streams)
        for e in engines:
            assert e.decode_compile_count == 1, \
                "decode recompiled with the full stack armed"
        assert {s.replica_name for s in streams} == \
            {"replica-0", "replica-1"}
        # same router, durable-stream ingress: records consumed as a
        # group must ride the SAME compiled decode step
        import time

        from analytics_zoo_tpu.serving.codec import (decode_record,
                                                     encode_record)
        from analytics_zoo_tpu.serving.streaming import DurableStream
        jobs = DurableStream(tmp_path / "jobs", max_backlog=16)
        outs = DurableStream(tmp_path / "outs", max_backlog=16)
        for j in range(3):
            jobs.enqueue(encode_record(
                {"uri": f"s{j}",
                 "tokens": [int(t)
                            for t in rng.integers(0, VOCAB, 8 + j)],
                 "max_new_tokens": 4}))
        r.ensure_started()
        cons = r.consume_stream(jobs, out_stream=outs,
                                group="generate", consumer="g0",
                                poll_s=0.02)
        try:
            deadline = time.monotonic() + 60
            while len(outs.log) < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            cons.stop()
        assert cons.records_handled == 3 and cons.errors == 0
        assert jobs.stats()["groups"]["generate"]["lag"] == 0
        for rec in outs.dequeue("check", "c0", max_records=3):
            doc = decode_record(rec.payload)
            assert len(doc["tokens"]) == 4
            assert doc["request_id"].startswith("strm-jobs-")
        for e in engines:
            assert e.decode_compile_count == 1, \
                "stream consumption recompiled the decode step"
        jobs.close()
        outs.close()
        r.stop()
        # the recorder + alert engine actually ran in the hot loops
        rec = history.get_recorder()
        assert rec is not None and len(rec.tail()) >= 1, \
            "armed recorder never sampled in the engine loops"
        for e in engines:
            assert e.decode_compile_count == 1, \
                "metrics-history recording recompiled the decode step"
    finally:
        history.reset_recorder()
        OrcaContext.slo_targets = prev_slo
        OrcaContext.slo_shed_attainment = prev_shed
        OrcaContext.watchdog_deadline_s = prev_wd
        OrcaContext.memory_sample_interval_s = prev_mem
        OrcaContext.observability_dir = prev_obs
        OrcaContext.metrics_history_interval_s = prev_hist


@pytest.mark.parametrize("build, kw", [
    ("engine", dict(tensor_parallel=-1)),
    ("engine", dict(speculative_decoding=True, speculative_k=0)),
    ("engine", dict(prefix_caching=True, kv_host_tier=-1)),
    ("engine", dict(kv_quantization="int4")),
    ("router", dict(n_replicas=0)),
    ("router", dict(n_replicas=-2)),
], ids=lambda v: v if isinstance(v, str) else
    ",".join(f"{k}={x}" for k, x in v.items()))
def test_constructors_refuse_what_the_setters_refused(lm, build, kw):
    """A feature's value is validated where it arrives, the
    constructor: what a global's setter used to refuse raises the same
    ValueError there, before anything serves."""
    model, params = lm
    geometry = dict(max_slots=2, block_size=8, max_context=32)
    with pytest.raises(ValueError):
        if build == "engine":
            GenerationEngine(model, params, registry=MetricsRegistry(),
                             **geometry, **kw)
        else:
            ReplicaRouter.build(model, params, warmup=False,
                                **geometry, **kw)
