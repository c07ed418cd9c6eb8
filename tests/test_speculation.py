"""Speculative decoding (serving/generation/speculation.py + the
engine's verify-k integration): drafter determinism and edge cases,
greedy bit-exactness against the legacy decode across prefix-cache
hit/miss x int8 KV x chunked prefill, free-list rollback exactness
under mixed accept/reject traffic, preemption losslessness with draft
state attached, fault-site fallback, default-off parity, and the
zero-recompile contract with the whole stack armed at tp=2."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.observability import request_log
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    GenerationEngine,
    SpecState,
    Speculator,
    ngram_draft,
)
from analytics_zoo_tpu.serving.generation.scheduler import Sequence
from analytics_zoo_tpu.serving.generation.speculation import (
    COOLDOWN_MAX,
    COOLDOWN_START,
)

VOCAB = 29


@pytest.fixture(scope="module")
def lm():
    model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=256)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


def _cycle_params(params, perm):
    """Zero every block's output projection (identity residual) and
    wire embedding->head as the permutation map `perm`, making greedy
    decode a deterministic bigram cycle: argmax(next | t) == perm[t]
    at EVERY position regardless of context.  The compiled step still
    runs the full transformer (zeros multiply, they don't vanish), so
    engines driven with these params exercise the real dispatch."""
    p = jax.device_get(params)
    for b in range(2):
        for name in (f"block_{b}_proj", f"block_{b}_fc2"):
            p[name]["kernel"] = np.zeros_like(p[name]["kernel"])
            p[name]["bias"] = np.zeros_like(p[name]["bias"])
    p["position_embed"]["embedding"] = np.zeros_like(
        p["position_embed"]["embedding"])
    emb = np.zeros_like(p["token_embed"]["embedding"])
    head = np.zeros_like(p["lm_head"]["kernel"])
    for t in range(VOCAB):
        emb[t, t] = 1.0
        head[t, perm[t]] = 10.0
    p["token_embed"]["embedding"] = emb
    p["lm_head"]["kernel"] = head
    p["lm_head"]["bias"] = np.zeros_like(p["lm_head"]["bias"])
    return jax.tree_util.tree_map(jnp.asarray, p)


@pytest.fixture(scope="module")
def cyc(lm):
    model, params = lm
    perm = np.random.default_rng(0).permutation(VOCAB)
    return model, _cycle_params(params, perm), perm


def _chain(perm, start, n):
    out = [int(start)]
    for _ in range(n - 1):
        out.append(int(perm[out[-1]]))
    return out


@pytest.fixture(scope="module")
def spec_pair(cyc):
    """A legacy reference engine and a speculative engine, both with
    prefix caching + chunked prefill + int8 KV armed — shared by the
    parity / fault / request-log tests to amortize warmup compiles."""
    model, params, _perm = cyc
    kw = dict(max_slots=4, block_size=8, max_context=128,
              kv_quantization="int8", prefix_caching=True,
              chunked_prefill=True, prefill_token_budget=16)
    ref = GenerationEngine(model, params, registry=MetricsRegistry(),
                          speculative_decoding=False, **kw)
    eng = GenerationEngine(model, params, registry=MetricsRegistry(),
                          speculative_decoding=True, speculative_k=4,
                          **kw)
    ref.warmup()
    eng.warmup()
    return ref, eng


def _run(engine, prompts, max_new=24):
    streams = [engine.submit(p, max_new_tokens=max_new)
               for p in prompts]
    engine.run_until_idle()
    return streams, [s.tokens() for s in streams]


# ----------------------------------------------------------------------
# drafter: determinism + suffix-match edges
# ----------------------------------------------------------------------

def test_ngram_draft_matches_most_recent_occurrence():
    # suffix [7, 8] occurred twice; the MOST RECENT earlier match
    # (index 5) supplies the continuation, not the first one
    ctx = [7, 8, 1, 2, 3, 7, 8, 4, 5, 7, 8]
    assert ngram_draft(ctx, 3) == [4, 5, 7]
    # deterministic: same history, same proposal, every call
    assert ngram_draft(ctx, 3) == ngram_draft(ctx, 3)
    # k caps the proposal length
    assert ngram_draft(ctx, 1) == [4]
    # longest n-gram wins: [2, 7, 8] has no earlier occurrence but
    # [7, 8] does — the 2-gram drives
    assert ngram_draft([1, 2, 7, 8, 9, 9, 2, 7, 8], 2) == [9, 9]


def test_ngram_draft_no_match_is_k_zero():
    assert ngram_draft([1, 2, 3, 4, 5], 4) == []      # nothing repeats
    assert ngram_draft([1], 4) == []                  # history too short
    assert ngram_draft([], 4) == []
    assert ngram_draft([1, 2, 1, 2], 0) == []         # k = 0


def test_ngram_draft_clips_past_eos():
    # the matched continuation crosses eos: the draft keeps eos and
    # drops everything after it (drafting past the end of a sequence
    # is dead verify width)
    ctx = [3, 4, 9, 0, 1, 3, 4]
    assert ngram_draft(ctx, 4, eos_id=9) == [9]
    assert ngram_draft(ctx, 4, eos_id=None) == [9, 0, 1, 3]


def test_draft_for_caps_at_remaining_budget():
    spec = Speculator(4)
    seq = Sequence([5, 6, 5, 6, 5, 6], max_new_tokens=3)
    seq.spec = None
    # remaining 3 -> k_eff 2 (accepted + bonus never exceed the cap)
    assert len(spec.draft_for(seq)) <= 2
    seq2 = Sequence([5, 6, 5, 6], max_new_tokens=1)
    seq2.spec = None
    assert spec.draft_for(seq2) == []   # last token: decode normally


# ----------------------------------------------------------------------
# backoff + bucket geometry
# ----------------------------------------------------------------------

def test_spec_state_exponential_backoff():
    st = SpecState()
    widths = []
    for _ in range(7):
        st.record(4, 0)                 # fully rejected round
        widths.append(st.cooldown)
    assert widths == [COOLDOWN_START, 4, 8, 16, 32, 32, 32]
    assert widths[-1] == COOLDOWN_MAX
    st.record(4, 2)                     # ANY acceptance resets
    assert st.cooldown == 0 and st.penalty == 0
    assert st.rounds == 8 and st.proposed == 32 and st.accepted == 2


def test_speculator_bucket_geometry():
    assert Speculator(1).buckets == (1,)
    assert Speculator(4).buckets == (2, 4)
    assert Speculator(8).buckets == (2, 4, 8)
    assert Speculator(6).buckets == (2, 4, 6)
    s = Speculator(8)
    assert [s.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [2, 2, 4, 8, 8]
    with pytest.raises(ValueError, match="exceeds"):
        s.bucket_for(9)
    with pytest.raises(ValueError, match=">= 1"):
        Speculator(0)


# ----------------------------------------------------------------------
# engine: greedy bit-exactness vs legacy, fully composed
# ----------------------------------------------------------------------

def test_spec_stream_identical_to_legacy_composed(cyc, spec_pair):
    """The acceptance gate's core: token streams from the speculative
    engine equal the legacy engine's exactly, across prefix-cache MISS
    (first wave) and HIT (second wave) with int8 KV + chunked prefill
    armed — and acceptance actually happened (cycle traffic drafts
    perfectly), so the parity is not vacuous."""
    _model, _params, perm = cyc
    ref, eng = spec_pair
    rng = np.random.default_rng(3)
    shared = _chain(perm, 5, 16)
    prompts = [shared + _chain(perm, perm[shared[-1]], 4),
               shared + _chain(perm, 11, 4),
               list(rng.integers(0, VOCAB, 11)),     # adversarial lane
               _chain(perm, 20, 40)]                 # chunked prefill
    ref1, want = _run(ref, prompts)
    eng1, got = _run(eng, prompts)
    assert got == want
    # second wave: the shared prefix is now committed -> HIT path
    ref2, want2 = _run(ref, [shared + _chain(perm, 3, 2)], max_new=16)
    streams, got2 = _run(eng, [shared + _chain(perm, 3, 2)], max_new=16)
    assert got2 == want2
    assert eng.prefix_cache.hit_rate() > 0
    assert eng._c_spec_accepted.value > 0, "parity test never accepted"
    assert eng._c_spec_rounds.value > 0
    # the k+1 bonus: cycle lanes emit more tokens than verify rounds
    assert eng._c_spec_accepted.value > eng._c_spec_rounds.value
    # verify programs: one compiled family per pow2 bucket, decode
    # untouched
    assert eng.decode_compile_count == 1
    assert eng.spec_verify_compile_count == len(eng.speculation.buckets)
    # pow2-sampled lifecycle events, inside the bounded-record cap
    rec = request_log.get(streams[0].request_id)
    kinds = [e["kind"] for e in rec["events"]]
    assert "spec_propose" in kinds and "spec_accept" in kinds
    assert len(rec["events"]) <= request_log.MAX_EVENTS_PER_REQUEST
    # speculation-exact round accounting: every cleanly finished
    # request satisfies n_tokens == 1 + n_decode_rounds + n_spec_tokens
    # (the leading 1 is prefill's token; spec tokens are counted at
    # emission so an eos mid-burst is respected), and a SPEC lane
    # really used verify rounds — the invariant is not vacuous.  Only
    # this test's own requests: the log is the process's, and under
    # `--dist loadfile` an earlier file's preempted request (two
    # prefills, so one token more) may still be in it
    mine = {s.request_id for s in (*ref1, *eng1, *ref2, *streams)}
    finished = [r for r in request_log.records(None)
                if r["request_id"] in mine
                and r["status"] == "finished" and r["n_tokens"] > 0]
    assert finished
    for r in finished:
        assert r["n_tokens"] == 1 + r["n_decode_rounds"] \
            + r["n_spec_tokens"], r["request_id"]
    assert any(r["n_spec_rounds"] > 0 for r in finished)


@pytest.mark.slow   # ~8s warm (PR 19 budget trim): sibling tier-1
# coverage: test_spec_stream_identical_to_legacy_composed keeps
# accept/rollback output parity in the gate and
# test_spec_preemption_lossless keeps rollback-across-preemption;
# the exact per-round ledger accounting moves out.
def test_spec_rollback_ledger_exact_after_mixed_rounds(cyc):
    """100+ mixed accept/reject verify rounds, then drain: every
    speculative block came back through the free list — available ==
    capacity, zero occupancy, no leaked refcounts."""
    model, params, perm = cyc
    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=128, registry=MetricsRegistry(),
                           speculative_decoding=True, speculative_k=4)
    eng.warmup()
    rng = np.random.default_rng(9)
    wave = 0
    while eng._c_spec_rounds.value < 100:
        wave += 1
        assert wave < 40, "spec rounds not accumulating"
        prompts = [_chain(perm, int(rng.integers(VOCAB)), 12),  # accept
                   _chain(perm, int(rng.integers(VOCAB)), 12),
                   list(rng.integers(0, VOCAB, 8)) * 2,         # reject
                   list(rng.integers(0, VOCAB, 12))]
        _run(eng, prompts, max_new=20)
        alloc = eng.cache.allocator
        assert alloc.available() == alloc.capacity, f"wave {wave} leaked"
        assert alloc.occupancy() == 0.0
    rejected = eng._c_spec_proposed.value - eng._c_spec_accepted.value
    assert eng._c_spec_accepted.value > 0 and rejected > 0, \
        "ledger test needs BOTH accepted and rejected rounds"


def test_spec_preemption_lossless(cyc):
    """Cache pressure preempts speculating lanes mid-stream; drafts
    and speculative blocks roll back with the lane, recompute-on-resume
    restores it, and every stream still equals the model's greedy
    cycle.  (Sibling tier-1 coverage: the non-speculative version is
    tests/test_generation.py::test_preemption_under_cache_pressure...)"""
    model, params, perm = cyc
    # 9 allocatable blocks, 4 lanes wanting up to ~5 each + spec slack
    eng = GenerationEngine(model, params, max_slots=4, block_size=8,
                           max_context=64, num_blocks=10,
                           registry=MetricsRegistry(),
                           speculative_decoding=True, speculative_k=4)
    starts = [3, 11, 7, 22, 15]
    prompts = [_chain(perm, s, 20) for s in starts]
    streams = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run_until_idle()
    assert eng.scheduler.n_preemptions > 0
    for p, s in zip(prompts, streams):
        out = s.tokens()
        assert out == _chain(perm, perm[p[-1]], 16)
    assert eng._c_spec_accepted.value > 0
    alloc = eng.cache.allocator
    assert alloc.available() == alloc.capacity
    assert alloc.occupancy() == 0.0


def test_spec_verify_fault_falls_back_to_decode(cyc, spec_pair):
    """An injected raise at generation.spec_verify evicts nothing: the
    drafted lanes roll their speculative blocks back and take the
    single-token decode round, output stays greedy-exact."""
    _model, _params, perm = cyc
    ref, eng = spec_pair
    prompt = _chain(perm, 9, 12)
    prev = OrcaContext.fault_plan
    OrcaContext.fault_plan = {"faults": [
        {"site": "generation.spec_verify", "at": 1, "action": "raise"}]}
    try:
        _s, want = _run(ref, [prompt], max_new=12)
        streams, got = _run(eng, [prompt], max_new=12)
    finally:
        OrcaContext.fault_plan = prev
    assert got == want
    # nothing was evicted: the request ran to its full length
    assert streams[0].finish_reason == "length"
    rec = request_log.get(streams[0].request_id)
    assert "evicted" not in {e["kind"] for e in rec["events"]}


def test_speculation_defaults_off_and_knob_plumbs(cyc):
    """Speculation defaults off: no Speculator, no verify families,
    the engine is the legacy engine.  The constructor's arguments arm
    it; a bad k is refused there."""
    model, params, _perm = cyc
    eng = GenerationEngine(model, params, max_slots=2, block_size=8,
                           max_context=32, registry=MetricsRegistry())
    assert eng.speculation is None
    assert eng.spec_verify_compile_count == 0
    geometry = dict(max_slots=2, block_size=8, max_context=32)
    eng2 = GenerationEngine(model, params, registry=MetricsRegistry(),
                            speculative_decoding=True, speculative_k=2,
                            **geometry)
    assert eng2.speculation is not None
    assert eng2.speculation.k == 2
    with pytest.raises(ValueError):
        GenerationEngine(model, params, registry=MetricsRegistry(),
                         speculative_decoding=True, speculative_k=0,
                         **geometry)


# ----------------------------------------------------------------------
# zero recompiles, whole stack armed, tp=2
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_zero_recompile_fully_armed_tp2(cyc):
    """The compiled-family contract under the FULL stack: tp=2 x
    prefix caching x chunked prefill x int8 KV x SLO x memory sampler
    x watchdog x speculation — exactly one decode program and
    len(buckets) verify programs, stable across hit/miss/adversarial
    traffic, streams equal to the single-device legacy engine.
    (Slow: mesh init + tp warmup; tier-1 siblings cover the same
    contract without tp — test_spec_stream_identical_to_legacy_composed
    here and test_zero_recompile_with_everything_armed in
    tests/test_prefix_cache.py.)"""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context

    model, params, perm = cyc
    prev_slo = OrcaContext.slo_targets
    prev_wd = OrcaContext.watchdog_deadline_s
    prev_mem = OrcaContext.memory_sample_interval_s
    OrcaContext.slo_targets = {"ttft_s": 60.0, "e2e_s": 600.0}
    OrcaContext.watchdog_deadline_s = 600.0
    OrcaContext.memory_sample_interval_s = 0.0
    stop_orca_context()
    init_orca_context(cluster_mode="local", mesh_shape={"tp": 2})
    try:
        kw = dict(max_slots=4, block_size=8, max_context=128,
                  kv_quantization="int8", prefix_caching=True,
                  chunked_prefill=True, prefill_token_budget=16)
        ref = GenerationEngine(model, params,
                               registry=MetricsRegistry(), **kw)
        eng = GenerationEngine(model, params, tensor_parallel=2,
                               registry=MetricsRegistry(),
                               speculative_decoding=True,
                               speculative_k=4, **kw)
        ref.warmup()
        eng.warmup()
        assert eng.watchdog is not None
        rng = np.random.default_rng(1)
        shared = _chain(perm, 5, 16)
        waves = [
            [shared + _chain(perm, perm[shared[-1]], 4),
             _chain(perm, 20, 40),
             list(rng.integers(0, VOCAB, 11))],        # miss wave
            [shared + _chain(perm, 3, 2),
             list(rng.integers(0, VOCAB, 9)) * 2],     # hit wave
        ]
        for prompts in waves:
            _s, want = _run(ref, prompts)
            _s, got = _run(eng, prompts)
            assert got == want
        assert eng._c_spec_accepted.value > 0
        n_buckets = len(eng.speculation.buckets)
        assert eng.decode_compile_count == 1
        assert eng.spec_verify_compile_count == n_buckets
        # ... and STABLE: more traffic, same programs
        _run(eng, [_chain(perm, 17, 10),
                   list(rng.integers(0, VOCAB, 13))])
        assert eng.decode_compile_count == 1
        assert eng.spec_verify_compile_count == n_buckets
    finally:
        stop_orca_context()
        OrcaContext.slo_targets = prev_slo
        OrcaContext.watchdog_deadline_s = prev_wd
        OrcaContext.memory_sample_interval_s = prev_mem
