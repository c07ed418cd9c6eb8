"""Prefix-cache subsystem tests (serving/generation/prefix_cache.py):
radix-tree lookup/commit/dedupe/LRU-eviction, refcounted block sharing
through admission and preemption, copy-on-write un-sharing, chunked
prefill interleaving with decode, the fault-injection site, and the
zero-recompile guarantee with the whole stack armed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    GenerationEngine,
    PagedKVCache,
    PrefixCache,
)

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=256)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


@pytest.fixture(scope="module")
def eng(lm):
    """One warmed prefix-caching + chunked engine shared by the tests
    that don't need a special pool geometry."""
    model, params = lm
    e = GenerationEngine(model, params, max_slots=4, block_size=8,
                         max_context=64, prefix_caching=True,
                         chunked_prefill=True)
    e.warmup()
    return e


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


# ----------------------------------------------------------------------
# radix tree (host-side, no engine)
# ----------------------------------------------------------------------

def test_radix_lookup_commit_and_refcounts():
    cache = PagedKVCache(n_layers=1, num_blocks=12, block_size=4,
                         n_head=1, head_dim=4)
    pc = PrefixCache(cache)
    a = cache.allocator
    toks = list(range(10))              # 2 full blocks + tail of 2

    # empty tree: miss, nothing pinned
    blocks, n = pc.lookup(toks)
    assert blocks == [] and n == 0

    # a sequence prefills and commits: the tree takes its own ref
    table = a.alloc(3)
    committed = pc.commit(toks, table)
    assert committed == table           # no dedupe needed
    assert pc.n_blocks == 2             # only FULL blocks cached
    assert a.ref_count(table[0]) == 2 and a.ref_count(table[1]) == 2
    assert a.ref_count(table[2]) == 1   # the partial block: seq-only

    # lookup pins for the caller; the match is capped one token short
    got, n = pc.lookup(toks)
    assert got == table[:2] and n == 8
    assert a.ref_count(table[0]) == 3
    # exactly-two-blocks query (8 tokens): cap leaves 1 full block
    got2, n2 = pc.lookup(toks[:8])
    assert got2 == table[:1] and n2 == 4
    a.free(got + got2)

    # identical prompt prefilled concurrently -> commit DEDUPES:
    # the duplicate blocks are freed, the cached ones adopted (the
    # adopter now holds a share on the cached blocks instead)
    dup = a.alloc(3)
    deduped = pc.commit(toks, dup)
    assert deduped[:2] == table[:2] and deduped[2] == dup[2]
    assert a.ref_count(dup[0]) == 0     # duplicate returned to pool
    assert pc.n_blocks == 2

    # release both owners: tree refs keep the blocks alive
    a.free(table)
    a.free(deduped)
    assert a.ref_count(table[0]) == 1 and pc.n_blocks == 2

    # eviction frees LRU leaves only while unreferenced
    a.share([table[1]])                 # simulate a lane pin
    assert pc.evict(8) == 0             # leaf pinned -> nothing freed
    a.free([table[1]])
    assert pc.evict(1) == 1             # leaf goes first
    assert pc.n_blocks == 1
    assert pc.evict(8) == 1 and pc.n_blocks == 0
    assert a.available() == a.capacity


def test_block_allocator_share_and_free_guards():
    from analytics_zoo_tpu.serving.generation import BlockAllocator

    a = BlockAllocator(6)
    got = a.alloc(2)
    a.share([got[0]])
    assert a.ref_count(got[0]) == 2 and a.n_shared() == 1
    # freeing the same id twice IN ONE CALL needs two references
    a.free([got[0], got[0]])
    assert a.ref_count(got[0]) == 0
    with pytest.raises(ValueError, match="double free"):
        a.free([got[1], got[1]])
    with pytest.raises(ValueError, match="share unallocated"):
        a.share([got[0]])
    a.free([got[1]])
    assert a.available() == a.capacity


# ----------------------------------------------------------------------
# engine: hit path, chunked prefill, preemption, COW
# ----------------------------------------------------------------------

def test_prefix_hit_skips_tail_prefill_and_matches_greedy(lm, eng):
    model, params = lm
    rng = np.random.default_rng(1)
    shared = list(rng.integers(0, VOCAB, 16))   # 2 full blocks
    p1 = shared + list(rng.integers(0, VOCAB, 5))
    out1 = eng.generate(p1, max_new_tokens=6)
    _assert_greedy(model, params, p1, out1)
    prefilled_before = eng._c_prefill_tokens.value
    hits_before = eng.prefix_cache._c_hits.value

    p2 = shared + list(rng.integers(0, VOCAB, 4))
    s2 = eng.submit(p2, max_new_tokens=6)
    eng.run_until_idle()
    _assert_greedy(model, params, p2, s2.tokens())
    assert eng.prefix_cache._c_hits.value == hits_before + 1
    # only the 4-token tail prefilled, not the 16 shared tokens
    assert eng._c_prefill_tokens.value - prefilled_before == len(p2) - 16
    # the lifecycle log carries the reuse event
    from analytics_zoo_tpu.observability import request_log
    rec = request_log.get(s2.request_id)
    kinds = [e["kind"] for e in rec["events"]]
    assert "prefix_hit" in kinds
    hit = next(e for e in rec["events"] if e["kind"] == "prefix_hit")
    assert hit["tokens"] == 16 and hit["blocks"] == 2


def test_chunked_prefill_interleaves_with_decode(lm):
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=128, chunked_prefill=True,
                              prefill_token_budget=16)
    engine.warmup()
    rng = np.random.default_rng(2)
    p_short = list(rng.integers(0, VOCAB, 6))
    short = engine.submit(p_short, max_new_tokens=24)
    engine.step()
    assert short.seq.status == "running"
    long_p = list(rng.integers(0, VOCAB, 100))
    long = engine.submit(long_p, max_new_tokens=4)
    # the 100-token prompt must NOT stall the short lane: its prefill
    # spreads over multiple rounds (16-token budget -> >= 6 chunks)
    # and the short lane keeps decoding between chunks
    gen_before = len(short.seq.generated)
    rounds = 0
    while long.seq.status in ("waiting", "prefilling"):
        engine.step()
        rounds += 1
        assert rounds < 60
    assert rounds >= 6
    assert len(short.seq.generated) > gen_before
    engine.run_until_idle()
    _assert_greedy(model, params, long_p, long.tokens())
    _assert_greedy(model, params, p_short, short.tokens())
    assert engine.decode_compile_count == 1


def test_preemption_with_shared_blocks_is_lossless(lm):
    """Satellite: preempting a lane whose prefix blocks are shared
    must not free blocks still referenced by other lanes or the radix
    tree, and every preempted request resumes losslessly."""
    model, params = lm
    # 9 allocatable blocks, 4 lanes wanting ~4-5 each -> preemptions
    engine = GenerationEngine(model, params, max_slots=4, block_size=8,
                              max_context=64, num_blocks=10,
                              prefix_caching=True, chunked_prefill=True)
    engine.warmup()
    rng = np.random.default_rng(3)
    shared = list(rng.integers(0, VOCAB, 16))
    reqs = [shared + list(rng.integers(0, VOCAB, 4)) for _ in range(5)]
    streams = [engine.submit(p, max_new_tokens=16) for p in reqs]
    engine.run_until_idle()
    assert engine.scheduler.n_preemptions > 0
    for p, s in zip(reqs, streams):
        out = s.tokens()
        assert len(out) == 16, s.seq.finish_reason
        _assert_greedy(model, params, p, out)
    # all lane references released; only the radix tree's refs remain
    a = engine.cache.allocator
    assert a.capacity - a.available() == engine.prefix_cache.n_blocks
    assert a.n_shared() == 0
    assert engine.decode_compile_count == 1


def test_cow_unshares_block_before_write(lm):
    """A shared block in a lane's write path is un-shared via the
    copy-on-write guard: fresh block, device-side copy, decode output
    unchanged — the forked holder's view is never scribbled on."""
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64, prefix_caching=True)
    engine.warmup()
    rng = np.random.default_rng(4)
    p = list(rng.integers(0, VOCAB, 12))
    s = engine.submit(p, max_new_tokens=10)
    engine.step()                       # prefill + first decode
    seq = s.seq
    idx = (seq.context_len - 1) // 8
    blk = seq.block_table[idx]
    engine.cache.allocator.share([blk])   # simulate a fork's hold
    engine.step()
    assert engine._c_cow.value >= 1
    assert seq.block_table[idx] != blk
    assert engine.cache.allocator.ref_count(blk) == 1
    engine.cache.allocator.free([blk])
    engine.run_until_idle()
    _assert_greedy(model, params, p, s.tokens())


def test_eviction_under_pool_pressure_prefers_cache_over_preemption(lm):
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64, num_blocks=10,
                              prefix_caching=True)
    engine.warmup()
    rng = np.random.default_rng(5)
    # two distinct prompts fill the tree, then a third needs the space
    for _ in range(2):
        p = list(rng.integers(0, VOCAB, 24))
        engine.generate(p, max_new_tokens=2)
    assert engine.prefix_cache.n_blocks == 6
    held = engine.cache.allocator.capacity \
        - engine.cache.allocator.available()
    assert held == 6                    # tree-only residency
    p3 = list(rng.integers(0, VOCAB, 30))
    out = engine.generate(p3, max_new_tokens=8)
    _assert_greedy(model, params, p3, out)
    assert engine.prefix_cache._c_evictions.value > 0
    assert engine.scheduler.n_preemptions == 0


def test_prefix_lookup_fault_site_fails_cleanly(lm):
    from analytics_zoo_tpu.resilience.faults import (
        SimulatedWorkerFailure)

    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64, prefix_caching=True)
    engine.warmup()
    rng = np.random.default_rng(6)
    p = list(rng.integers(0, VOCAB, 12))
    out = engine.generate(p, max_new_tokens=4)
    prev = OrcaContext.fault_plan
    OrcaContext.fault_plan = {"faults": [
        {"site": "generation.prefix_lookup", "at": 1,
         "action": "raise"}]}
    try:
        s = engine.submit(p, max_new_tokens=4)
        with pytest.raises(SimulatedWorkerFailure):
            engine.run_until_idle()
    finally:
        OrcaContext.fault_plan = prev
    # the tree survived the injected lookup failure: drain the stuck
    # request, then the same prompt still serves (and still hits)
    engine.scheduler.waiting.clear()
    s.seq.status = "finished"
    hits = engine.prefix_cache._c_hits.value
    assert engine.generate(p, max_new_tokens=4) == out
    assert engine.prefix_cache._c_hits.value == hits + 1


def test_zero_recompile_with_everything_armed(lm):
    """decode_compiles == 1 with prefix caching + chunked prefill +
    int8 KV + SLO judging + memory sampler + watchdog all armed (the
    acceptance gate's tier-1 sibling)."""
    model, params = lm
    prev_slo = OrcaContext.slo_targets
    prev_wd = OrcaContext.watchdog_deadline_s
    prev_mem = OrcaContext.memory_sample_interval_s
    OrcaContext.slo_targets = {"ttft_s": 60.0, "e2e_s": 600.0}
    OrcaContext.watchdog_deadline_s = 600.0
    OrcaContext.memory_sample_interval_s = 0.0
    try:
        engine = GenerationEngine(model, params, max_slots=4,
                                  block_size=8, max_context=64,
                                  cache_dtype=jnp.float16,
                                  kv_quantization="int8",
                                  prefix_caching=True,
                                  chunked_prefill=True)
        engine.warmup()
        assert engine.watchdog is not None
        rng = np.random.default_rng(7)
        shared = list(rng.integers(0, VOCAB, 16))
        streams = [engine.submit(
            shared + list(rng.integers(0, VOCAB, 1 + j)),
            max_new_tokens=5, temperature=0.5 * j, top_k=j)
            for j in range(5)]
        engine.run_until_idle()
        assert all(len(s.tokens()) == 5 for s in streams)
        assert engine.decode_compile_count == 1, \
            "decode recompiled with the full stack armed"
        assert engine.prefix_cache.hit_rate() > 0
    finally:
        OrcaContext.slo_targets = prev_slo
        OrcaContext.watchdog_deadline_s = prev_wd
        OrcaContext.memory_sample_interval_s = prev_mem


def test_prefix_caching_off_is_default_and_legacy(lm):
    """The knob defaults off: no prefix cache object, no chunk-step
    warmup, the legacy whole-prompt prefill path drives (bitwise
    bit-identical behavior is pinned by the untouched
    tests/test_generation.py suite)."""
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=32)
    assert engine.prefix_cache is None
    assert engine._use_chunks is False
