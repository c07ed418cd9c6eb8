"""Host-tier subsystem tests (serving/generation/host_tier.py + the
prefix-cache spill/restore wiring + the router's phase-aware
disaggregation): bounded-bytes LRU accounting, geometry guards,
refcount-1-only spill candidates, the spill -> restore round trip
(greedy parity, prefill savings, zero recompiles), double-free guards
across spill/restore, the staged-restore-vs-eviction race, injected
restore corruption degrading to a lossless recompute, and the
defaults-off parity pin (the legacy eviction path is bitwise
untouched while the knobs ship off)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    GenerationEngine,
    PagedKVCache,
)
from analytics_zoo_tpu.serving.generation.host_tier import (
    HostKVTier,
    dma_events,
    reset_dma,
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


def _tier_engine(lm, **kw):
    model, params = lm
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefix_caching", True)
    kw.setdefault("chunked_prefill", True)
    kw.setdefault("kv_host_tier", 1 << 20)
    engine = GenerationEngine(model, params, **kw)
    engine.warmup()
    return engine


# ----------------------------------------------------------------------
# tier unit behavior (no engine)
# ----------------------------------------------------------------------

def test_tier_lru_bounded_bytes_and_dedupe():
    tier = HostKVTier(300, registry=MetricsRegistry())
    kv = np.zeros((1, 2, 4, 4), np.float32)      # 128 bytes
    assert tier.put((1, 2, 3, 4), kv, None)
    assert tier.put((1, 2, 3, 4, 5, 6, 7, 8), kv, None)
    assert len(tier) == 2 and tier.bytes_used == 256
    # re-put of a resident key dedupes (refreshes recency, no growth)
    assert tier.put((1, 2, 3, 4), kv, None)
    assert len(tier) == 2 and tier._c_spilled.value == 2
    # a third entry exceeds capacity: the LRU entry (the 8-token key,
    # since the 4-token one was just refreshed) is evicted to fit
    assert tier.put((9, 9, 9, 9), kv, None)
    assert len(tier) == 2 and tier.bytes_used == 256
    assert tier._c_evictions.value == 1
    assert tier.fetch((1, 2, 3, 4, 5, 6, 7, 8)) is None
    assert tier.fetch((1, 2, 3, 4)) is not None
    # an entry that alone exceeds capacity is refused outright
    big = np.zeros((1, 2, 64, 1, 4), np.float32)
    assert not tier.put((7,), big, None)
    # the memory provider reports live accounting
    stats = tier._stats()
    assert stats["entries"] == 2 and stats["bytes_used"] == 256
    assert stats["bytes_capacity"] == 300
    # clear drops everything (advisory: only future restores lost)
    assert tier.clear() == 2
    assert len(tier) == 0 and tier.bytes_used == 0


def test_tier_geometry_guard_refuses_mismatched_slabs():
    cache = PagedKVCache(n_layers=1, num_blocks=8, block_size=4,
                         n_head=1, head_dim=4)
    tier = HostKVTier(1 << 16, registry=MetricsRegistry())
    tier.bind_geometry(cache)
    good = np.zeros((1, 2, 4, 4), np.asarray(cache.kv).dtype)
    assert tier.put((1, 2, 3, 4), good, None)
    # wrong block size / unexpected scales: refused, tier unchanged
    assert not tier.put((9,), np.zeros((1, 2, 8, 1, 4), good.dtype),
                        None)
    assert not tier.put((9,), good, np.zeros((1, 2, 4), np.float32))
    assert len(tier) == 1
    # re-binding to an incompatible pool drops the resident entries —
    # a heterogeneous fleet must never adopt garbage
    other = PagedKVCache(n_layers=1, num_blocks=8, block_size=8,
                         n_head=1, head_dim=4)
    tier.bind_geometry(other)
    assert len(tier) == 0


def test_match_tokens_is_read_only_and_capped():
    cache = PagedKVCache(n_layers=1, num_blocks=8, block_size=4,
                         n_head=1, head_dim=4)
    tier = HostKVTier(1 << 16, registry=MetricsRegistry())
    tier.bind_geometry(cache)
    toks = list(range(12))
    kv = np.zeros((1, 2, 4, 4), np.asarray(cache.kv).dtype)
    tier.put(tuple(toks[:4]), kv, None)
    tier.put(tuple(toks[:8]), kv, None)
    order_before = list(tier._entries)
    # capped one short of the query like the radix tree: 12 tokens ->
    # 2 usable blocks, 8 tokens -> 1
    assert tier.match_tokens(toks) == 8
    assert tier.match_tokens(toks[:8]) == 4
    assert tier.match_tokens([5] + toks[1:]) == 0
    # read-only: no LRU touch, no counter tick
    assert list(tier._entries) == order_before
    assert tier._c_restored.value == 0


# ----------------------------------------------------------------------
# engine: spill on evict, restore on miss
# ----------------------------------------------------------------------

def test_spill_restore_round_trip_matches_greedy(lm):
    model, params = lm
    engine = _tier_engine(lm)
    tier = engine.host_tier
    assert tier is not None
    rng = np.random.default_rng(21)
    p = list(rng.integers(0, VOCAB, 24))
    out = engine.generate(p, max_new_tokens=6)
    _assert_greedy(model, params, p, out)

    # evict the whole tree: every refcount-1 block spills to the host
    spilled0 = tier._c_spilled.value
    reset_dma()
    freed = engine.prefix_cache.evict(32)
    assert freed >= 3 and engine.prefix_cache.n_blocks == 0
    assert tier._c_spilled.value - spilled0 == freed
    assert sum(1 for e in dma_events()
               if e["kind"] == "host_spill") == freed

    # the re-run restores the device match from the host instead of
    # recomputing it: only the tail prefills
    prefilled0 = engine._c_prefill_tokens.value
    s = engine.submit(p, max_new_tokens=6)
    engine.run_until_idle()
    assert s.tokens() == out
    assert tier._c_restored.value >= 2
    assert engine._c_prefill_tokens.value - prefilled0 == len(p) - 16
    assert any(e["kind"] == "host_restore" for e in dma_events())
    assert engine.decode_compile_count == 1


def test_only_refcount1_blocks_are_spill_candidates(lm):
    engine = _tier_engine(lm)
    tier = engine.host_tier
    rng = np.random.default_rng(22)
    p = list(rng.integers(0, VOCAB, 24))
    engine.generate(p, max_new_tokens=2)
    a = engine.cache.allocator
    # pin the tree's leaf (simulating a lane still holding it): the
    # chain has no refcount-1 leaf left, so NOTHING evicts or spills
    leaves = engine.prefix_cache._evictable()
    assert leaves, "expected an evictable leaf after release"
    pin = leaves[0].block
    a.share([pin])
    spilled0 = tier._c_spilled.value
    assert engine.prefix_cache.evict(32) == 0
    assert tier._c_spilled.value == spilled0
    # released, the chain peels leaves-first and every block spills
    a.free([pin])
    freed = engine.prefix_cache.evict(32)
    assert freed >= 3
    assert tier._c_spilled.value - spilled0 == freed
    assert a.available() == a.capacity


def test_double_free_guard_across_spill_restore(lm):
    engine = _tier_engine(lm)
    tier = engine.host_tier
    rng = np.random.default_rng(23)
    p = list(rng.integers(0, VOCAB, 24))
    engine.generate(p, max_new_tokens=4)
    engine.prefix_cache.evict(32)
    # restore path: the caller ends with one pinned ref per restored
    # block (alloc) and the tree with its own (share) — exactly a
    # device hit; releasing the lane must leave tree-only residency
    s = engine.submit(p, max_new_tokens=4)
    engine.run_until_idle()
    assert tier._c_restored.value >= 2
    a = engine.cache.allocator
    assert a.capacity - a.available() == engine.prefix_cache.n_blocks
    assert a.n_shared() == 0
    # a second evict/spill cycle over the restored blocks must free
    # each exactly once (the allocator raises on double free) and the
    # tier must dedupe the re-spilled keys instead of duplicating
    entries0 = len(tier)
    nb = engine.prefix_cache.n_blocks
    freed = engine.prefix_cache.evict(32)
    assert freed == nb
    assert a.available() == a.capacity
    assert len(tier) == entries0, "re-spill duplicated resident keys"


def test_staged_restore_race_falls_back_to_recompute(lm):
    """A restore staged ahead of admission can lose the race with
    host-tier eviction; the lane must recompute losslessly."""
    model, params = lm
    engine = _tier_engine(lm)
    tier = engine.host_tier
    rng = np.random.default_rng(24)
    p = list(rng.integers(0, VOCAB, 24))
    out = engine.generate(p, max_new_tokens=6)
    engine.prefix_cache.evict(32)
    s = engine.submit(p, max_new_tokens=6)
    engine._stage_host_restores()
    assert any(e.staged_kv is not None
               for e in tier._entries.values()), "nothing staged"
    # the race: every staged entry evicted before the restore lands
    tier.clear()
    restored0 = tier._c_restored.value
    engine.run_until_idle()
    assert tier._c_restored.value == restored0
    got = s.tokens()                    # drains once
    assert got == out                   # lossless full recompute
    _assert_greedy(model, params, p, got)
    assert engine.decode_compile_count == 1


def test_restore_corruption_fault_degrades_to_recompute(lm):
    model, params = lm
    engine = _tier_engine(lm)
    tier = engine.host_tier
    rng = np.random.default_rng(25)
    p = list(rng.integers(0, VOCAB, 24))
    out = engine.generate(p, max_new_tokens=6)
    engine.prefix_cache.evict(32)
    failed0 = tier._c_restore_failed.value
    restored0 = tier._c_restored.value
    evictions0 = engine.prefix_cache._c_evictions.value
    prev = OrcaContext.fault_plan
    OrcaContext.fault_plan = {"faults": [
        {"site": "generation.host_restore", "at": 1,
         "action": "nan"}]}
    try:
        s = engine.submit(p, max_new_tokens=6)
        engine.run_until_idle()
    finally:
        OrcaContext.fault_plan = prev
    # the corrupt entry was dropped and counted; the lane recomputed
    # the whole prefix and produced the exact same tokens — with zero
    # collateral prefix-cache evictions
    assert tier._c_restore_failed.value == failed0 + 1
    assert tier._c_restored.value == restored0
    assert engine.prefix_cache._c_evictions.value == evictions0
    got = s.tokens()
    assert got == out
    _assert_greedy(model, params, p, got)


def test_defaults_off_is_legacy_eviction_path(lm):
    """Both knobs ship off: no tier object anywhere, no restore step,
    and eviction frees blocks without recording a single DMA — the
    legacy path the parity suites pin is untouched."""
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64, prefix_caching=True)
    engine.warmup()
    assert engine.host_tier is None
    assert engine.prefix_cache.host_tier is None
    rng = np.random.default_rng(26)
    p = list(rng.integers(0, VOCAB, 24))
    out = engine.generate(p, max_new_tokens=4)
    _assert_greedy(model, params, p, out)
    reset_dma()
    assert engine.prefix_cache.evict(32) >= 3
    assert dma_events() == []           # nothing spilled anywhere
    a = engine.cache.allocator
    assert a.available() == a.capacity


# ----------------------------------------------------------------------
# router: phase-aware prefill/decode disaggregation
# ----------------------------------------------------------------------

@pytest.mark.slow   # ~11s warm (PR 19 budget trim): sibling tier-1
# coverage: test_phase_blind_router_has_no_phase_state keeps the
# phase-state plumbing honest, and spill/restore correctness stays in
# the gate via test_spill_restore_round_trip_matches_greedy and
# test_staged_restore_race_falls_back_to_recompute; the end-to-end
# two-replica phase-routing drive moves out.
def test_router_phase_routing_over_shared_tier(lm):
    from analytics_zoo_tpu.serving.distributed import ReplicaRouter

    model, params = lm
    shared = HostKVTier(1 << 20, registry=MetricsRegistry())
    engines = [GenerationEngine(model, params, max_slots=2,
                                block_size=8, max_context=64,
                                prefix_caching=True,
                                chunked_prefill=True,
                                kv_host_tier=shared,
                                registry=MetricsRegistry())
               for _ in range(2)]
    for e in engines:
        e.warmup()
    r = ReplicaRouter(engines, phase_aware=True,
                      registry=MetricsRegistry())
    try:
        assert [rep.phase for rep in r.replicas] == \
            ["prefill", "decode"]
        # only the prefill replica writes through on commit
        assert engines[0].prefix_cache.host_write_through is True
        assert engines[1].prefix_cache.host_write_through is False
        rng = np.random.default_rng(27)
        # a long novel prompt classifies as prefill and lands on the
        # prefill-tagged replica (preference on an idle fleet)
        long_p = list(rng.integers(0, VOCAB, 32))
        s1 = r.submit(long_p, max_new_tokens=4)
        r.run_until_idle()
        assert s1.replica_name == "replica-0"
        assert r._c_phase_prefill.value == 1
        toks1 = s1.tokens()
        _assert_greedy(model, params, long_p, toks1)
        # write-through published the prefix to the shared tier ...
        assert shared.match_tokens(long_p) >= 16
        # ... so the same prompt now classifies as decode (mostly
        # cached fleet-wide) and prefers the decode replica, which
        # ADOPTS the blocks from the host tier instead of recomputing
        restored0 = shared._c_restored.value
        s2 = r.submit(long_p, max_new_tokens=4)
        r.run_until_idle()
        assert r._c_phase_decode.value == 1
        assert s2.replica_name == "replica-1"
        assert shared._c_restored.value > restored0
        assert s2.tokens() == toks1
        rows = r.stats()["replicas"]
        assert [row["phase"] for row in rows] == ["prefill", "decode"]
        for e in engines:
            assert e.decode_compile_count == 1
    finally:
        r.stop()


def test_phase_blind_router_has_no_phase_state(lm):
    from analytics_zoo_tpu.serving.distributed import ReplicaRouter

    model, params = lm
    engines = [GenerationEngine(model, params, max_slots=2,
                                block_size=8, max_context=64,
                                registry=MetricsRegistry())
               for _ in range(2)]
    r = ReplicaRouter(engines, registry=MetricsRegistry())
    try:
        assert r.phase_aware is False
        assert all(rep.phase is None for rep in r.replicas)
        assert r._c_phase_prefill.value == 0
        assert r._c_phase_decode.value == 0
    finally:
        r.stop()
