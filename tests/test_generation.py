"""Continuous-batching generation subsystem tests
(serving/generation/): block allocator invariants, scheduler
join/leave + preemption, the zero-recompile decode guarantee, KV-cached
vs full-recompute logit equivalence, and streamed /generate end-to-end
through ServingServer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.generation import (
    BlockAllocator,
    CausalLM,
    GenerationEngine,
    PagedKVCache,
    sample_tokens,
)
from analytics_zoo_tpu.serving.generation.engine import SAMPLE_PATHS

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
    """One warmed engine shared by the tests that don't need a special
    pool/slot geometry — mirrors a long-lived serving process."""
    model, params = lm
    e = GenerationEngine(model, params, max_slots=4, block_size=8,
                         max_context=64)
    e.warmup()
    return e


def _assert_greedy(model, params, prompt, out):
    """Verify `out` is the greedy full-recompute decode of `prompt`
    with ONE forward: greedy decoding == teacher forcing, so on the
    completed sequence every generated token must be the argmax of the
    logits at its preceding position (causality makes position j's
    logits independent of later tokens)."""
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
# block allocator
# ----------------------------------------------------------------------

def test_block_allocator_invariants():
    a = BlockAllocator(8)               # 7 allocatable, block 0 null
    assert a.capacity == 7
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.available() == 4
    assert abs(a.occupancy() - 3 / 7) < 1e-9
    assert a.alloc(5) is None           # over-ask: nothing handed out
    assert a.available() == 4
    rest = a.alloc(4)
    assert a.alloc(1) is None and a.occupancy() == 1.0
    a.free(got)
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    with pytest.raises(ValueError, match="null block"):
        a.free([0])
    with pytest.raises(ValueError, match="out of range"):
        a.free([99])
    # a duplicate id WITHIN one call is a double free too — and the
    # guard validates the whole request before mutating, so the pool
    # is untouched by the rejected call
    with pytest.raises(ValueError, match="double free"):
        a.free([rest[0], rest[0]])
    assert a.ref_count(rest[0]) == 1
    a.free(rest)
    assert a.available() == 7 and a.occupancy() == 0.0


def test_paged_cache_shapes():
    """One row of heads * head_dim columns per token slot; the block
    view splits the slot axis and nothing else, and a block's slab is
    what the host tier moves."""
    from analytics_zoo_tpu.serving.generation.kv_cache import block_view
    c = PagedKVCache(n_layers=2, num_blocks=5, block_size=4, n_head=2,
                     head_dim=8)
    assert c.kv.shape == (2, 2, 20, 16)
    assert block_view(c.kv, 4).shape == (2, 2, 5, 4, 16)
    assert c.slab_shape == (2, 2, 4, 16)
    rows, scale = c.read_block(3)
    assert rows.shape == c.slab_shape and scale is None
    assert c.blocks_for(1) == 1 and c.blocks_for(4) == 1
    assert c.blocks_for(5) == 2
    q = PagedKVCache(n_layers=2, num_blocks=5, block_size=4, n_head=2,
                     head_dim=8, quantization="int8")
    assert q.kv_scale.shape == (2, 2, 20)
    assert block_view(q.kv_scale, 4).shape == (2, 2, 5, 4)
    assert q.read_block(3)[1].shape == q.slab_shape[:3]


@pytest.mark.parametrize("quantization", [None, "int8"])
@pytest.mark.parametrize("n_rows", [1, 7, 32])
def test_write_kv_rows_match_the_layerwise_scatter(quantization, n_rows):
    """`write_kv`'s ONE scatter of rows (index (layer, k/v, slot),
    window a merged row) against the write it replaced — a
    `kv.at[:, 0, dest]` / `kv.at[:, 1, dest]` scatter of [h, d] slabs
    over a [..., heads, head_dim] pool — on a seeded pool: every slot
    but the null block's (where the duplicates land: dead lanes and
    padding, any winner) holds the same values, int8 scales included,
    and `gather_kv` reads back what was written."""
    from analytics_zoo_tpu.serving.generation.kv_cache import (
        gather_kv, quantize_kv_tokens, write_kv)
    L, nb, bs, h, d = 3, 6, 4, 2, 8
    rng = np.random.default_rng(5 + n_rows)
    c = PagedKVCache(n_layers=L, num_blocks=nb, block_size=bs, n_head=h,
                     head_dim=d, quantization=quantization)
    int8 = quantization == "int8"
    if int8:
        kv = jnp.asarray(rng.integers(-127, 128, c.kv.shape), jnp.int8)
        scale = jnp.asarray(rng.uniform(0.01, 0.1, c.kv_scale.shape),
                            jnp.float32)
    else:
        kv = jnp.asarray(rng.normal(size=c.kv.shape), jnp.float32)
        scale = jnp.zeros((1,), jnp.float32)    # the engine's placeholder
    # distinct live slots, then padding that all names the null block
    live = bs + rng.permutation((nb - 1) * bs)[:max(1, n_rows - 3)]
    dest = jnp.asarray(np.concatenate(
        [live, np.zeros(n_rows - len(live), np.int64)]), jnp.int32)
    new_k, new_v = (jnp.asarray(rng.normal(size=(L, n_rows, h, d)),
                                jnp.float32) for _ in range(2))
    got_kv, got_scale = write_kv(kv, scale, dest, new_k, new_v)

    old = kv.reshape(L, 2, nb * bs, h, d)
    want_scale = scale
    if int8:
        (qk, sk), (qv, sv) = (quantize_kv_tokens(x)
                              for x in (new_k, new_v))
        old = old.at[:, 0, dest].set(qk).at[:, 1, dest].set(qv)
        want_scale = scale.at[:, 0, dest].set(sk).at[:, 1, dest].set(sv)
    else:
        old = old.at[:, 0, dest].set(new_k).at[:, 1, dest].set(new_v)
    assert got_kv.shape == kv.shape and got_kv.dtype == kv.dtype
    np.testing.assert_array_equal(
        np.asarray(got_kv)[:, :, bs:],
        np.asarray(old.reshape(kv.shape))[:, :, bs:])
    if int8:
        np.testing.assert_array_equal(np.asarray(got_scale)[:, :, bs:],
                                      np.asarray(want_scale)[:, :, bs:])
    else:
        assert got_scale is scale or np.array_equal(got_scale, scale)
    # the read side of the same layout: heads split out of the gather
    ctx_k, ctx_v = gather_kv(got_kv, got_scale, dest[:len(live)], h)
    want_k, want_v = new_k[:, :len(live)], new_v[:, :len(live)]
    tol = 0.05 if int8 else 0.0
    np.testing.assert_allclose(np.asarray(ctx_k), np.asarray(want_k),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(ctx_v), np.asarray(want_v),
                               atol=tol)


POOLS = {
    # kind: (PagedKVCache keywords, heads, head_dim)
    "bf16": (dict(dtype=jnp.bfloat16), 2, 8),
    "int8": (dict(quantization="int8"), 2, 8),
    # one row a token, its 100 columns stored padded to 128
    "latent": (dict(rows=1), 1, 100),
}


@pytest.mark.parametrize("n, length", [
    (16, 8), (16, 6), (16, 1), (16, 16), (14, 14), (14, 3)],
    ids=["blocks", "mid_block", "one", "whole_bucket",
         "ragged_bucket", "ragged_bucket_short"])
@pytest.mark.parametrize("kind", list(POOLS))
def test_write_kv_blocks_match_the_row_write(kind, n, length):
    """`write_kv_blocks` — a prompt's rows by whole blocks, ONE scatter
    into the block view — against `write_kv` with the same rows by
    token slot, on a seeded pool: every slot below `length` bit-equal,
    scales included; every block that is neither the prompt's nor the
    null block as it was before.  (The last real block's rows past
    `length` differ by design: the block form writes the block
    whole.)"""
    from analytics_zoo_tpu.serving.generation.kv_cache import (
        write_kv, write_kv_blocks)
    kw, h, d = POOLS[kind]
    L, nb, bs = 3, 9, 4
    rng = np.random.default_rng(40 + n + length)
    c = PagedKVCache(n_layers=L, num_blocks=nb, block_size=bs, n_head=h,
                     head_dim=d, **kw)
    if kind == "int8":
        kv = jnp.asarray(rng.integers(-127, 128, c.kv.shape), jnp.int8)
        scale = jnp.asarray(rng.uniform(0.01, 0.1, c.kv_scale.shape),
                            jnp.float32)
    else:
        kv = jnp.asarray(rng.normal(size=c.kv.shape), c.kv.dtype)
        scale = jnp.zeros((1,), jnp.float32)    # the engine's placeholder
    table = 1 + rng.permutation(nb - 1)[:-(-n // bs)]
    pos = np.arange(n)
    dest = jnp.asarray(np.where(
        pos < length, table[pos // bs] * bs + pos % bs, 0), jnp.int32)
    blocks = jnp.asarray(np.where(
        np.arange(len(table)) * bs < length, table, 0), jnp.int32)
    new_k = jnp.asarray(rng.normal(size=(L, n, h, d)), jnp.float32)
    new_v = None if c.rows == 1 else jnp.asarray(
        rng.normal(size=(L, n, h, d)), jnp.float32)
    want_kv, want_scale = write_kv(kv, scale, dest, new_k, new_v)
    got_kv, got_scale = write_kv_blocks(kv, scale, blocks, new_k, new_v,
                                        bs)
    assert got_kv.shape == kv.shape and got_kv.dtype == kv.dtype
    live = np.asarray(dest)[:length]
    assert len(set(live)) == length and live.min() >= bs
    np.testing.assert_array_equal(np.asarray(got_kv)[:, :, live],
                                  np.asarray(want_kv)[:, :, live])
    assert np.asarray(got_kv)[:, :, live].any()
    written = set(np.asarray(blocks).tolist()) | {0}
    others = np.concatenate(
        [np.arange(b * bs, (b + 1) * bs) for b in range(nb)
         if b not in written])
    np.testing.assert_array_equal(np.asarray(got_kv)[:, :, others],
                                  np.asarray(kv)[:, :, others])
    if kind == "int8":
        np.testing.assert_array_equal(np.asarray(got_scale)[:, :, live],
                                      np.asarray(want_scale)[:, :, live])
        np.testing.assert_array_equal(np.asarray(got_scale)[:, :, others],
                                      np.asarray(scale)[:, :, others])
    else:
        assert got_scale is scale
    if kind == "latent":        # the padding columns are written as zeros
        assert not np.asarray(got_kv)[:, :, live, d:].any()


def test_a_prompt_that_ends_mid_block_decodes_what_a_recompute_does(lm):
    """The `prefill` program writes a prompt's last block WHOLE
    (`write_kv_blocks`): its rows past the prompt hold the padded
    positions' keys and values.  A prompt of 21 tokens over blocks of
    16, then 28 decode rounds (through the rest of that block and on
    into the next): every round's logits are the full recompute's at
    that position and the tokens the concat oracle's — each of those
    rows is written by a decode round before any round reads it."""
    model, params = lm
    prompt = list(np.random.default_rng(40).integers(0, VOCAB, 21))
    served = {}
    for attention in ("paged", "concat"):
        engine = GenerationEngine(
            model, params, max_slots=2, block_size=16, max_context=64,
            prefill_buckets=[32, 64], decode_attention=attention,
            registry=MetricsRegistry())
        step, taken = engine._decode_jit.fn, []

        def tapped(*args, step=step, taken=taken):
            out = step(*args)
            taken.append(np.asarray(out[3][0]))
            return out
        engine._decode_jit.fn = tapped
        tokens = engine.generate(prompt, max_new_tokens=29)
        served[attention] = tokens, np.stack(taken)
        counts = engine.registry.snapshot()
        assert counts["generation_prefill_pool_writes_total_block"] == 1
        assert counts["generation_prefill_pool_writes_total_row"] == 0
    tokens, logits = served["paged"]
    assert tokens == served["concat"][0] and len(tokens) == 29
    seq = prompt + tokens
    want, _, _ = model.apply(
        {"params": params}, jnp.asarray(seq)[None],
        jnp.arange(len(seq))[None], token_mask=jnp.ones((1, len(seq))))
    # decode round i reads the context up to the i-th served token
    want = np.asarray(want[0])[len(prompt):len(prompt) + len(logits)]
    assert len(logits) >= 28
    for got in (logits, served["concat"][1]):
        np.testing.assert_allclose(got, want, atol=1e-4)


def _toy(kind):
    """(model, params) of each kind of model a serving cell runs, at
    toy widths: the other test files' own."""
    if kind == "CausalLM":
        model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                         intermediate_size=64, max_position_len=256)
        return model, model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            jnp.arange(8)[None])["params"]
    import importlib
    module, cls = {
        "DecoderLM": ("test_decoder_lm", "DecoderLM"),
        "HybridLM": ("test_hybrid_lm", "HybridLM"),
        "latent_DecoderLM": ("test_latent_attention", "DecoderLM"),
    }[kind]
    cases = importlib.import_module(module)
    model = getattr(cases, cls).from_config(cases.toy_config())
    return model, cases.seeded(model)


@pytest.mark.parametrize("kind", ["CausalLM", "DecoderLM", "HybridLM",
                                  "latent_DecoderLM"])
def test_every_whole_prompt_prefill_writes_blocks(kind):
    """Each serving cell's kind of model, at toy widths, through the
    engine as the cells configure it (no prefix cache, no chunks):
    `generation_prefill_pool_writes_total_block` counts every prefill
    dispatched — admissions and a preempted lane's resume — and `_row`
    (a chunk's write of single rows) stays 0."""
    model, params = _toy(kind)
    vocab = model.vocab
    engine = GenerationEngine(model, params, max_slots=3, block_size=4,
                              max_context=64, num_blocks=14,
                              prefill_buckets=[8, 16, 32, 64],
                              registry=MetricsRegistry())
    engine.warmup()
    rng = np.random.default_rng(41)
    streams = [engine.submit(list(rng.integers(0, vocab, n)),
                             max_new_tokens=12)
               for n in (5, 17, 9, 22, 13)]
    engine.run_until_idle()
    assert all(len(s.tokens()) == 12 for s in streams)
    counts = engine.registry.snapshot()
    prefills = len(streams) + engine.scheduler.n_preemptions
    assert counts["generation_prefill_pool_writes_total_block"] == prefills
    assert counts["generation_prefill_pool_writes_total_row"] == 0
    assert counts["generation_prefill_seconds"]["calls"] == prefills


# ----------------------------------------------------------------------
# logit equivalence: KV-cached decode == full-sequence recompute
# ----------------------------------------------------------------------

def test_attention_kv_cache_path_matches_full():
    from analytics_zoo_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(0)
    b, t, h, d = 2, 9, 2, 8
    q, k, v = (rng.normal(size=(b, t, h, d)).astype(np.float32)
               for _ in range(3))
    full = dot_product_attention(q, k, v, causal=True,
                                 compute_dtype=jnp.float32)
    # cached view of the last token: context gathered (with garbage
    # padding past ctx_len) + the new token itself
    pad = 4
    ctx_k = np.concatenate(
        [k[:, :t - 1], rng.normal(size=(b, pad, h, d))], 1
    ).astype(np.float32)
    ctx_v = np.concatenate(
        [v[:, :t - 1], rng.normal(size=(b, pad, h, d))], 1
    ).astype(np.float32)
    ctx_len = np.full(b, t - 1, np.int32)
    cached = dot_product_attention(
        q[:, t - 1:], k[:, t - 1:], v[:, t - 1:],
        compute_dtype=jnp.float32,
        ctx_k=ctx_k, ctx_v=ctx_v, ctx_len=ctx_len)
    np.testing.assert_allclose(np.asarray(cached),
                               np.asarray(full[:, t - 1:]), atol=1e-5)


def test_model_cached_logits_match_full_recompute(lm):
    model, params = lm
    rng = np.random.default_rng(1)
    L = 12
    ctx = rng.integers(0, VOCAB, L).astype(np.int32)
    full, all_k, all_v = model.apply(
        {"params": params}, jnp.asarray(ctx)[None],
        jnp.arange(L)[None], token_mask=jnp.ones((1, L)))
    # decode-style: last token against the cache of the first L-1
    # (padded with garbage the ctx_len mask must hide)
    pad = 5
    junk = rng.normal(size=(model.n_block, 1, pad, model.n_head,
                            model.hidden_size // model.n_head))
    ck = jnp.concatenate([all_k[:, :, :L - 1], jnp.asarray(junk)], 2)
    cv = jnp.concatenate([all_v[:, :, :L - 1], jnp.asarray(junk)], 2)
    cached, _, _ = model.apply(
        {"params": params}, jnp.asarray(ctx[L - 1:])[None],
        jnp.full((1, 1), L - 1), ctx_k=ck, ctx_v=cv,
        ctx_len=jnp.full(1, L - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(cached[0, 0]),
                               np.asarray(full[0, -1]), atol=1e-4)


def test_engine_greedy_matches_full_recompute(lm, eng):
    model, params = lm
    rng = np.random.default_rng(2)
    for trial in range(3):
        prompt = list(rng.integers(0, VOCAB, int(rng.integers(4, 20))))
        n = int(rng.integers(3, 12))
        _assert_greedy(model, params, prompt,
                       eng.generate(prompt, max_new_tokens=n))


# ----------------------------------------------------------------------
# zero recompiles after warmup
# ----------------------------------------------------------------------

def test_decode_compiles_once_after_warmup(lm, eng):
    model, params = lm
    assert eng.decode_compile_count == 1
    rng = np.random.default_rng(3)
    # mixed prompt lengths and batch occupancies, staggered finishes —
    # steady-state serving must never touch the compiler again
    streams = [eng.submit(list(rng.integers(0, VOCAB, l)),
                          max_new_tokens=m, temperature=temp, top_k=k)
               for l, m, temp, k in [(5, 3, 0.0, 0), (17, 9, 0.7, 5),
                                     (33, 2, 0.0, 0), (8, 12, 1.2, 1),
                                     (50, 5, 0.3, 40), (3, 7, 0.0, 0)]]
    eng.run_until_idle()
    assert all(len(s.tokens()) > 0 for s in streams)
    assert eng.decode_compile_count == 1, \
        "decode step recompiled during steady-state serving"


# ----------------------------------------------------------------------
# scheduler: join/leave mid-stream, preemption
# ----------------------------------------------------------------------

def test_scheduler_join_and_leave_midstream(lm):
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64)
    rng = np.random.default_rng(4)
    p_long = list(rng.integers(0, VOCAB, 10))
    p_short = list(rng.integers(0, VOCAB, 6))
    long_s = engine.submit(p_long, max_new_tokens=20)
    engine.step()                       # long admitted + prefilled
    assert long_s.seq.status == "running"
    short_s = engine.submit(p_short, max_new_tokens=3)
    engine.step()                       # short JOINS the running batch
    assert short_s.seq.status == "running"
    assert len(engine.scheduler.running()) == 2
    while short_s.seq.status == "running":
        engine.step()
    # short LEFT; long is still mid-stream on its lane
    assert short_s.seq.finish_reason == "length"
    assert long_s.seq.status == "running"
    # the freed lane is immediately admittable
    third = engine.submit(p_short, max_new_tokens=2)
    engine.step()
    assert third.seq.status in ("running", "finished")
    engine.run_until_idle()
    _assert_greedy(model, params, p_long, long_s.tokens())
    _assert_greedy(model, params, p_short, short_s.tokens())
    assert len(long_s.seq.generated) == 20
    assert len(short_s.seq.generated) == 3


def test_preemption_under_cache_pressure_is_lossless(lm):
    model, params = lm
    # 9 allocatable blocks for 4 lanes that want up to 8 each
    engine = GenerationEngine(model, params, max_slots=4, block_size=8,
                              max_context=64, num_blocks=10)
    rng = np.random.default_rng(5)
    reqs = [list(rng.integers(0, VOCAB, 20)) for _ in range(5)]
    streams = [engine.submit(p, max_new_tokens=16) for p in reqs]
    engine.run_until_idle()
    assert engine.scheduler.n_preemptions > 0
    for p, s in zip(reqs, streams):
        out = s.tokens()
        assert len(out) == 16
        _assert_greedy(model, params, p, out)
    # release-on-finish: every block returned to the pool
    assert engine.cache.allocator.occupancy() == 0.0
    assert engine.cache.allocator.available() == \
        engine.cache.allocator.capacity


def test_submit_validation(lm):
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=32)
    with pytest.raises(ValueError, match="max_context"):
        engine.submit(list(range(30)), max_new_tokens=10)
    with pytest.raises(ValueError, match="vocab"):
        engine.submit([VOCAB + 5], max_new_tokens=1)
    with pytest.raises(ValueError, match="empty"):
        engine.submit([], max_new_tokens=1)


def test_sampling_controls():
    logits = jnp.asarray(np.random.default_rng(6)
                         .normal(size=(3, 32)).astype(np.float32))
    rng = jax.random.PRNGKey(0)
    greedy = np.argmax(np.asarray(logits), -1)
    # temperature 0 → greedy; top_k=1 → greedy regardless of temp
    t0 = sample_tokens(logits, rng, jnp.zeros(3), jnp.zeros(3, jnp.int32))
    np.testing.assert_array_equal(np.asarray(t0), greedy)
    k1 = sample_tokens(logits, rng, jnp.full(3, 2.0),
                       jnp.ones(3, jnp.int32))
    np.testing.assert_array_equal(np.asarray(k1), greedy)
    # top_k restricts support
    k4 = sample_tokens(logits, jax.random.PRNGKey(7), jnp.full(3, 1.5),
                       jnp.full(3, 4, jnp.int32))
    top4 = np.argsort(np.asarray(logits), -1)[:, -4:]
    for row, tok in enumerate(np.asarray(k4)):
        assert tok in top4[row]


def sample_tokens_plain(logits, rng, temperature, top_k):
    """The sampler as it was before its work was chosen on the device
    (PR 38): the sort and the draw for every round, whatever the lanes
    ask for.  Kept as the plain reference."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kk = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab) - 1
    thresh = jnp.take_along_axis(desc, kk[:, None], axis=-1)
    filtered = jnp.where(logits >= thresh, logits, -jnp.inf)
    scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)


SAMPLER_LANES, SAMPLER_VOCAB = 6, 997
#: (temperature, top_k, live) a lane, by what the round's sampler has
#: to run for them
SAMPLER_ROUNDS = {
    "all_greedy": ([0.0] * 6, [0] * 6, [1] * 6),
    "temperature_only": ([0.7, 1.0, 1.3, 2.0, 0.2, 5.0], [0] * 6, [1] * 6),
    "top_k_on_every_lane": ([0.7, 1.0, 1.3, 2.0, 0.2, 5.0],
                            [1, 2, 40, 500, 7, 996], [1] * 6),
    "mixed": ([0.0, 0.0, 0.9, 0.0, 1.5, 0.0], [0, 0, 5, 0, 0, 0],
              [1] * 6),
    "top_k_at_and_past_the_vocabulary": (
        [1.0] * 6, [997, 998, 5000, 2 ** 31 - 1, 996, 0], [1] * 6),
    "top_k_without_a_temperature": ([0.0] * 6, [3, 0, 40, 1, 0, 997],
                                    [1] * 6),
    "a_temperature_beside_top_k_on_a_greedy_lane": (
        [0.0, 1.2, 0.0, 0.8, 0.0, 0.0], [9, 0, 0, 0, 3, 0], [1] * 6),
    "dead_lane_with_stale_fields": ([0.0, 0.0, 3.0, 0.0, 0.0, 0.0],
                                    [0, 0, 40, 0, 0, 0],
                                    [1, 1, 0, 1, 1, 0]),
    "dead_lane_beside_a_sampling_lane": ([0.0, 1.1, 3.0, 0.0, 0.0, 0.0],
                                         [0, 0, 1, 0, 7, 0],
                                         [1, 1, 0, 1, 1, 0]),
}


def sampler_round(name):
    """(temperature, top_k, live) of `SAMPLER_ROUNDS[name]` as the
    sampler takes them."""
    temperature, top_k, live = SAMPLER_ROUNDS[name]
    return (jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(live, bool))


def sampler_logits(tied: bool):
    logits = np.random.default_rng(38).normal(
        size=(SAMPLER_LANES, SAMPLER_VOCAB)).astype(np.float32)
    if tied:
        # few distinct values: the row's best, its k-th largest and its
        # smallest are each shared by many entries
        logits = np.round(logits)
    return jnp.asarray(logits)


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("name", list(SAMPLER_ROUNDS))
def test_sampler_serves_the_tokens_of_its_plain_form(name, tied):
    """Whatever a round's lanes ask for, a live lane's token is the one
    the unconditional sampler gives from the same key — and every
    lane's where every lane is live."""
    temperature, top_k, live = sampler_round(name)
    logits = sampler_logits(tied)
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        got = np.asarray(jax.jit(sample_tokens)(
            logits, key, temperature, top_k, live))
        want = np.asarray(sample_tokens_plain(logits, key, temperature,
                                              top_k))
        live = np.asarray(live)
        np.testing.assert_array_equal(got[live], want[live])
        assert got.dtype == np.int32


def test_a_dead_lane_keeps_no_branch_alive():
    """Which branch ran, read off the dead lane's own token: beside
    live greedy lanes it is the argmax though its stale row asks for a
    draw (no draw was made); beside a live lane with a temperature and
    no `top_k` it is a free draw though its stale `top_k` is 1 (no
    sort was made)."""
    logits = sampler_logits(False)
    best = np.argmax(np.asarray(logits), -1)
    key = jax.random.PRNGKey(11)

    stale = sampler_round("dead_lane_with_stale_fields")
    plain = np.asarray(sample_tokens_plain(logits, key, *stale[:2]))
    assert plain[2] != best[2]            # the draw would have shown
    np.testing.assert_array_equal(sample_tokens(logits, key, *stale), best)
    # top_k = 1 under the sort is the argmax; without it, a draw at
    # temperature 3 over 997 logits
    beside = sample_tokens(
        logits, key, *sampler_round("dead_lane_beside_a_sampling_lane"))
    assert beside[2] != best[2]


def sample_rounds(engine):
    """(`generation_sample_rounds_total_<path>` by path, the decode
    rounds and prefills the engine served)."""
    value = engine.registry.counter
    return ({path: value(f"generation_sample_rounds_total_{path}").value
             for path in SAMPLE_PATHS},
            engine._h_decode.calls + engine._h_prefill.calls)


@pytest.mark.parametrize("sampling, path", [
    ({}, "argmax"), ({"top_k": 5}, "argmax"),
    ({"temperature": 0.9}, "draw"),
    ({"temperature": 0.9, "top_k": 5}, "sort")],
    ids=["greedy", "top_k_without_a_temperature", "temperature", "top_k"])
def test_sample_rounds_name_the_path_the_lanes_ask_for(lm, sampling,
                                                       path):
    """One increment a decode round and a prefill, all of them under
    the path the run's lanes ask for; the scheduler's two counts are
    back at nought when every lane is released."""
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=2, block_size=8,
                              max_context=64, registry=MetricsRegistry())
    streams = [engine.submit(list(range(3, 9 + n)), max_new_tokens=5 + n,
                             **sampling) for n in range(3)]
    engine.run_until_idle()
    assert [len(s.tokens()) for s in streams] == [5, 6, 7]
    counts, served = sample_rounds(engine)
    assert served >= 3 + 7
    assert counts[path] == served == sum(counts.values())
    assert (engine.scheduler.n_drawing, engine.scheduler.n_sorting) \
        == (0, 0)


def test_sample_rounds_follow_the_lanes_through_a_mixed_run(lm):
    """A `top_k` request beside greedy ones costs its rounds the sort
    and no round after it (one round's lead at most: the round
    enqueued before its last token was collected); a preempted lane
    leaves the counts as a released one does."""
    model, params = lm
    engine = GenerationEngine(model, params, max_slots=3, block_size=8,
                              max_context=64, registry=MetricsRegistry())
    engine.submit(list(range(5, 12)), max_new_tokens=20)
    engine.submit(list(range(7, 12)), max_new_tokens=4, temperature=0.8,
                  top_k=3)
    engine.submit(list(range(2, 12)), max_new_tokens=20, temperature=0.0,
                  top_k=7)
    engine.step()
    sched = engine.scheduler
    assert (sched.n_drawing, sched.n_sorting) == (1, 1)
    with engine._lock:
        engine._drain("preempt")
        while sched.slotted():
            sched._preempt_newest()
    assert (sched.n_drawing, sched.n_sorting) == (0, 0)
    engine.run_until_idle()
    assert (sched.n_drawing, sched.n_sorting) == (0, 0)
    counts, served = sample_rounds(engine)
    assert sum(counts.values()) == served
    assert counts["draw"] == 0
    # the sampled request's prefills (one, and one after the
    # preemption) and its three or four decode rounds
    assert 2 + 3 <= counts["sort"] <= 2 + 5
    assert counts["argmax"] >= 4 + 15


# ----------------------------------------------------------------------
# end-to-end: streamed /generate through ServingServer
# ----------------------------------------------------------------------

def test_streamed_generate_end_to_end(lm, eng):
    import json
    from urllib.request import urlopen

    from analytics_zoo_tpu.serving import InputQueue, ServingServer

    model, params = lm
    srv = ServingServer(generation_engine=eng).start()
    try:
        iq = InputQueue(srv.host, srv.port)
        rng = np.random.default_rng(7)
        prompt = list(rng.integers(0, VOCAB, 9))
        toks = []
        for t in iq.generate(prompt, max_new_tokens=8):
            toks.append(t)
        _assert_greedy(model, params, prompt, toks)
        assert iq.last_generate["n_tokens"] == 8
        assert iq.last_generate["finish_reason"] == "length"
        # concurrent streams share the decode batch
        import threading
        outs = {}

        def go(j):
            c = InputQueue(srv.host, srv.port)
            p = list(np.random.default_rng(20 + j)
                     .integers(0, VOCAB, 5 + j))
            outs[j] = (p, c.generate_tokens(p, max_new_tokens=6))

        threads = [threading.Thread(target=go, args=(j,))
                   for j in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for j, (p, o) in outs.items():
            _assert_greedy(model, params, p, o)
        # still exactly one compiled decode program
        assert eng.decode_compile_count == 1
        # bad request surfaces as an HTTP error, not a hang
        with pytest.raises(RuntimeError, match="serving error"):
            list(iq.generate([VOCAB + 9], max_new_tokens=2))
        # /metrics exposes the generation decomposition
        text = urlopen(f"http://{srv.host}:{srv.port}/metrics",
                       timeout=10).read().decode()
        for key in ("generation_tokens_total",
                    "generation_cache_occupancy",
                    "generation_prefill_seconds",
                    "generation_decode_seconds"):
            assert key in text, key
        # /stats carries the live generation snapshot
        stats = json.loads(urlopen(
            f"http://{srv.host}:{srv.port}/stats", timeout=10).read())
        assert "generation" in stats
        assert stats["generation"]["tokens_total"] >= 8
    finally:
        srv.stop()


def test_generation_only_server_rejects_predict(lm, eng):
    from analytics_zoo_tpu.serving import InputQueue, ServingServer

    model, params = lm
    srv = ServingServer(generation_engine=eng).start()
    try:
        iq = InputQueue(srv.host, srv.port)
        with pytest.raises(RuntimeError, match="generation-only"):
            iq.predict(np.zeros(4, np.float32))
    finally:
        srv.stop()
