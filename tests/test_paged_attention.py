"""Paged decode attention + int8 KV quantization suite (PR 6,
docs/kernels.md "Paged decode attention", docs/generation.md "KV
quantization"): kernel-vs-concat-path logit parity across block sizes,
every candidate block-gather config and ragged ctx_lens (including a
lane mid-preemption), the XLA fallback's bit-match contract, the int8
round-trip error bound, the decode-shaped tuner key family, and the
zero-recompile guarantee with the paged kernel + quantized blocks +
full telemetry armed."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    paged_decode_attention,
)
from analytics_zoo_tpu.ops.pallas.paged_attention import (
    DEFAULT_BLOCK_GATHER,
    paged_decode_candidates,
)
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    GenerationEngine,
    dequantize_kv_tokens,
    quantize_kv_tokens,
)

VOCAB = 61
H, D = 4, 16


L, LAYER = 3, 1


def _scene(bs, mb, s=4, h=H, d=D, seed=0, quantized=False,
           dtype=np.float32, ctx=None):
    """One decode scene: a whole pool in the engine's block view
    [L, 2, nb, bs, h*d] (the layers other than LAYER hold garbage of
    their own — a kernel that read the wrong layer would see it),
    per-lane tables and RAGGED ctx_lens — lane 0 is freshly preempted
    (null table, ctx 0: a dead lane), lane 1 holds a partial first
    block, the last lane is block-aligned full; the rest end inside a
    block (or the lanes' `ctx` as given).  Tables beyond each lane's
    blocks stay null and pool contents are garbage there — the mask
    must hide all of it."""
    rng = np.random.default_rng(seed)
    nb = s * mb + 1
    pool = jnp.asarray(rng.normal(size=(L, 2, nb, bs, h, d)), dtype)
    tables = np.zeros((s, mb), np.int32)
    perm = 1 + rng.permutation(nb - 1)
    choices = [0, max(1, bs // 2)] + [
        int(rng.integers(1, mb * bs)) for _ in range(max(0, s - 3))
    ] + [mb * bs] if ctx is None else list(ctx)
    ctx = np.zeros(s, np.int32)
    for i in range(s):
        ctx[i] = choices[i]
        used = -(-int(ctx[i]) // bs)
        tables[i, :used] = perm[i * mb:i * mb + used]
    q = rng.normal(size=(s, h, d)).astype(np.float32)
    nk = rng.normal(size=(s, h, d)).astype(np.float32)
    nv = rng.normal(size=(s, h, d)).astype(np.float32)
    scale = None
    if quantized:
        pool, scale = quantize_kv_tokens(pool)
    return dict(q=q, new_k=nk, new_v=nv, tables=tables, ctx=ctx,
                kv_pool=pool.reshape(L, 2, nb, bs, h * d),
                kv_scale=scale)


def _concat_reference(sc):
    """The pre-paged decode path, computed independently: host-side
    gather of LAYER's token rows (dequantizing first when the pool is
    int8) + the dot_product_attention KV-cache read path."""
    s, h, d = sc["q"].shape
    bs = sc["kv_pool"].shape[3]
    flat = np.asarray(sc["kv_pool"][LAYER].astype(jnp.float32)
                      ).reshape(2, -1, h, d)
    if sc["kv_scale"] is not None:
        flat = flat * np.asarray(sc["kv_scale"][LAYER]).reshape(
            2, -1, 1, 1)
    tok = (sc["tables"][:, :, None] * bs
           + np.arange(bs)[None, None, :]).reshape(s, -1)
    out = dot_product_attention(
        jnp.asarray(sc["q"])[:, None], jnp.asarray(sc["new_k"])[:, None],
        jnp.asarray(sc["new_v"])[:, None], compute_dtype=jnp.float32,
        ctx_k=jnp.asarray(flat[0][tok]), ctx_v=jnp.asarray(flat[1][tok]),
        ctx_len=jnp.asarray(sc["ctx"]))
    return np.asarray(out[:, 0])


def _paged(sc, impl, block_gather=None, traced_layer=False):
    """`traced_layer`: LAYER reaches the call as a traced int32 scalar,
    as a looped decoder's pool slot does (the kernel's scalar
    prefetch, the XLA form's gather index)."""
    def run(layer):
        return paged_decode_attention(
            jnp.asarray(sc["q"]), jnp.asarray(sc["new_k"]),
            jnp.asarray(sc["new_v"]), sc["kv_pool"],
            jnp.asarray(sc["tables"]), jnp.asarray(sc["ctx"]),
            layer=layer, kv_scale=sc["kv_scale"],
            impl=impl, block_gather=block_gather,
            interpret=(True if impl == "pallas" else None))
    if traced_layer:
        return np.asarray(jax.jit(run)(jnp.int32(LAYER)))
    return np.asarray(run(LAYER))


# ----------------------------------------------------------------------
# parity: paged kernel / XLA fallback vs the concat path
# ----------------------------------------------------------------------

def test_xla_fallback_bitmatches_concat_path():
    """The fallback IS the pre-paged decode path: identical gather,
    identical concat-attend — bit for bit, not merely close."""
    sc = _scene(bs=8, mb=4, seed=1)
    np.testing.assert_array_equal(_paged(sc, "xla"),
                                  _concat_reference(sc))


def test_pallas_parity_across_block_sizes_and_gather_configs():
    """Every candidate block-gather config, at two pool block sizes,
    against the concat path over ragged ctx_lens (empty lane, partial
    block, mid-block, block-aligned full).  Whatever schedule the
    tuner picks, the logits must not move."""
    for bs, mb in ((8, 4), (16, 6)):
        sc = _scene(bs=bs, mb=mb, seed=2 + bs)
        ref = _concat_reference(sc)
        cands = paged_decode_candidates(bs, mb, H, D)
        assert len(cands) >= 2, cands
        for cfg in cands:
            out = _paged(sc, "pallas",
                         block_gather=cfg["block_gather"])
            np.testing.assert_allclose(
                out, ref, atol=2e-5, rtol=2e-5,
                err_msg=f"bs={bs} cfg={cfg}")


#: (heads, head_dim, table blocks, lane contexts, layer traced):
#: GPT-2 small's 12 x 64 = 768 merged columns, six whole lane tiles; a
#: toy width whose 4 x 16 = 64 is no multiple of 128, which only has to
#: be correct (both: `_scene`'s contexts, LAYER a Python int); and the
#: looped decoder's 16 x 128 = 2048 (`ouro_2p6b_serve`: tables of 20
#: blocks, lanes dead, at one position, mid-block and at a full table,
#: the pool slot a traced int32 scalar)
WIDTHS = {"768": (12, 64, 4, None, False),
          "toy64": (4, 16, 4, None, False),
          "looped2048": (16, 128, 20, (0, 1, 121, 320), True)}


@pytest.mark.parametrize("block_gather", [1, 2, 4, 8])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_pallas_on_the_merged_layout(width, pool, block_gather):
    """The kernel (interpret mode) on the pool as it is stored — whole,
    [L, 2, nb, bs, h*d], the layer an index — against the XLA fallback
    and, through it, the concat oracle: bf16 and int8 pools, every
    gather width, a context that ends inside a block, a dead lane."""
    h, d, mb, ctx, traced = WIDTHS[width]
    sc = _scene(bs=16, mb=mb, s=4, h=h, d=d, seed=31 + block_gather,
                quantized=pool == "int8",
                dtype=jnp.bfloat16 if pool == "bf16" else np.float32,
                ctx=ctx)
    assert sc["kv_pool"].shape[-1] == h * d
    assert sc["ctx"][0] == 0 and 0 < sc["ctx"][2] % 16   # dead, ragged
    assert sc["ctx"][-1] == mb * 16                      # a full table
    ref = _paged(sc, "xla")
    np.testing.assert_array_equal(ref, _concat_reference(sc))
    out = _paged(sc, "pallas", block_gather=block_gather,
                 traced_layer=traced)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
    # the dead lane is pure self-attention, whatever the pool holds
    np.testing.assert_allclose(out[0], sc["new_v"][0], atol=1e-6)


def test_mid_preemption_lane_is_inert():
    """A lane preempted between steps (blocks freed -> null table,
    ctx 0) must neither read garbage nor perturb its neighbours: its
    output is pure self-attention (= new_v at q_len=1), and the other
    lanes' outputs are identical whether the dead lane's table is
    null or stale garbage ids."""
    sc = _scene(bs=8, mb=4, seed=7)
    out_null = _paged(sc, "pallas")
    np.testing.assert_allclose(out_null[0], sc["new_v"][0],
                               atol=1e-6, rtol=1e-6)
    stale = dict(sc)
    stale_tables = sc["tables"].copy()
    stale_tables[0] = np.arange(1, stale_tables.shape[1] + 1)
    stale["tables"] = stale_tables
    out_stale = _paged(stale, "pallas")
    np.testing.assert_array_equal(out_null[1:], out_stale[1:])
    np.testing.assert_allclose(out_stale[0], sc["new_v"][0],
                               atol=1e-6, rtol=1e-6)


# ----------------------------------------------------------------------
# int8 quantized pools
# ----------------------------------------------------------------------

def test_int8_roundtrip_error_bound():
    """Per-token-slot symmetric quantization: the round-trip error of
    every element is bounded by half a quantization step of ITS OWN
    token's scale (no cross-token drift — appends never requantize
    neighbours), and all-zero slabs survive exactly."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(3, 40, H, D)).astype(np.float32) * \
        rng.uniform(0.1, 8.0, size=(3, 40, 1, 1)).astype(np.float32)
    x[0, 0] = 0.0                      # amax == 0 slab
    q, scale = quantize_kv_tokens(jnp.asarray(x))
    assert np.asarray(q).dtype == np.int8
    deq = np.asarray(dequantize_kv_tokens(q, scale))
    err = np.abs(x - deq)
    bound = np.asarray(scale)[..., None, None] * 0.5 + 1e-7
    assert (err <= bound).all(), float((err - bound).max())
    np.testing.assert_array_equal(deq[0, 0], 0.0)
    # and the relative error per token slab is the int8 textbook one
    amax = np.abs(x).max(axis=(-2, -1))
    rel = err.max(axis=(-2, -1))[amax > 0] / amax[amax > 0]
    assert rel.max() <= 0.5 / 127 + 1e-6


def test_int8_pallas_matches_xla_dequant():
    """The kernel's dequant-on-read (scales folded into score/prob
    columns) vs the fallback's dequantize-then-attend: same math."""
    sc = _scene(bs=8, mb=4, seed=13, quantized=True)
    ref = _paged(sc, "xla")
    np.testing.assert_array_equal(ref, _concat_reference(sc))
    for cfg in paged_decode_candidates(8, 4, H, D):
        out = _paged(sc, "pallas", block_gather=cfg["block_gather"])
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5,
                                   err_msg=str(cfg))


def test_int8_attention_close_to_f32_reference():
    """End-to-end quantization quality: int8 pool attention vs the
    same attention over the unquantized f32 pool."""
    sc32 = _scene(bs=8, mb=4, seed=17)
    scq = _scene(bs=8, mb=4, seed=17, quantized=True)
    out32 = _paged(sc32, "xla")
    outq = _paged(scq, "xla")
    # |values| ~ N(0,1): per-element quant noise ~ amax/254 ~ 1.5e-2;
    # softmax averaging keeps the output within a few quanta
    np.testing.assert_allclose(outq, out32, atol=0.08, rtol=0.08)


# ----------------------------------------------------------------------
# the decode-shaped tuner key family
# ----------------------------------------------------------------------

@pytest.fixture()
def clean_tuner():
    from analytics_zoo_tpu.common.context import OrcaContext
    from analytics_zoo_tpu.ops import tuning
    prev_dir = OrcaContext.kernel_tuning_cache_dir
    prev_mode = OrcaContext.kernel_tuning_mode
    tuning.clear_memo()
    yield tuning
    OrcaContext.kernel_tuning_cache_dir = prev_dir
    OrcaContext.kernel_tuning_mode = prev_mode
    tuning.clear_memo()


def test_decode_key_family_bucketing(clean_tuner):
    """paged_decode keys bucket pow2 per dim — 5 lanes and 8 lanes
    share an entry, as do head dims 48 and 64 — and name-sort their
    dims so the family reads bs,d,lanes."""
    tuning = clean_tuner
    k1 = tuning.make_key("paged_decode",
                         {"bs": 16, "lanes": 5, "d": 48},
                         jnp.int8, "tpu")
    k2 = tuning.make_key("paged_decode",
                         {"d": 64, "bs": 16, "lanes": 8},
                         jnp.int8, "tpu")
    assert k1 == k2 == "paged_decode|tpu|int8|bs=16,d=64,lanes=8"


def test_decode_default_table_entries_resolve(clean_tuner):
    """The checked-in warm starts actually sit under the keys the
    dispatch path computes — a renamed dim or dtype would silently
    orphan every entry."""
    tuning = clean_tuner
    with open(tuning.DEFAULT_TABLE_PATH) as f:
        entries = json.load(f)["entries"]
    for dtype in (jnp.float32, jnp.bfloat16, jnp.int8):
        key = tuning.make_key("paged_decode",
                              {"bs": 16, "lanes": 8, "d": 64},
                              dtype, "tpu")
        assert key in entries, key
        assert entries[key]["config"]["block_gather"] >= 1
    # the looped decoder's key (16 lanes, 16 heads x 128, a bf16 pool,
    # tables of 20 blocks): its row resolves through the dispatch
    # path's lookup, and its width is one the kernel's VMEM estimate
    # still offers — gather 8 among them
    key = tuning.make_key("paged_decode", {"bs": 16, "lanes": 16, "d": 128},
                          jnp.bfloat16, "tpu")
    assert key == "paged_decode|tpu|bfloat16|bs=16,d=128,lanes=16"
    offered = [c["block_gather"]
               for c in paged_decode_candidates(16, 20, 16, 128, 2)]
    assert 8 in offered, offered
    assert entries[key]["config"]["block_gather"] in offered


def test_decode_tuning_persists_and_reloads(clean_tuner, tmp_path):
    """An explicit paged_decode search persists its winner and a fresh
    process answers from the file without benchmarking — the flash
    persistence contract, on the new key family."""
    from analytics_zoo_tpu.common.context import OrcaContext
    tuning = clean_tuner
    OrcaContext.kernel_tuning_cache_dir = str(tmp_path)
    shape = {"bs": 16, "lanes": 8, "d": 64}
    calls = []

    def bench(cfg):
        calls.append(cfg)
        return 1.0 / cfg["block_gather"]   # widest gather wins

    cands = paged_decode_candidates(16, 8, 8, 64)
    cfg = tuning.tune("paged_decode", shape, jnp.float32, cands, bench)
    assert cfg == {"block_gather": 8}
    assert len(calls) == len(cands)
    path = os.path.join(str(tmp_path), tuning.CACHE_FILE_NAME)
    key = tuning.make_key("paged_decode", shape, jnp.float32)
    with open(path) as f:
        assert json.load(f)["entries"][key]["config"] == cfg

    tuning.clear_memo()
    got = tuning.get_config("paged_decode", shape, jnp.float32,
                            default={"block_gather": 1},
                            allow_search=False)
    assert got == cfg and len(calls) == len(cands)
    assert tuning.config_source("paged_decode", shape,
                                jnp.float32) == "cache"


# ----------------------------------------------------------------------
# engine end-to-end: the real kernel in the decode loop
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_params():
    model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=256)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


def _assert_greedy(model, params, prompt, out):
    """Greedy decode == teacher forcing: every generated token is the
    argmax at its preceding position of ONE full-recompute forward."""
    assert out, "no tokens generated"
    seq = list(prompt) + list(out)
    logits, _, _ = model.apply(
        {"params": params}, jnp.asarray(seq)[None],
        jnp.arange(len(seq))[None], token_mask=jnp.ones((1, len(seq))))
    want = np.argmax(np.asarray(logits[0]), axis=-1)
    for i, tok in enumerate(out):
        assert tok == want[len(prompt) + i - 1], (i, tok)


def test_engine_decodes_through_pallas_kernel(lm_params):
    """The whole engine loop — scheduler, pool writes, block tables —
    driving the REAL Pallas kernel (CPU interpreter), greedy-matching
    the full recompute, with exactly one compiled decode step."""
    model, params = lm_params
    pallas_model = CausalLM(
        vocab=model.vocab, hidden_size=model.hidden_size,
        n_head=model.n_head, n_block=model.n_block,
        intermediate_size=model.intermediate_size,
        max_position_len=model.max_position_len,
        paged_attention_impl="pallas")
    eng = GenerationEngine(pallas_model, params, max_slots=2,
                           block_size=8, max_context=32)
    eng.warmup()
    rng = np.random.default_rng(23)
    for L, n in ((5, 4), (11, 3)):
        prompt = list(rng.integers(0, VOCAB, L))
        _assert_greedy(model, params, prompt,
                       eng.generate(prompt, max_new_tokens=n))
    assert eng.decode_compile_count == 1


def test_zero_recompile_paged_int8_with_full_telemetry(lm_params):
    """The PR 2/4/5 invariant with the PR 6 stack armed: paged decode
    dispatch + int8-quantized pool + SLO targets + per-fenced-step
    memory sampling + the stall watchdog — the decode hot loop still
    compiles exactly once, and the sampler sees the logical/physical
    pool split (the residency gauge)."""
    from analytics_zoo_tpu.common.context import OrcaContext
    from analytics_zoo_tpu.observability import get_registry, memory
    model, params = lm_params
    prev_slo = OrcaContext.slo_targets
    prev_mem = OrcaContext.memory_sample_interval_s
    prev_wd = OrcaContext.watchdog_deadline_s
    try:
        OrcaContext.slo_targets = {"ttft_s": 30.0, "e2e_s": 60.0}
        OrcaContext.memory_sample_interval_s = 0.0
        OrcaContext.watchdog_deadline_s = 60.0
        engine = GenerationEngine(model, params, max_slots=2,
                                  block_size=8, max_context=64,
                                  kv_quantization="int8")
        assert engine.cache.quantization == "int8"
        assert engine.cache.kv.dtype == jnp.int8
        assert engine.watchdog is not None
        engine.warmup()
        for prompt in ([1, 2, 3], [4, 5, 6, 7], [8]):
            assert engine.generate(prompt, max_new_tokens=5)
        assert engine.decode_compile_count == 1, \
            "decode recompiled with int8 KV + telemetry armed"
        latest = memory.snapshot()["latest"]
        assert latest is not None
        assert latest["kv_pool_pool_bytes_physical"] > 0
        assert (latest["kv_pool_pool_bytes_logical"]
                > latest["kv_pool_pool_bytes_physical"])
        # physical = int8 values + f32 scales; logical = f32 here
        stats = engine._kv_pool_stats()
        assert stats["pool_bytes_logical"] == \
            engine.cache.kv.size * 4
        engine.watchdog.stop()
    finally:
        OrcaContext._slo_targets = prev_slo
        OrcaContext.memory_sample_interval_s = prev_mem
        OrcaContext.watchdog_deadline_s = prev_wd
        get_registry()  # keep import used; registry state is shared


def test_engine_int8_stays_greedy_exact_on_small_model(lm_params):
    """int8 KV noise must not flip this small model's greedy argmax —
    a soft end-to-end accuracy gate on the quantized read+write path
    (the tight numeric bound lives in the roundtrip/parity tests)."""
    model, params = lm_params
    eng = GenerationEngine(model, params, max_slots=2, block_size=8,
                           max_context=48, kv_quantization="int8")
    eng.warmup()
    rng = np.random.default_rng(29)
    prompt = list(rng.integers(0, VOCAB, 9))
    _assert_greedy(model, params, prompt,
                   eng.generate(prompt, max_new_tokens=6))
    assert eng.decode_compile_count == 1
