"""`DecoderLM` with `latent_attention` (serving/generation/decoder.py:
one cached row a token a layer, expanded at prefill, absorbed at
decode) against the plain reference the benchmark keeps
(`benchmarks/reference/sarvam_mla_ref.py`, the expanded form only) at
a small size on the CPU: hidden 64, 4 heads of 16 + 8 (values 16) over
a latent of 32, YaRN over the 8 rotary columns, 8 experts top-2 with a
shared one (4 held), the first of 4 FFNs dense.  Logits, not tokens.

Tolerances.  Everything here runs in float32 on the CPU, program and
reference alike; what separates them is the order of float32 sums —
and, at decode, that the program multiplies the query into the key
up-projection first (the absorbed form) where the reference expands
every key: a few 1e-6 on logits of size 0.1.  5e-5 absolute, as
`test_decoder_lm.py`; a wrong frequency, a missing factor on the
softmax scale or a row read at the wrong slot moves a logit by 1e-3 or
more."""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import sarvam_mla_ref as ref  # noqa: E402
from test_decoder_lm import capture, seeded  # noqa: E402

from analytics_zoo_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
)
from analytics_zoo_tpu.ops.attention import (  # noqa: E402
    dot_product_attention,
    latent_decode_attention,
)
from analytics_zoo_tpu.serving.generation import (  # noqa: E402
    DecoderLM,
    GenerationEngine,
    kv_cache,
)
from analytics_zoo_tpu.serving.generation import decoder  # noqa: E402

TOL = 5e-5
VOCAB = 97
#: sarvam-105b's `rope_scaling`, as published
YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=4096,
            type="deepseek_yarn")


def toy_config(**over):
    config = dict(
        model_type="sarvam_mla", vocab_size=VOCAB, hidden_size=64,
        num_attention_heads=4, head_dim=40, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, q_head_dim=24,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
        routed_scaling_factor=2.5, first_k_dense_replace=1,
        num_hidden_layers=4, rope_theta=10000,
        # the ramp between dimensions 0 and 1 of 4: both ends of it and
        # the factor on the scale are in every toy logit
        rope_scaling=dict(YARN, original_max_position_embeddings=32),
        rms_norm_eps=1e-6, max_position_embeddings=4096,
        experts_held=[2, 4])
    config.update(over)
    return config


@pytest.fixture(scope="module")
def lm():
    config = toy_config()
    model = DecoderLM.from_config(config)
    return config, model, seeded(model)


def engine_of(lm, **kw):
    _, model, params = lm
    kw = dict(dict(max_slots=4, block_size=4, max_context=64,
                   prefill_buckets=[8, 16, 32, 64],
                   registry=MetricsRegistry()), **kw)
    return GenerationEngine(model, params, **kw)


# --- the constants, by hand --------------------------------------------

def test_yarn_constants_by_hand():
    """sarvam-105b's own: 64 rotary columns, theta 10000, factor 40
    over an original context of 4096."""
    low, high = decoder.yarn_correction_range(64, 10000.0, YARN)
    # a dimension turns 4096 * f_i / 2 pi times over the original
    # context: 32 turns at i = 10.47, one at i = 22.51
    assert (low, high) == (10, 23)
    assert ref.yarn_range(64, 10000.0, YARN) == (10, 23)
    m = decoder.yarn_mscale(40, 1)
    assert m == pytest.approx(0.1 * math.log(40) + 1) \
        == pytest.approx(1.36889, abs=1e-5)
    inv = decoder.yarn_frequencies(64, 10000.0, YARN)
    f = lambda i: 10000.0 ** (-2 * i / 64)
    assert inv[3] == pytest.approx(f(3))               # under the ramp
    assert inv[16] == pytest.approx(                    # on it: 6 / 13
        f(16) / 40 * (6 / 13) + f(16) * (7 / 13), rel=1e-6)
    assert inv[30] == pytest.approx(f(30) / 40)        # past it
    np.testing.assert_allclose(
        inv, np.asarray(ref.yarn_inv_freq(64, 10000.0, YARN)), rtol=1e-6)
    # the softmax scale carries the factor squared, cos and sin none
    factor, sigma = ref.attention_constants(
        dict(qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling=YARN))
    assert factor == 1.0
    assert sigma == pytest.approx(192 ** -0.5 * 1.36889 ** 2, rel=1e-5)


def test_the_controls_rounding_is_e4m3():
    """The reference lowers a cached row by arithmetic (the chip's
    compiler keeps a conversion down and straight back up as excess
    precision): it is the conversion, value for value."""
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(size=20000) * 100,
                        rng.normal(size=20000) * 0.01,
                        rng.uniform(-448, 448, 20000),
                        [448.0, -448.0, 0.0, 2.0 ** -9, 2.0 ** -10,
                         1.5 * 2.0 ** -9, 0.0017]]).astype(np.float32)
    x = jnp.asarray(np.clip(x, -448, 448))
    np.testing.assert_array_equal(
        np.asarray(ref.round_e4m3(x)),
        np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.float32)))
    rows = jnp.asarray(rng.normal(size=(5, 40)), jnp.float32)
    lowered = np.asarray(ref.lower_rows(rows, "fp8"))
    gap = np.linalg.norm(lowered - rows, axis=-1) \
        / np.linalg.norm(rows, axis=-1)
    assert 0.01 < gap.mean() < 0.05         # three mantissa bits: 3%


def test_geometry_and_leaf_names(lm):
    config, model, params = lm
    assert model.latent and model.kv_geometry() == (4, 1, 40, 1)
    assert kv_cache.pool_geometry(model) == (4, 1, 40)
    assert kv_cache.pool_rows(model) == 1
    assert model.layer_types == ("latent_attention",) * 4
    assert model.mlp_layer_types == ("dense",) + ("sparse",) * 3
    kinds = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert kinds == {"kernel", "embedding", "scale", "bias"}
    assert params["block_0_q"]["kernel"].shape == (64, 4 * 24)
    assert params["block_0_kv_a"]["kernel"].shape == (64, 32 + 8)
    assert params["block_0_kv_b"]["kernel"].shape == (32, 4 * (16 + 16))
    assert params["block_0_o"]["kernel"].shape == (4 * 16, 64)
    assert params["block_0_q_norm"]["scale"].shape == (24,)
    assert params["block_0_kv_a_norm"]["scale"].shape == (32,)


def test_a_model_mixes_no_latent_layer_with_a_kv_layer():
    with pytest.raises(ValueError, match="one pool holds one form"):
        DecoderLM(vocab=8, hidden_size=8, n_head=2, n_kv_head=2,
                  head_dim=4, intermediate_size=8, kv_lora_rank=4,
                  qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
                  layer_types=("latent_attention", "full_attention"),
                  mlp_layer_types=("dense", "dense"))


# --- the model against the reference ------------------------------------

@pytest.mark.parametrize("mscale", [1, 0.5])
def test_whole_prompt_forward_matches_the_reference(lm, mscale):
    """`mscale` 1 is the published pair (cos and sin carry no factor);
    0.5 against an `mscale_all_dim` of 1 puts one on them."""
    config, model, params = lm
    if mscale != 1:
        config = toy_config(rope_scaling=dict(
            config["rope_scaling"], mscale=mscale))
        model = DecoderLM.from_config(config)
        assert model.latent_constants()[1] == pytest.approx(
            (0.05 * math.log(40) + 1) / (0.1 * math.log(40) + 1))
    tokens = np.random.default_rng(1).integers(0, VOCAB, 27)
    logits, rows, none = model.apply(
        {"params": params}, jnp.asarray(tokens)[None],
        jnp.arange(27)[None], token_mask=jnp.ones((1, 27)))
    want, margin, cached = ref.forward(params, jnp.asarray(tokens),
                                       config)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL, rtol=0)
    # one row a token a layer, 32 + 8 wide, and no second kind: the
    # rows the reference says a cache holds (past the first sparse
    # layer they would part wherever a near-tie fell the other way:
    # none does in float32)
    assert rows.shape == (4, 1, 27, 1, 40) and none is None
    np.testing.assert_allclose(np.asarray(rows[:, 0, :, 0]),
                               np.stack(cached), atol=TOL, rtol=0)
    assert np.isfinite(np.asarray(margin)).any()


def test_prefill_attention_in_blocks_is_the_whole_square():
    """A key wider than the value takes the blocked form: blocks of 8
    query rows over a prompt of 27 against the one-block form, with the
    padding mask and without, and over a cached context."""
    from analytics_zoo_tpu.ops import attention
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(size=(2, 27, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 27, 4, 16)), jnp.float32)
    mask = (1.0 - (jnp.arange(27) < 20)[None, None, None]) * -1e9
    kw = dict(compute_dtype=jnp.float32, ctx_k=None, ctx_v=None,
              ctx_len=None, scale=0.3)
    for m in (None, mask):
        whole = attention._blocked_attention(q, k, v, mask=m, causal=True,
                                             **kw)
        blocks = attention._blocked_attention(q, k, v, mask=m, causal=True,
                                              q_block=8, **kw)
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                                   atol=1e-6)
    # by hand: position 5 of row 0 reads keys 0..5
    s = np.einsum("hd,khd->hk", q[0, 5], k[0, :6]) * 0.3
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), v[0, :6])
    np.testing.assert_allclose(np.asarray(whole[0, 5]), want, atol=1e-5)
    # over a context: 12 cached columns, 9 and 12 of them live
    ck = jnp.asarray(rng.normal(size=(2, 12, 4, 24)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(2, 12, 4, 16)), jnp.float32)
    kw.update(ctx_k=ck, ctx_v=cv, ctx_len=jnp.asarray([9, 12]))
    whole = dot_product_attention(q, k, v, **{**kw, "compute_dtype":
                                              jnp.float32})
    blocks = attention._blocked_attention(q, k, v, mask=None, causal=False,
                                          q_block=8, **kw)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=1e-6)
    keys = np.concatenate([ck[0, :9], k[0, :3]])
    vals = np.concatenate([cv[0, :9], v[0, :3]])
    s = np.einsum("hd,khd->hk", q[0, 2], keys) * 0.3
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), vals)
    np.testing.assert_allclose(np.asarray(whole[0, 2]), want, atol=1e-5)


@pytest.mark.parametrize("attention", ["paged", "concat"])
def test_engine_prefill_then_decode_matches_the_reference(lm, attention):
    """Prefill (expanded), then decoding through the latent pool
    (`paged`: the absorbed form, the XLA path of the latent op;
    `concat`: the gathered rows expanded), four lanes, block-aligned
    contexts and not: every served position's logits against the
    reference's full forward over the prompt and the served tokens."""
    config, model, params = lm
    eng = engine_of(lm, decode_attention=attention)
    eng.warmup()
    got = capture(eng)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (3, 8, 13, 24)]
    streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    for prompt, stream in zip(prompts, streams):
        tokens = stream.tokens()
        assert len(tokens) == 12
        seq = prompt + tokens[:-1]
        want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(
                got[(tuple(prompt), pos)], want[pos], atol=TOL, rtol=0,
                err_msg=f"prompt of {len(prompt)}, position {pos}")
            assert tokens[pos - len(prompt) + 1] == int(want[pos].argmax())
    assert eng.decode_compile_count == 1
    snap = eng.registry.snapshot()
    assert snap["generation_moe_dropped_total"] == 0
    # one row of (32 + 8) float32 columns a token a layer, four layers
    assert snap["generation_kv_rows_per_token"] == 1
    assert snap["generation_kv_row_bytes"] == 4 * 40 * 4
    # the pool stores a row padded to a lane tile and says so
    assert eng.cache.kv.shape == (4, 1, eng.cache.num_blocks * 4, 128)
    assert eng.cache.logical_nbytes * 128 == eng.cache.physical_nbytes * 40


def test_a_kv_model_reports_two_rows_a_token():
    from test_decoder_lm import toy_config as kv_config
    model = DecoderLM.from_config(kv_config())
    eng = GenerationEngine(model, seeded(model), max_slots=2, block_size=4,
                           max_context=32, registry=MetricsRegistry())
    snap = eng.registry.snapshot()
    assert snap["generation_kv_rows_per_token"] == 2
    assert snap["generation_kv_row_bytes"] == 4 * 2 * 2 * 16 * 4
    assert eng.cache.logical_nbytes == eng.cache.physical_nbytes


def test_absorbed_is_expanded(lm):
    """One set of weights, one pool: a token's logits through the
    absorbed form (the latent op over the paged pool) and through the
    expanded form (the same rows gathered and up-projected) — the
    factor on the softmax scale and the rotation in both."""
    config, model, params = lm
    bs, mb = 4, 6
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, VOCAB, (3, 17))
    ctx_len = np.asarray([17, 9, 14])
    # the rows of each lane's context, from a whole-prompt forward
    _, rows, _ = model.apply({"params": params}, jnp.asarray(tokens),
                             jnp.tile(jnp.arange(17)[None], (3, 1)))
    cache = kv_cache.PagedKVCache(4, 3 * mb + 1, bs, 1, 40, rows=1)
    tables = 1 + np.arange(3 * mb).reshape(3, mb)
    slots = (tables[:, :, None] * bs + np.arange(bs)).reshape(3, -1)
    kv = cache.kv
    for lane in range(3):
        kv, _ = kv_cache.write_kv(
            kv, None, jnp.asarray(slots[lane, :17]),
            rows[:, lane], None)
    nxt = jnp.asarray(rng.integers(0, VOCAB, (3, 1)))
    pos = jnp.asarray(ctx_len)[:, None]
    absorbed, new, _ = model.apply(
        {"params": params}, nxt, pos,
        kv_pool=kv_cache.block_view(kv, bs),
        block_tables=jnp.asarray(tables), ctx_len=jnp.asarray(ctx_len))
    ctx, none = kv_cache.gather_kv(kv, None, jnp.asarray(slots), 1, 40)
    assert none is None and ctx.shape == (4, 3, mb * bs, 1, 40)
    expanded, new2, _ = model.apply(
        {"params": params}, nxt, pos, ctx_k=ctx, ctx_v=None,
        ctx_len=jnp.asarray(ctx_len))
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(new), np.asarray(new2), atol=1e-5)
    # ... and both are the reference's at the lane's next position
    for lane, n in enumerate(ctx_len):
        seq = np.concatenate([tokens[lane, :n], np.asarray(nxt[lane])])
        want = ref.forward(params, jnp.asarray(seq), config)[0]
        np.testing.assert_allclose(np.asarray(absorbed[lane, 0]),
                                   np.asarray(want[-1]), atol=TOL, rtol=0)


# --- the op and its kernel ----------------------------------------------

def latent_case(seed=4, lanes=5, heads=4, width=40, vw=32, bs=4, mb=6,
                dtype=jnp.float32):
    """A latent pool (stored 128 wide) with ragged contexts: a full
    table, a context that ends mid-block, one block, an empty context
    and a dead lane (table all null, context 0)."""
    rng = np.random.default_rng(seed)
    nb = lanes * mb + 1
    pool = np.zeros((2, 1, nb, bs, 128), np.float32)
    pool[..., :width] = rng.normal(size=(2, 1, nb, bs, width))
    tables = 1 + rng.permutation(nb - 1)[:lanes * mb].reshape(lanes, mb)
    ctx_len = np.asarray([mb * bs, 2 * bs + 3, bs, 0, 0][:lanes])
    tables[-1] = 0
    q = rng.normal(size=(lanes, heads, width))
    new = rng.normal(size=(lanes, width))
    return (jnp.asarray(q, dtype), jnp.asarray(new, dtype),
            jnp.asarray(pool, dtype), jnp.asarray(tables, jnp.int32),
            jnp.asarray(ctx_len, jnp.int32))


def test_xla_form_is_latent_attention_by_hand():
    q, new, pool, tables, ctx_len = latent_case()
    out = latent_decode_attention(q, new, pool, tables, ctx_len, layer=1,
                                  value_width=32, scale=0.21, impl="xla")
    assert out.shape == (5, 4, 32)
    for lane in range(5):
        n = int(ctx_len[lane])
        rows = np.concatenate([
            np.asarray(pool)[1, 0, np.asarray(tables)[lane]]
            .reshape(-1, 128)[:n, :40], np.asarray(new)[lane][None]])
        s = np.asarray(q)[lane] @ rows.T * 0.21
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :32]
        np.testing.assert_allclose(np.asarray(out[lane]), want, atol=1e-5)
    # a dead lane and an empty context read their own row alone
    np.testing.assert_allclose(
        np.asarray(out[-1]), np.tile(np.asarray(new)[-1, :32], (4, 1)),
        atol=1e-6)


@pytest.mark.parametrize("block_gather", [1, 2, 4, 8])
def test_kernel_matches_the_xla_form(block_gather):
    """The Pallas latent kernel in the interpreter: ragged contexts, a
    context that ends mid-block, a dead lane on the null block, a table
    that is no multiple of the gather."""
    args = latent_case()
    kw = dict(layer=1, value_width=32, scale=0.21)
    want = latent_decode_attention(*args, impl="xla", **kw)
    got = latent_decode_attention(*args, impl="pallas", interpret=True,
                                  block_gather=block_gather, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6)


def test_kernel_on_a_bf16_pool():
    """The pool's own dtype on the chip: products in bfloat16 with
    float32 sums, as the XLA form's; the two round their probabilities
    at different points (before and after the division), a few 1e-3 of
    values of size 1."""
    args = latent_case(dtype=jnp.bfloat16)
    kw = dict(layer=0, value_width=32, scale=0.21)
    want = latent_decode_attention(*args, impl="xla", **kw)
    got = latent_decode_attention(*args, impl="pallas", interpret=True,
                                  **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2)


def test_engine_through_the_latent_kernel(lm):
    config, _, params = lm
    model = DecoderLM.from_config(config, paged_attention_impl="pallas")
    eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                           max_context=32, prefill_buckets=[16, 32],
                           registry=MetricsRegistry())
    got = capture(eng)
    prompt = np.random.default_rng(3).integers(0, VOCAB, 11).tolist()
    tokens = eng.generate(prompt, max_new_tokens=6)
    seq = prompt + tokens[:-1]
    want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
    for pos in range(len(prompt) - 1, len(seq)):
        np.testing.assert_allclose(got[(tuple(prompt), pos)], want[pos],
                                   atol=TOL, rtol=0)


# --- the pool's latent form ---------------------------------------------

def test_pool_round_trip_in_the_latent_form():
    """write_kv -> gather_kv -> copy_block -> read_block -> restore:
    one row a token, stored padded, the padding zeros."""
    from analytics_zoo_tpu.serving.generation import steps
    cache = kv_cache.PagedKVCache(3, 5, 4, 1, 40, rows=1)
    assert cache.kv.shape == (3, 1, 20, 128)
    assert cache.slab_shape == (3, 1, 4, 128)
    assert cache.token_nbytes == 3 * 40 * 4
    assert cache.logical_nbytes == 3 * 20 * 40 * 4
    assert cache.physical_nbytes == 3 * 20 * 128 * 4
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.normal(size=(3, 6, 1, 40)), jnp.float32)
    dest = jnp.asarray([4, 5, 6, 7, 12, 13])      # block 1, half of 3
    kv, scale = kv_cache.write_kv(cache.kv, None, dest, rows, None)
    assert scale is None
    got, none = kv_cache.gather_kv(kv, None, dest[None], 1, 40)
    assert none is None
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(rows))
    assert not np.asarray(kv[..., 40:]).any()
    assert kv_cache.block_view(kv, 4).shape == (3, 1, 5, 4, 128)

    class Model:                # all `build_steps` asks of a model here
        max_position_len = 64
        kv_geometry = staticmethod(lambda: (3, 1, 40, 1))
    *_, copy_block, restore_block = steps.build_steps(
        Model(), block_size=4, n_head=1, quantized=False, paged=True,
        width=8, counted=False, prefill_variants=1)
    one = jnp.zeros((1,), jnp.float32)
    kv, _ = copy_block(kv, one, jnp.int32(1), jnp.int32(2))
    cache.kv = kv
    slab, none = cache.read_block(2)
    assert none is None and slab.shape == cache.slab_shape
    np.testing.assert_array_equal(np.asarray(slab[:, 0, :, :40]),
                                  np.asarray(rows[:, :4, 0]))
    kv, _ = restore_block(kv, one, jnp.int32(4), slab, one)
    np.testing.assert_array_equal(np.asarray(kv[:, :, 16:20]),
                                  np.asarray(slab))


def test_a_quantized_latent_pool_is_refused():
    with pytest.raises(ValueError, match="no quantized form"):
        kv_cache.PagedKVCache(2, 4, 4, 1, 40, rows=1, quantization="int8")


# --- the engine's other programs ----------------------------------------

@pytest.mark.parametrize("feature,kw", [
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("prefix_caching", dict(prefix_caching=True)),
    ("speculative_decoding", dict(speculative_decoding=True,
                                  speculative_k=3)),
])
def test_engine_features_serve_the_logits_of_a_whole_prefill(lm, feature,
                                                             kw):
    """Chunked prefill (chunks of 16 over a prompt of 37), a
    prefix-cache hit (the prompt asked twice) and a verify window read
    the latent pool through the gather and the expanded form: every
    first token's logits are the reference's over the whole prompt, the
    tokens the plain engine's."""
    config, _, params = lm
    prompt = (np.random.default_rng(4).integers(0, VOCAB, 9).tolist() * 5
              )[:37]
    want = np.asarray(ref.forward(params, jnp.asarray(prompt), config)[0])

    def serve(**more):
        eng = engine_of(lm, max_slots=2, prefill_token_budget=16, **more)
        firsts = []
        chunk = eng._chunk_jit

        def on_chunk(*args):
            out = chunk(*args)
            firsts.append((int(args[4]) + int(args[5]),
                           np.asarray(out[3])))
            return out
        eng._chunk_jit = on_chunk
        tokens = [eng.generate(prompt, max_new_tokens=10) for _ in range(2)]
        assert eng.registry.snapshot()["generation_moe_dropped_total"] == 0
        return tokens, firsts, eng
    plain, _, _ = serve()
    tokens, firsts, eng = serve(**kw)
    assert tokens == plain
    if feature != "speculative_decoding":
        # the chunk that ends the prompt hands back its last position's
        # logits, whatever was cached or chunked before it
        ends = [last for end, last in firsts if end == len(prompt)]
        assert len(ends) == 2
        for last in ends:
            np.testing.assert_allclose(last, want[-1], atol=TOL, rtol=0)
    if feature == "prefix_caching":
        assert eng.registry.snapshot()["prefix_cache_hits_total"] >= 1


def test_a_slot_is_reused_after_a_finished_lane(lm):
    """One lane, three requests one after another: the second and
    third land in the slot and the blocks the first left, and are the
    reference's all the same."""
    config, model, params = lm
    eng = engine_of(lm, max_slots=1)
    got = capture(eng)
    rng = np.random.default_rng(6)
    for n in (21, 5, 13):
        prompt = rng.integers(0, VOCAB, n).tolist()
        tokens = eng.generate(prompt, max_new_tokens=7)
        seq = prompt + tokens[:-1]
        want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(got[(tuple(prompt), pos)],
                                       want[pos], atol=TOL, rtol=0)


def test_host_tier_spills_and_restores_latent_slabs(lm):
    """The prefix tree evicted to the host tier and asked again: the
    slabs are the latent pool's ([L, 1, block, stored columns]) and the
    tokens the first serving's."""
    eng = engine_of(lm, max_slots=2, block_size=8, prefix_caching=True,
                    chunked_prefill=True, kv_host_tier=1 << 20)
    eng.warmup()
    prompt = np.random.default_rng(7).integers(0, VOCAB, 24).tolist()
    out = eng.generate(prompt, max_new_tokens=6)
    freed = eng.prefix_cache.evict(32)
    assert freed >= 3 and eng.host_tier._c_spilled.value == freed
    stream = eng.submit(prompt, max_new_tokens=6)
    eng.run_until_idle()
    assert stream.tokens() == out
    assert eng.host_tier._c_restored.value >= 2


@pytest.mark.parametrize("kw,what", [
    (dict(tensor_parallel=2), "latent row all heads read"),
    (dict(kv_quantization="int8"), "reads an int8 pool"),
])
def test_engine_refuses_what_the_model_cannot_serve(lm, kw, what):
    with pytest.raises(NotImplementedError, match=what):
        engine_of(lm, **kw)
