"""The looped form of `DecoderLM` (serving/generation/decoder.py,
`total_ut_steps` > 1) against the plain reference the benchmark keeps
(`benchmarks/reference/ouro_ref.py`) at a small size on the CPU: Ouro's
keys at hidden 64, 4 heads of 16, 2 layers run 3 times a token (6 pool
slots a token).  Logits, not tokens.

Tolerances.  In float32 program and reference differ by the order of
float32 sums (a scan's products against a loop's, a softmax over a
gathered context against one over the whole sequence): a few 1e-6 on
logits of size 1.  5e-5 is ten times that; a slot read at the wrong
step moves a logit by 1e-2 or more.  In bfloat16 the program rounds
every activation to 8 bits of mantissa where the reference keeps 24:
the widest logit gap measured at this size is 0.012 to 0.023 (weights
of seeds 0-4, logits up to 1.4), so 0.04 holds it 1.7 times over; the
reference whose cached rows are rounded to fp8 (the benchmark's
control) sits 0.050 to 0.151 off its float32 self on the same seeds,
past that tolerance."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import ouro_ref as ref  # noqa: E402

from analytics_zoo_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
)
from analytics_zoo_tpu.ops.attention import (  # noqa: E402
    paged_decode_attention,
)
from analytics_zoo_tpu.serving.generation import (  # noqa: E402
    DecoderLM,
    GenerationEngine,
)
from analytics_zoo_tpu.serving.generation.kv_cache import (  # noqa: E402
    block_view,
)

from test_decoder_lm import capture, seeded  # noqa: E402

TOL = {"f32": 5e-5, "bf16": 0.04}
LAYERS, STEPS, VOCAB = 2, 3, 97


def toy_config(**over):
    """Ouro's published keys at toy widths."""
    config = dict(
        model_type="ouro", vocab_size=VOCAB, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        intermediate_size=96, num_hidden_layers=LAYERS,
        layer_types=["full_attention"] * LAYERS, rms_norm_eps=1e-6,
        rope_theta=1e6, rope_scaling=None, max_position_embeddings=4096,
        sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=STEPS, early_exit_threshold=1)
    config.update(over)
    return config


def build(dtype="f32", seed=0, **over):
    config = toy_config(**over)
    kw = {} if dtype == "f32" else dict(compute_dtype=jnp.bfloat16,
                                        param_dtype=jnp.bfloat16)
    model = DecoderLM.from_config(config, **kw)
    return config, model, seeded(model, seed)


@pytest.fixture(scope="module")
def lm():
    return build()


def test_geometry_stacked_leaves_and_one_layer_body(lm):
    config, model, params = lm
    assert model.kv_geometry() == (LAYERS * STEPS, 4, 16)
    assert params["loop_q"]["kernel"].shape == (LAYERS, 64, 64)
    assert params["loop_down"]["kernel"].shape == (LAYERS, 96, 64)
    assert params["loop_ffn_post_norm"]["scale"].shape == (LAYERS, 64)
    assert not any(k.startswith("block_") or "q_norm" in k for k in params)
    # the program holds ONE layer body, looped: a while over steps
    # around a while over layers, three products of the width the
    # attention projections have (q, k, v) and no more
    text = jax.jit(lambda p, ids: model.apply(
        {"params": p}, ids, jnp.arange(8)[None])).lower(
            params, jnp.zeros((1, 8), jnp.int32)).as_text()
    assert text.count("stablehlo.while") == 2
    assert text.count("tensor<1x8x64xf32>, tensor<64x64xf32>") == 4


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_whole_prompt_forward_matches_the_reference(dtype):
    """Every position's logits, and every slot's keys and values as the
    prefill hands them to the pool: slot s * L + l is step s's layer l."""
    config, model, params = build(dtype)
    tokens = np.random.default_rng(1).integers(0, VOCAB, 21)
    logits, new_k, new_v = model.apply(
        {"params": params}, jnp.asarray(tokens)[None],
        jnp.arange(21)[None], token_mask=jnp.ones((1, 21)))
    want, cached = ref.forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL[dtype], rtol=0)
    if dtype == "bf16":
        control = ref.forward(params, jnp.asarray(tokens), config,
                              mode="fp8")[0]
        assert float(jnp.abs(control - want).max()) > TOL[dtype]
    assert new_k.shape == new_v.shape == (LAYERS * STEPS, 1, 21, 4, 16)
    assert new_k.dtype == model.compute_dtype
    for slot, (k, v) in enumerate(cached):
        for got, ref_rows in ((new_k, k), (new_v, v)):
            np.testing.assert_allclose(
                np.asarray(got[slot, 0], np.float32), np.asarray(ref_rows),
                atol=TOL[dtype] * 2, rtol=TOL[dtype], err_msg=f"slot {slot}")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_prefill_then_decode_matches_the_reference(lm, impl):
    """Prefill, then decoding through the paged pool of 6 slots a token
    (the XLA form of the paged op; the Pallas kernel in the
    interpreter, the slot carried in by scalar prefetch): every served
    position's logits against the reference's full forward."""
    config, _, params = lm
    model = DecoderLM.from_config(config, paged_attention_impl=impl)
    reg = MetricsRegistry()
    eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                           max_context=32, prefill_buckets=[16, 32],
                           registry=reg)
    assert eng.cache.kv.shape[:2] == (LAYERS * STEPS, 2)
    got = capture(eng)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 12)]
    streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for prompt, stream in zip(prompts, streams):
        tokens = stream.tokens()
        seq = prompt + tokens[:-1]
        want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(
                got[(tuple(prompt), pos)], want[pos], atol=TOL["f32"],
                rtol=0, err_msg=f"prompt of {len(prompt)}, position {pos}")
    assert eng.decode_compile_count == 1
    snap = reg.snapshot()
    assert snap["generation_loop_steps"] == STEPS
    assert snap["generation_kv_layer_slots"] == LAYERS * STEPS
    assert snap["generation_kv_row_bytes"] == LAYERS * STEPS * 2 * 64 * 4


def test_a_slot_is_read_by_its_own_application_alone(lm):
    """Decode one token a lane over a prefilled pool, then again with
    pool slot s's cached rows scrambled: the keys and values of every
    application up to (s's step, s's layer) come out as they were —
    none of them read slot s — and the next application's differ: it
    starts from what application s read."""
    config, model, params = lm
    bs, n = 4, 10
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, n + 1))
    _, new_k, new_v = model.apply({"params": params}, tokens[None, :n],
                                  jnp.arange(n)[None])
    slots = LAYERS * STEPS
    pool = jnp.zeros((slots, 2, 4 * bs, 64), jnp.float32)
    pool = pool.at[:, 0, bs:bs + n].set(new_k[:, 0].reshape(slots, n, 64))
    pool = pool.at[:, 1, bs:bs + n].set(new_v[:, 0].reshape(slots, n, 64))
    table = jnp.asarray([[1, 2, 3]], jnp.int32)

    @jax.jit
    def decode(kv):
        return model.apply({"params": params}, tokens[None, n:],
                           jnp.asarray([[n]]), kv_pool=block_view(kv, bs),
                           block_tables=table, ctx_len=jnp.asarray([n]))
    logits, k0, _ = decode(pool)
    noise = jnp.asarray(np.random.default_rng(4).normal(size=(2, n, 64)),
                        jnp.float32)
    for s in range(slots):
        logits_s, k_s, _ = decode(pool.at[s, :, bs:bs + n].set(noise))
        np.testing.assert_array_equal(np.asarray(k_s[:s + 1]),
                                      np.asarray(k0[:s + 1]))
        if s + 1 < slots:
            assert not np.allclose(k_s[s + 1], k0[s + 1], atol=1e-3), s
        assert not np.allclose(logits_s, logits, atol=1e-4), s


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_traced_layer_reads_what_the_same_static_layer_reads(impl):
    rng = np.random.default_rng(5)
    layers, bs, s, h, d = 3, 4, 2, 4, 16
    pool = jnp.asarray(rng.normal(size=(layers, 2, 8, bs, h * d)),
                       jnp.float32)
    q, nk, nv = (jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
                 for _ in range(3))
    table = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    ctx = jnp.asarray([11, 6], jnp.int32)

    def attention(layer):
        return paged_decode_attention(q, nk, nv, pool, table, ctx,
                                      layer=layer, impl=impl)
    traced = jax.jit(attention)
    for layer in range(layers):
        np.testing.assert_array_equal(np.asarray(traced(jnp.int32(layer))),
                                      np.asarray(attention(layer)))


def test_one_step_with_the_switches_off_is_todays_module():
    """`total_ut_steps` 1 and the three switches at their defaults: the
    leaves and the logits of the module as it was; and one step with
    Ouro's switches on, run layer by layer, is the reference's loop of
    one step over the same weights stacked."""
    from test_decoder_lm import toy_config as exaone_config
    config = exaone_config(mlp_layer_types=["dense"] * 4)
    today = DecoderLM.from_config(config)
    same = DecoderLM.from_config(config, total_ut_steps=1,
                                 rotary_full=False, qk_norm=True,
                                 sandwich_norm=False)
    params = seeded(today)
    ids = jnp.asarray(np.random.default_rng(6).integers(0, 97, 9))[None]
    pos = jnp.arange(9)[None]

    def logits(module, params):
        return np.asarray(jax.jit(module.apply)({"params": params}, ids,
                                                pos)[0])
    assert jax.tree_util.tree_structure(seeded(same)) \
        == jax.tree_util.tree_structure(params)
    np.testing.assert_array_equal(logits(today, params),
                                  logits(same, params))

    once = toy_config(total_ut_steps=1)
    model = DecoderLM.from_config(once)
    params = seeded(model)
    assert "block_0_attn_post_norm" in params and not any(
        "q_norm" in k for k in params)
    stacked = {f"loop_{n}": {"kernel": jnp.stack([
        params[f"block_{i}_{n}"]["kernel"] for i in range(LAYERS)])}
        for n in ("q", "k", "v", "o")}
    stacked.update({f"loop_{n}": {"kernel": jnp.stack([
        params[f"block_{i}_mlp"][n]["kernel"] for i in range(LAYERS)])}
        for n in ("gate", "up", "down")})
    stacked.update({f"loop_{n}": {"scale": jnp.stack([
        params[f"block_{i}_{n}"]["scale"] for i in range(LAYERS)])}
        for n in ref.NORMS})
    stacked.update((k, params[k]) for k in
                   ("token_embed", "final_norm", "lm_head"))
    want = ref.forward(stacked, ids[0], once)[0]
    np.testing.assert_allclose(logits(model, params)[0], np.asarray(want),
                               atol=TOL["f32"], rtol=0)


def test_engine_features_serve_the_same_tokens_over_the_slots(lm):
    """Chunked prefill with the prefix cache (the concat read over a
    gathered context of 6 slots a token) and speculation (the verify
    form over the pool): the tokens are the plain engine's."""
    config, model, params = lm
    prompt = (np.random.default_rng(4).integers(0, VOCAB, 9).tolist()
              * 3)[:21]

    def serve(**more):
        eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                               max_context=64,
                               prefill_buckets=[8, 16, 32, 64],
                               prefill_token_budget=16,
                               registry=MetricsRegistry(), **more)
        return [eng.generate(prompt, max_new_tokens=10) for _ in range(2)]
    plain = serve()
    assert serve(chunked_prefill=True, prefix_caching=True) == plain
    assert serve(speculative_decoding=True, speculative_k=3) == plain


@pytest.mark.parametrize("over,what", [
    (dict(layer_types=["full_attention", "sliding_attention"],
          sliding_window=8), "one attention kind"),
    (dict(early_exit_threshold=0.9), "early_exit_threshold"),
])
def test_what_a_loop_cannot_be_is_refused(over, what):
    with pytest.raises(ValueError, match=what):
        DecoderLM.from_config(toy_config(**over))
