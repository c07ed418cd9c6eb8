"""`DecoderLM` (serving/generation/decoder.py) against the plain
reference the benchmark keeps (`benchmarks/reference/exaone_moe_ref.py`)
at a small size on the CPU: hidden 64, 4 query / 2 KV heads of 16,
window 8, 8 experts top-2 with a shared one, layers L L L G, the first
FFN dense.  Logits, not tokens.

Tolerances.  Everything here runs in float32 on the CPU, program and
reference alike, so what separates them is the order of float32 sums
(the program's grouped product against the reference's expert at a
time; a softmax over a gathered context against one over the whole
sequence): a few 1e-6 relative on logits of size 0.1.  5e-5 absolute
is ten times that and a hundredth of the gap between the best two
logits; a wrong window edge, a wrong KV head or a dropped assignment
moves a logit by 1e-2 or more."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import exaone_moe_ref as ref  # noqa: E402

from analytics_zoo_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
)
from analytics_zoo_tpu.serving.generation import (  # noqa: E402
    DecoderLM,
    ExpertLayer,
    GenerationEngine,
    lane_state,
)
from analytics_zoo_tpu.serving.generation.decoder import (  # noqa: E402
    ExpertCounters,
)

TOL = 5e-5
WINDOW = 8


def toy_config(**over):
    config = dict(
        vocab_size=97, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, routed_scaling_factor=2.5,
        norm_topk_prob=True, sliding_window=WINDOW,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 3,
        rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
        rms_norm_eps=1e-5, max_position_embeddings=4096,
        experts_held=[2, 4])
    config.update(over)
    return config


def seeded(model, seed=0, t=8):
    """N(0, 0.05) kernels (wider than the benchmark's 0.02: at hidden
    64 the router's scores would otherwise all sit at a half), norm
    scales near 1, a correction bias that matters."""
    ids = jnp.zeros((1, t), jnp.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(abstract)
    rng = np.random.default_rng(seed)
    out = []
    for path, leaf in leaves:
        kind = str(getattr(path[-1], "key", path[-1]))
        if kind == "scale":
            v = 1.0 + 0.1 * rng.normal(size=leaf.shape)
        elif kind == "bias":
            v = 0.02 * rng.normal(size=leaf.shape)
        else:
            v = 0.05 * rng.normal(size=leaf.shape)
        out.append(jnp.asarray(v, leaf.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), out)


@pytest.fixture(scope="module")
def lm():
    config = toy_config()
    model = DecoderLM.from_config(config)
    return config, model, seeded(model)


def test_geometry_and_leaf_names(lm):
    config, model, params = lm
    assert model.kv_geometry() == (4, 2, 16)
    assert model.moe_counts_shape == (3, 6) and model.moe_layers == (1, 2, 3)
    kinds = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert kinds == {"kernel", "embedding", "scale", "bias"}
    gate = params["block_1_moe"]["experts_gate"]["kernel"]
    assert gate.shape == (4, 64, 32)          # the held experts, stacked


def test_whole_prompt_forward_matches_the_reference(lm):
    """The prefill form over a prompt three windows long, every
    position's logits."""
    config, model, params = lm
    tokens = np.random.default_rng(1).integers(0, 97, 27)
    logits, new_k, _ = model.apply(
        {"params": params}, jnp.asarray(tokens)[None],
        jnp.arange(27)[None], token_mask=jnp.ones((1, 27)))
    want, margin = ref.forward(params, jnp.asarray(tokens), config)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL, rtol=0)
    assert new_k.shape == (4, 1, 27, 2, 16)
    assert np.isfinite(np.asarray(margin)).any()


def capture(eng):
    """Record the logits every prefill and decode dispatch hands back
    (the steps' fourth output, which the loop itself drops), keyed by
    (request, position)."""
    got = {}
    prefill, decode = eng._prefill_jit, eng._decode_jit

    def on_prefill(*args):
        _, row, tokens = lane_state.split_request(
            np.asarray(args[4]), eng._lanes.width)
        out = prefill(*args)
        length = int(row[lane_state.CTX])
        prompt = tuple(int(t) for t in tokens[0, :length])
        got[(prompt, length - 1)] = np.asarray(out[3])
        return out

    def on_decode(*args):
        ctx = np.asarray(lane_state.patched(args[3]["rows"], args[4])
                         )[:, lane_state.CTX]
        out = decode(*args)
        last = np.asarray(out[3])
        for seq in eng.scheduler.running():
            got[(tuple(seq.prompt), int(ctx[seq.slot]))] = last[seq.slot]
        return out

    on_decode._cache_size = decode._cache_size
    eng._prefill_jit, eng._decode_jit = on_prefill, on_decode
    return got


@pytest.mark.parametrize("attention", ["paged", "concat"])
def test_engine_prefill_then_decode_matches_the_reference(lm, attention):
    """Prefill, then decoding through the pool (`paged`: the XLA form
    of the paged op; `concat`: the parity oracle), four lanes with
    contexts under, at and past the window, block-aligned and not:
    every served position's logits against the reference's full
    forward over the prompt and the served tokens."""
    config, model, params = lm
    reg = MetricsRegistry()
    eng = GenerationEngine(model, params, max_slots=4, block_size=4,
                           max_context=64, prefill_buckets=[8, 16, 32, 64],
                           registry=reg, decode_attention=attention)
    eng.warmup()
    got = capture(eng)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 97, n).tolist() for n in (3, 8, 13, 24)]
    streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    for prompt, stream in zip(prompts, streams):
        tokens = stream.tokens()
        assert len(tokens) == 12
        seq = prompt + tokens[:-1]
        want, _ = ref.forward(params, jnp.asarray(seq), config)
        want = np.asarray(want)
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(
                got[(tuple(prompt), pos)], want[pos], atol=TOL, rtol=0,
                err_msg=f"prompt of {len(prompt)}, position {pos}")
            assert tokens[pos - len(prompt) + 1] == int(want[pos].argmax())
    assert eng.decode_compile_count == 1
    snap = reg.snapshot()
    assert snap["generation_moe_dropped_total"] == 0
    routed = (snap["generation_moe_assignments_total_held"]
              + snap["generation_moe_assignments_total_elsewhere"])
    # every real token of every dispatch, top-2, in three expert layers
    assert routed == 3 * 2 * (sum(map(len, prompts)) + 4 * 11)
    per_expert = sum(v for k, v in snap.items()
                     if k.startswith("generation_moe_expert_tokens_total"))
    assert per_expert == snap["generation_moe_assignments_total_held"] > 0
    assert "generation_moe_expert_tokens_total_layer1_expert2" in snap


def test_engine_through_the_paged_kernel(lm):
    """The same engine with the Pallas kernel pinned (interpreter):
    grouped heads and windows reach it through the model."""
    config, _, params = lm
    model = DecoderLM.from_config(config, paged_attention_impl="pallas")
    eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                           max_context=32, prefill_buckets=[16, 32],
                           registry=MetricsRegistry())
    got = capture(eng)
    prompt = np.random.default_rng(3).integers(0, 97, 11).tolist()
    tokens = eng.generate(prompt, max_new_tokens=6)
    seq = prompt + tokens[:-1]
    want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
    for pos in range(len(prompt) - 1, len(seq)):
        np.testing.assert_allclose(got[(tuple(prompt), pos)], want[pos],
                                   atol=TOL, rtol=0)


@pytest.mark.parametrize("feature,kw", [
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("prefix_caching", dict(prefix_caching=True)),
    ("speculative_decoding", dict(speculative_decoding=True,
                                  speculative_k=3)),
])
def test_engine_features_that_work_serve_the_same_tokens(lm, feature, kw):
    """Chunked prefill, the prefix cache and speculation go through the
    concat read and the verify form, which carry the window and the
    grouped heads: the tokens are the plain engine's."""
    config, model, params = lm
    prompt = (np.random.default_rng(4).integers(0, 97, 9).tolist() * 3)[:21]

    def serve(**more):
        eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                               max_context=64,
                               prefill_buckets=[8, 16, 32, 64],
                               prefill_token_budget=16,
                               registry=MetricsRegistry(), **more)
        out = [eng.generate(prompt, max_new_tokens=10) for _ in range(2)]
        assert eng.registry.snapshot()["generation_moe_dropped_total"] == 0
        return out
    assert serve(**kw) == serve()


@pytest.mark.parametrize("kw,what", [
    (dict(tensor_parallel=2), "tensor_parallel"),
    (dict(kv_quantization="int8"), "int8"),
])
def test_engine_refuses_what_the_model_cannot_serve(lm, kw, what):
    _, model, params = lm
    with pytest.raises(NotImplementedError, match=what):
        GenerationEngine(model, params, max_slots=2, block_size=4,
                         max_context=32, registry=MetricsRegistry(), **kw)


def test_flops_model_is_not_guessed_for_a_model_it_cannot_count(lm):
    _, model, params = lm
    eng = GenerationEngine(model, params, max_slots=2, block_size=4,
                           max_context=32, registry=MetricsRegistry())
    assert eng._flops is None
    assert eng.cache.kv.shape == (4, 2, eng.cache.num_blocks * 4, 2 * 16)


def test_expert_counters_read_the_counts_layout(lm):
    """`ExpertCounters` against a hand-made `[expert layers, held + 2]`
    array (tokens a held expert, then assignments to held experts, then
    all assignments): tokens by layer and global expert id, held,
    elsewhere, dropped, and the (layer, expert) pairs with a token —
    the weights that dispatch had to read — by program."""
    _, model, _ = lm                   # experts 2..5 held, layers 1 2 3
    reg = MetricsRegistry()
    counters = ExpertCounters.of(model, reg)
    counts = np.array([[3, 0, 1, 0, 4, 10],
                       [0, 0, 0, 0, 0, 10],
                       [2, 2, 2, 1, 8, 10]])      # 1 of 8 went missing
    counters.add(counts, "decode")
    counters.add(counts[::-1] * 0 + [1, 0, 0, 0, 1, 2], "prefill")
    snap = {k[len("generation_moe_"):]: v
            for k, v in reg.snapshot().items()
            if k.startswith("generation_moe_")}
    assert snap["expert_tokens_total_layer1_expert2"] == 3 + 1
    assert snap["expert_tokens_total_layer1_expert4"] == 1
    assert snap["expert_tokens_total_layer3_expert5"] == 1
    assert snap["expert_tokens_total_layer2_expert3"] == 0
    assert snap["assignments_total_held"] == 12 + 3
    assert snap["assignments_total_elsewhere"] == 18 + 3
    assert snap["dropped_total"] == 1
    assert snap["expert_loads_total_decode"] == 2 + 0 + 4
    assert snap["expert_loads_total_prefill"] == 3
    assert len(snap) == 3 * 4 + 5
    from analytics_zoo_tpu.serving.generation import CausalLM
    assert ExpertCounters.of(CausalLM(
        vocab=8, hidden_size=8, n_head=2, n_block=1, intermediate_size=8,
        max_position_len=8), reg) is None


# --- the expert layer ---------------------------------------------------

def expert_params(lm):
    config, _, params = lm
    return config, params["block_2_moe"]


def layer_with(config, held):
    return ExpertLayer(
        num_experts=config["num_experts"], experts_held=held,
        top_k=config["num_experts_per_tok"],
        width=config["moe_intermediate_size"],
        scale=config["routed_scaling_factor"])


def share_cases(shape):
    """(the uncut layer's configuration, its reference, the shares):
    `kexaone` — the toy's 8 experts top-2 in uneven shares;
    `sarvam` — `sarvam_105b_ep8_serve`'s own shape, 128 experts top-8
    over eight chips of 16, in front of latent attention."""
    if shape == "kexaone":
        return (toy_config(experts_held=[0, 8]), ref,
                ((0, 2), (2, 4), (6, 1), (7, 1)))
    from benchmarks.reference import sarvam_mla_ref
    from test_latent_attention import toy_config as latent_config
    return (latent_config(num_experts=128, num_experts_per_tok=8,
                          experts_held=[0, 128]), sarvam_mla_ref,
            tuple((16 * rank, 16) for rank in range(8)))


@pytest.mark.parametrize("shape", ["kexaone", "sarvam"])
def test_the_shares_add_up(shape):
    """Over all the `experts_held` slices of one layer, the routed
    parts plus the shared expert counted once equal the uncut
    reference's layer output — with the program's layer and with the
    reference's own share."""
    whole, ref, shares = share_cases(shape)
    model = DecoderLM.from_config(whole)
    p = seeded(model, seed=5)["block_2_moe"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 19, 64)),
                    jnp.float32)
    want, _ = ref.expert_layer(x[0], p, whole)

    def share(first, count):
        cut = {k: ({"kernel": v["kernel"][first:first + count]}
                   if k.startswith("experts_") else v)
               for k, v in p.items()}
        mine, counts = layer_with(whole, (first, count)).apply(
            {"params": cut}, x)
        theirs, _ = ref.expert_layer(x[0], cut, whole,
                                     experts_held=(first, count))
        np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(theirs),
                                   atol=TOL, rtol=0)
        only_shared, _ = ref.expert_layer(x[0], cut, whole,
                                          experts_held=(first, 0))
        return np.asarray(mine[0]) - np.asarray(only_shared), counts

    parts = [share(first, count) for first, count in shares]
    shared, _ = ref.expert_layer(x[0], p, whole, experts_held=(0, 0))
    total = sum(part for part, _ in parts) + np.asarray(shared)
    np.testing.assert_allclose(total, np.asarray(want), atol=TOL, rtol=0)
    # every assignment was computed by exactly one share
    assert sum(int(c[:-2].sum()) for _, c in parts) \
        == 19 * whole["num_experts_per_tok"]


@pytest.mark.parametrize("tokens", [1, 64])
def test_no_assignment_is_dropped_when_every_token_picks_one_expert(tokens):
    """A router that sends every token to expert 5 first: all of them
    are computed, at 1 token or 64 (a capacity would have cut them)."""
    config = toy_config()
    layer = layer_with(config, (4, 2))
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, tokens, 64)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree_util.tree_map(lambda a: a, params)
    params["bias"] = jnp.zeros(8).at[5].set(10.0)
    y, counts = layer.apply({"params": params}, x)
    counts = np.asarray(counts)
    assert counts[1] == tokens                  # expert 5, every token
    assert counts[:2].sum() == counts[2]        # computed == routed here
    assert counts[3] == 2 * tokens
    want, _ = ref.expert_layer(x[0], params, config, experts_held=(4, 2))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=TOL, rtol=0)


def test_padding_and_dead_lanes_are_routed_nowhere():
    config = toy_config()
    layer = layer_with(config, (0, 8))
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 6, 64)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0]])
    _, counts = layer.apply({"params": params}, x, mask)
    assert int(counts[:8].sum()) == int(counts[8]) == int(counts[9]) == 8
