"""`HybridLM` (serving/generation/hybrid.py), the state-space operators
(`ops/ssm.py`) and the recurrent-state pool against the plain reference
the benchmark keeps (`benchmarks/reference/nemotron_h_ref.py`: the
state-space layer as the plain recurrence over time) at a small size on
the CPU: hidden 64, pattern `M E M * E M`, 8 state-space heads of 8 in
2 groups with a state of 16, chunks of 8, 4 query / 2 KV heads of 16, 8
experts top-3 (4 held) in a latent space of 24 with a shared expert.
Logits, not tokens.

Tolerances.  Everything here runs in float32 on the CPU, program and
reference alike, so what separates them is the order of float32 sums:
the chunked scan against the recurrence (a chunk's masked product sums
what the recurrence sums a step at a time), the grouped product against
an expert at a time, a softmax over a gathered context against one over
the whole sequence — some 1e-6 relative on logits of size 0.3.  1e-4
absolute is some thirty times that; a state not reset, a bucket's
padding advancing the state, a convolution tail one row off or a
dropped assignment moves a logit by 1e-2 or more.  States are compared
at 1e-5 relative to the state's largest element, for the same reason.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import nemotron_h_ref as ref  # noqa: E402
from test_decoder_lm import capture  # noqa: E402

from analytics_zoo_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
)
from analytics_zoo_tpu.ops import ssm  # noqa: E402
from analytics_zoo_tpu.serving.generation import (  # noqa: E402
    ExpertLayer,
    GenerationEngine,
    HybridLM,
)

TOL = 1e-4
VOCAB = 97


def toy_config(**over):
    config = dict(
        vocab_size=VOCAB, hidden_size=64, hybrid_override_pattern="MEM*EM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        conv_kernel=4, chunk_size=8, moe_intermediate_size=32,
        moe_latent_size=24, moe_shared_expert_intermediate_size=48,
        n_routed_experts=8, num_experts_per_tok=3, experts_held=[2, 4],
        routed_scaling_factor=5.0, norm_topk_prob=True, norm_eps=1e-5,
        max_position_embeddings=4096)
    config.update(over)
    return config


def seeded(model, seed=0, t=8):
    """N(0, 0.05) kernels (wider than the benchmark's 0.02, as in
    test_decoder_lm), norm scales near 1, a correction bias that
    matters, and state-space leaves that make the state matter: decays
    from a step to a few hundred, a convolution of size one."""
    ids = jnp.zeros((1, t), jnp.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
    rng = np.random.default_rng(seed)
    out = []
    for path, leaf in leaves:
        kind = str(getattr(path[-1], "key", path[-1]))
        if kind in ("scale", "norm_scale", "D"):
            v = 1.0 + 0.1 * rng.normal(size=leaf.shape)
        elif kind in ("bias", "conv_bias"):
            v = 0.02 * rng.normal(size=leaf.shape)
        elif kind == "A_log":
            v = np.log(rng.uniform(1.0, 16.0, size=leaf.shape))
        elif kind == "dt_bias":
            v = np.log(np.expm1(np.exp(rng.uniform(
                np.log(1e-3), np.log(0.3), size=leaf.shape))))
        elif kind == "conv_kernel":
            v = rng.uniform(-0.5, 0.5, size=leaf.shape)
        else:
            v = 0.05 * rng.normal(size=leaf.shape)
        out.append(jnp.asarray(v, leaf.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), out)


@pytest.fixture(scope="module")
def lm():
    config = toy_config()
    model = HybridLM.from_config(config)
    return config, model, seeded(model)


def new_engine(model, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("registry", MetricsRegistry())
    return GenerationEngine(model, params, block_size=4, max_context=64,
                            prefill_buckets=[8, 16, 32, 64], **kw)


def close_states(got, want):
    for g, w in zip(got, want):
        w = np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-5 * np.abs(w).max())


def test_geometry_and_leaf_names(lm):
    _, model, params = lm
    # the paged pool holds the one attention layer's rows alone; the
    # recurrent pool the three state-space layers' state a lane
    assert model.kv_geometry() == (1, 2, 16)
    layers, (state, dtype), (tail, _) = model.state_geometry()
    assert (layers, state, dtype) == (3, (8, 8, 16), jnp.float32)
    assert tail == (3, 8 * 8 + 2 * 2 * 16)
    assert model.moe_layers == (1, 4) and model.moe_counts_shape == (2, 6)
    moe = params["block_1_moe"]
    assert moe["experts_up"]["kernel"].shape == (4, 24, 32)
    assert moe["experts_down"]["kernel"].shape == (4, 32, 24)
    assert "experts_gate" not in moe
    assert moe["latent_in"]["kernel"].shape == (64, 24)
    assert moe["shared"]["up"]["kernel"].shape == (64, 48)
    assert params["block_0_mixer"]["in_proj"]["kernel"].shape \
        == (64, 64 + 128 + 8)
    assert HybridLM.from_config(
        toy_config(hybrid_override_pattern="*E")).state_geometry() is None


def ssm_recurrence(x, dt, A, B, C, D, h0=None):
    """`ssm_scan`'s result by `ssm_step` a position at a time: the
    plain recurrence."""
    b, t, H, P = x.shape
    h = (jnp.zeros((b, H, P, B.shape[-1]), jnp.float32) if h0 is None
         else h0)
    ys = []
    for i in range(t):
        y, h = ssm.ssm_step(h, x[:, i], dt[:, i], A, B[:, i], C[:, i], D)
        ys.append(y)
    return jnp.stack(ys, axis=1), h


@pytest.mark.parametrize("t,length", [(16, 16), (24, 24), (19, 19),
                                      (32, 21), (16, 3), (64, 40)])
def test_chunked_scan_is_the_recurrence(t, length):
    """`ssm_scan` in chunks of 8 against `ssm_step` a position at a
    time: lengths that are and are not multiples of the chunk, and a
    bucket's padding after `length` (dt = 0 there) that must not
    advance the state."""
    k = jax.random.split(jax.random.PRNGKey(t * 100 + length), 6)
    b, H, P, G, S = 2, 8, 4, 2, 16
    x = jax.random.normal(k[0], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, H)) - 2.0)
    dt = dt * (jnp.arange(t) < length)[None, :, None]
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(k[3], (b, t, G, S))
    C = jax.random.normal(k[4], (b, t, G, S))
    D = jnp.ones(H)
    h0 = jax.random.normal(k[5], (b, H, P, S))
    for start in (None, h0):
        y, h = ssm.ssm_scan(x, dt, A, B, C, D, chunk=8, h0=start)
        y_want, h_want = ssm_recurrence(x, dt, A, B, C, D, start)
        np.testing.assert_allclose(y[:, :length], y_want[:, :length],
                                   atol=2e-5, rtol=0)
        np.testing.assert_allclose(h, h_want, atol=2e-5, rtol=0)
        # ... and the state is the one after `length` real tokens
        _, h_cut = ssm_recurrence(
            x[:, :length], dt[:, :length], A, B[:, :length],
            C[:, :length], D, start)
        np.testing.assert_allclose(h, h_cut, atol=2e-5, rtol=0)


def test_convolution_tail_feeds_the_next_position():
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(k[0], (2, 12, 6))
    w = jax.random.normal(k[1], (4, 6))
    bias = jnp.full((6,), 0.1)
    whole = ssm.causal_conv(x, w, bias)
    for n in (1, 2, 7, 11):
        tail = ssm.conv_tail(x, jnp.asarray([n, n]), 4)
        assert tail.shape == (3, 2, 6)
        one = ssm.causal_conv(x[:, n:n + 1], w, bias, tail)
        np.testing.assert_allclose(one, whole[:, n:n + 1], atol=1e-6)
    # shorter than the kernel: zeros stand where no position was
    assert not np.asarray(ssm.conv_tail(x, jnp.asarray([1, 2]), 4)[0]).any()
    with pytest.raises(ValueError, match="impl"):
        ssm.ssm_step(jnp.zeros((1, 2, 2, 2)), None, None, None, None, None,
                     None, impl="mosaic")


@pytest.mark.parametrize("length", [27, 16, 5])
def test_whole_prompt_forward_matches_the_reference(lm, length):
    """The prefill form over a bucket of 32 holding `length` real
    tokens: every real position's logits, and the state the padding
    must not have advanced."""
    config, model, params = lm
    tokens = np.random.default_rng(1).integers(0, VOCAB, 32)
    mask = (jnp.arange(32) < length)[None]
    logits, new_k, _, state = model.apply(
        {"params": params}, jnp.asarray(tokens)[None], jnp.arange(32)[None],
        token_mask=mask)
    want, margin, states = ref.forward(params, jnp.asarray(tokens), config,
                                       length=length)
    np.testing.assert_allclose(np.asarray(logits[0, :length]),
                               np.asarray(want[:length]), atol=TOL, rtol=0)
    assert new_k.shape == (1, 1, 32, 2, 16)
    assert np.isfinite(np.asarray(margin)).any()
    close_states([np.asarray(h[0]) for h in state["ssm"]], states)
    assert state["conv"][0].shape == (3, 1, 8 * 8 + 2 * 2 * 16)


@pytest.mark.parametrize("attention", ["paged", "concat"])
def test_engine_prefill_then_decode_matches_the_reference(lm, attention):
    """Prefill, then decoding through BOTH pools: five requests over
    three lanes (so two slots are reused, their state reset by the
    admission), prompts shorter than the convolution, at a chunk's edge
    and across chunks; every served position's logits against the
    reference's full forward over the prompt and the served tokens, and
    the state a finished lane leaves in its slot against the
    reference's recurrence."""
    config, model, params = lm
    reg = MetricsRegistry()
    eng = new_engine(model, params, registry=reg,
                     decode_attention=attention)
    eng.warmup()
    got = capture(eng)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist()
               for n in (2, 8, 13, 24, 5)]
    streams = [eng.submit(p, max_new_tokens=9 + i)
               for i, p in enumerate(prompts)]
    slots = {}
    while eng.scheduler.has_work():
        eng.step()
        for s in streams:
            if s.seq.slot is not None:
                slots[id(s)] = s.seq.slot
    eng.run_until_idle()
    assert len(set(slots.values())) == 3
    for prompt, stream in zip(prompts, streams):
        tokens = stream.tokens()
        seq = prompt + tokens[:-1]
        want, _, states = ref.forward(params, jnp.asarray(seq), config)
        want = np.asarray(want)
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(
                got[(tuple(prompt), pos)], want[pos], atol=TOL, rtol=0,
                err_msg=f"prompt of {len(prompt)}, position {pos}")
        if stream is streams[-1] or stream is streams[-2]:
            # the last holders of their slots: their state is still there
            close_states(eng.recurrent_state(slots[id(stream)])["ssm"],
                         states)
    assert eng.decode_compile_count == 1
    snap = reg.snapshot()
    assert snap["generation_moe_dropped_total"] == 0
    assert snap["generation_state_resets_total"] == 5
    assert snap["generation_state_rebuilds_total"] == 0
    assert snap["generation_state_slots_in_use"] == 0
    assert snap["generation_state_bytes"] == 3 * 3 * (
        8 * 8 * 16 * 4 + 3 * 128 * 4)


def test_a_preempted_lane_is_rebuilt_by_its_resume(lm):
    """A pool too small for three growing lanes: the newest is
    preempted, its blocks freed and its slot's state abandoned; its
    resume prefills prompt + generated and the state with them, and
    every request still reads the reference's logits."""
    config, model, params = lm
    reg = MetricsRegistry()
    eng = new_engine(model, params, registry=reg, num_blocks=14)
    got = capture(eng)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (9, 10, 11)]
    streams = [eng.submit(p, max_new_tokens=14) for p in prompts]
    eng.run_until_idle()
    snap = reg.snapshot()
    assert snap["generation_preemptions"] >= 1
    assert snap["generation_state_rebuilds_total"] >= 1
    assert snap["generation_state_resets_total"] \
        == 3 + snap["generation_state_rebuilds_total"]
    for prompt, stream in zip(prompts, streams):
        tokens = stream.tokens()
        assert len(tokens) == 14
        seq = prompt + tokens[:-1]
        want = np.asarray(ref.forward(params, jnp.asarray(seq), config)[0])
        for pos in range(len(prompt) - 1, len(seq)):
            # a resume's prefill is keyed by the longer prompt it ran
            key = next((k for k in got if k[1] == pos
                        and tuple(seq[:len(k[0])]) == k[0]), None)
            assert key is not None, pos
            np.testing.assert_allclose(got[key], want[pos], atol=TOL,
                                       rtol=0)


def test_dead_lanes_leave_their_state_untouched(lm):
    """A finished lane's state stays as its last step left it while the
    other lanes decode on (the step masks the update by the lane's
    active flag), until an admission replaces it."""
    _, model, params = lm
    eng = new_engine(model, params)
    short = eng.submit([3, 4, 5, 6, 7], max_new_tokens=3)
    long = eng.submit([9, 8, 7, 6, 5, 4, 3], max_new_tokens=20)
    while short.seq.status != "finished":
        eng.step()
    slot = next(i for i in range(3)
                if eng.scheduler.slots[i] is not long.seq
                and np.abs(eng.recurrent_state(i)["ssm"][0]).max() > 0)
    before = eng.recurrent_state(slot)
    for _ in range(5):
        eng.step()
    assert long.seq.status != "finished"
    after = eng.recurrent_state(slot)
    for kind in ("ssm", "conv"):
        for b, a in zip(before[kind], after[kind]):
            np.testing.assert_array_equal(b, a)
    moving = eng.recurrent_state(long.seq.slot)["ssm"][0]
    eng.run_until_idle()
    assert np.abs(eng.recurrent_state(slot)["ssm"][0] - before["ssm"][0]
                  ).max() == 0
    assert np.abs(moving).max() > 0


def test_the_four_shares_add_up_to_the_uncut_layer(lm):
    """model-configs guide, section 4: the routed parts the four shares
    of an expert layer give, with what every chip computes alike (the
    shared expert) counted once, add up to the uncut reference layer —
    the latent projections being linear, a share's exit from the latent
    space is its part of the whole's."""
    config, model, params = lm
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 11, 64)),
                    jnp.float32)
    p = params["block_1_moe"]
    uncut, _ = ref.expert_layer(x[0], _whole(p, config), config,
                                experts_held=(0, 8))
    shared_only = ref.squared_relu_mlp(
        x[0], p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"],
        "f32")
    total = np.zeros((11, 64), np.float32)
    for first in (0, 2, 4, 6):
        layer = ExpertLayer(num_experts=8, experts_held=(first, 2), top_k=3,
                            width=32, scale=5.0, gated=False, latent=24,
                            shared_width=48)
        share = _share(p, config, first, 2)
        y, counts = layer.apply({"params": share}, x)
        total += np.asarray(y[0]) - np.asarray(shared_only)
        assert int(counts[:2].sum()) == int(counts[2])   # none dropped
    np.testing.assert_allclose(total + np.asarray(shared_only),
                               np.asarray(uncut), atol=TOL, rtol=0)


def _whole(p, config):
    """Layer parameters holding all 8 experts: the fixture's 4 held
    (ids 2-5) and seeded others around them."""
    rng = np.random.default_rng(6)
    up, down = (np.asarray(p[k]["kernel"]) for k in ("experts_up",
                                                     "experts_down"))
    more_up = 0.05 * rng.normal(size=(8,) + up.shape[1:])
    more_down = 0.05 * rng.normal(size=(8,) + down.shape[1:])
    more_up[2:6], more_down[2:6] = up, down
    return dict(p, experts_up={"kernel": jnp.asarray(more_up, jnp.float32)},
                experts_down={"kernel": jnp.asarray(more_down,
                                                    jnp.float32)})


def _share(p, config, first, count):
    whole = _whole(p, config)
    return dict(whole, **{
        k: {"kernel": whole[k]["kernel"][first:first + count]}
        for k in ("experts_up", "experts_down")})


@pytest.mark.parametrize("kw,feature", [
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(chunked_prefill=True), "chunked_prefill"),
    (dict(speculative_decoding=True), "speculative_decoding"),
    (dict(prefix_caching=True, kv_host_tier=1 << 20), "prefix_caching"),
    (dict(kv_host_tier=1 << 20), "kv_host_tier"),
    (dict(tensor_parallel=2), "tensor_parallel"),
    (dict(kv_quantization="int8"), "int8"),
])
def test_engine_refuses_what_a_state_model_cannot_serve(lm, kw, feature):
    """At construction, by the feature's name and with the reason —
    not by a shape error from inside a program."""
    _, model, params = lm
    with pytest.raises(NotImplementedError, match=feature) as err:
        new_engine(model, params, **kw)
    assert "HybridLM cannot be served with" in str(err.value)
    if feature not in ("tensor_parallel", "int8"):
        assert "snapshot of the recurrent state" in str(err.value)


def test_a_pattern_without_state_layers_gets_no_pool(lm):
    """... and refuses nothing on the state's account: it is called as
    `DecoderLM` is."""
    config = toy_config(hybrid_override_pattern="*E*")
    model = HybridLM.from_config(config)
    params = seeded(model)
    eng = new_engine(model, params, chunked_prefill=True)
    assert eng.state_pool is None and eng.recurrent_state(0) is None
    assert "recurrent" not in eng._lanes.state
    assert "generation_state_bytes" not in eng.registry.snapshot()
    prompt = list(range(3, 20))
    tokens = eng.generate(prompt, max_new_tokens=5)
    want = np.asarray(ref.forward(
        params, jnp.asarray(prompt + tokens[:-1]), config)[0])
    assert tokens == [int(want[i].argmax())
                      for i in range(len(prompt) - 1, len(prompt) + 4)]
