"""`HybridLM` by `lfm2_moe`'s keys (serving/generation/hybrid.py: the
gated short convolution `C`, attention with q/k norms and rotary
positions, dense `D` and gated expert `E` FFNs), its conv-only state in
the recurrent pool (kv_cache.RecurrentStatePool), `ExpertLayer` without
a shared expert and the grouped-query paged kernel at head_dim 64,
against the plain reference the benchmark keeps
(`benchmarks/reference/lfm2_moe_ref.py`) at a small size on the CPU:
hidden 64, six layers `conv, full_attention, conv, conv,
full_attention, conv` with the first dense (96 wide) and the rest
routed, 8 query / 2 KV heads of 8, 8 experts of 32, top-3, a
convolution of 3 taps.  Logits, not tokens.

Tolerances.  Everything here runs in float32 on the CPU, program and
reference alike, so what separates them is the order of float32 sums
(the grouped product against an expert at a time, a softmax over a
gathered context or the pool against one over the whole sequence) and
the published 1e-6 in the router's normalisation, which the reference
keeps and the program leaves out — some 1e-6 relative on logits of
size 0.3.  1e-4 absolute is some thirty times that; a tail one row
off, a bucket's padding shifted into the tail, a rotation in the wrong
pairing or a q/k norm left out moves a logit by 1e-2 or more.  Tails
are compared at 1e-5 relative to the tail's largest element, for the
same reason.
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

from benchmarks.reference import lfm2_moe_ref as ref  # noqa: E402
from test_decoder_lm import capture  # noqa: E402
from test_hybrid_lm import close_states, new_engine, seeded  # noqa: E402

from analytics_zoo_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
)
from analytics_zoo_tpu.ops.attention import (  # noqa: E402
    paged_decode_attention,
)
from analytics_zoo_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_decode_pallas,
)
from analytics_zoo_tpu.serving.generation import (  # noqa: E402
    ExpertLayer,
    HybridLM,
    RecurrentStatePool,
)

#: engine against reference, float32 throughout on the CPU: the same
#: sums in another order (blocked attention, the experts' sorted rows,
#: the convolution as shifted adds) differ by some 1e-6 of logits of
#: about 1 (9e-7 measured); a tail row one position off moves them by
#: tenths
TOL = 1e-4
VOCAB = 97
LAYERS = ["conv", "full_attention", "conv", "conv", "full_attention",
          "conv"]


def toy_config(**over):
    config = dict(
        model_type="lfm2_moe", vocab_size=VOCAB, hidden_size=64,
        layer_types=LAYERS, num_hidden_layers=6, num_dense_layers=1,
        num_attention_heads=8, num_key_value_heads=2, conv_L_cache=3,
        conv_bias=False, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=3, use_expert_bias=True,
        norm_topk_prob=True, routed_scaling_factor=1.0, norm_eps=1e-5,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        max_position_embeddings=4096)
    config.update(over)
    return config


@pytest.fixture(scope="module")
def lm():
    config = toy_config()
    model = HybridLM.from_config(config)
    return config, model, seeded(model)


def test_from_config_reads_the_lfm2_keys(lm):
    """Two letters a layer — its operator, then its FFN, dense below
    `num_dense_layers` — and the leaves each letter holds: no scan
    state, no latent space, no shared expert."""
    _, model, params = lm
    assert model.pattern == "CD*ECECE*ECE"
    assert (model.head_dim, model.conv_kernel, model.rope_theta) \
        == (8, 3, 1e6) and model.qk_norm and model.moe_gated
    # the paged pool holds the two attention layers' rows alone; the
    # recurrent pool the four convolution layers' two-row tails
    assert model.kv_geometry() == (2, 2, 8)
    assert model.state_geometry() == (4, None, ((2, 64), jnp.float32))
    assert model.moe_layers == (3, 5, 7, 9, 11)
    assert params["block_0_conv"]["in_proj"]["kernel"].shape == (64, 192)
    assert params["block_0_conv"]["out_proj"]["kernel"].shape == (64, 64)
    assert params["block_0_conv"]["conv_kernel"].shape == (3, 64)
    assert params["block_1_ffn"]["gate"]["kernel"].shape == (64, 96)
    assert params["block_2_q_norm"]["scale"].shape == (8,)
    moe = params["block_3_moe"]
    assert set(moe) == {"router", "bias", "experts_gate", "experts_up",
                        "experts_down"}
    assert moe["experts_gate"]["kernel"].shape == (8, 64, 32)
    assert moe["experts_down"]["kernel"].shape == (8, 32, 64)
    with pytest.raises(ValueError, match="one kind of state layer"):
        HybridLM(vocab=8, hidden_size=8, pattern="MC", n_head=1,
                 n_kv_head=1, head_dim=8)
    with pytest.raises(NotImplementedError, match="convolution bias"):
        HybridLM.from_config(toy_config(conv_bias=True))


def close_tails(got, want):
    close_states([np.asarray(g, np.float32) for g in got], want)


@pytest.mark.parametrize("length", [27, 16, 5, 2])
def test_whole_prompt_forward_matches_the_reference(lm, length):
    """The prefill form over a bucket of 32 holding `length` real
    tokens: every real position's logits, and the tail the padding must
    not have advanced (a prompt of 2 fills the tail exactly)."""
    config, model, params = lm
    tokens = np.random.default_rng(1).integers(0, VOCAB, 32)
    mask = (jnp.arange(32) < length)[None]
    logits, new_k, _, state = model.apply(
        {"params": params}, jnp.asarray(tokens)[None], jnp.arange(32)[None],
        token_mask=mask)
    want, margin, tails = ref.forward(params, jnp.asarray(tokens), config,
                                      length=length)
    np.testing.assert_allclose(np.asarray(logits[0, :length]),
                               np.asarray(want[:length]), atol=TOL, rtol=0)
    assert new_k.shape == (2, 1, 32, 2, 8)
    assert np.isfinite(np.asarray(margin)).all()
    assert state["ssm"] == ()
    assert [c.shape for c in state["conv"]] == [(2, 1, 64)] * 4
    close_tails([c[:, 0] for c in state["conv"]], tails)


@pytest.mark.parametrize("attention", ["paged", "concat", "kernel"])
def test_engine_prefill_then_decode_matches_the_reference(lm, attention):
    """Prefill, then decoding through both pools: five requests over
    three lanes (two slots reused, their tails replaced by the
    admission), a prompt that just fills the tail, prompts in the 8,
    16 and 32 buckets with their padding; every served position's logits
    against the reference's full forward over the prompt and the served
    tokens, and the tail a finished lane leaves in its slot against
    the reference's last rows of v.  `kernel`: the paged reads through
    the grouped-query kernel in the interpreter (4 queries a KV head)."""
    config, _, params = lm
    model = HybridLM.from_config(
        config, paged_attention_impl="pallas" if attention == "kernel"
        else None)
    reg = MetricsRegistry()
    eng = new_engine(model, params, registry=reg,
                     decode_attention="concat" if attention == "concat"
                     else "paged")
    eng.warmup()
    got = capture(eng)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist()
               for n in (2, 8, 13, 24, 5)]
    streams = [eng.submit(p, max_new_tokens=7 + i)
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
        want, _, tails = ref.forward(params, jnp.asarray(seq), config)
        want = np.asarray(want)
        for pos in range(len(prompt) - 1, len(seq)):
            np.testing.assert_allclose(
                got[(tuple(prompt), pos)], want[pos], atol=TOL, rtol=0,
                err_msg=f"prompt of {len(prompt)}, position {pos}")
        if stream is streams[-1] or stream is streams[-2]:
            # the last holders of their slots: their tail is still there
            state = eng.recurrent_state(slots[id(stream)])
            assert state["ssm"] == []
            close_tails(state["conv"], tails)
    assert eng.decode_compile_count == 1
    snap = reg.snapshot()
    assert snap["generation_moe_dropped_total"] == 0
    assert snap["generation_state_resets_total"] == 5
    # 3 lanes x 4 layers x 2 rows of 64 float32: the tails and nothing
    # else
    assert snap["generation_state_bytes"] == 3 * 4 * 2 * 64 * 4


def test_the_conv_only_pool():
    """Only tails: `"ssm"` is an empty tuple in the pool the programs
    are handed, the shapes keep the slot axis second, and the bytes are
    the tails'."""
    pool = RecurrentStatePool((7, None, ((2, 2048), jnp.bfloat16)), 128)
    assert pool.shapes == {"conv": ((2, 128, 2048), jnp.bfloat16)}
    zeros = pool.zeros()
    assert zeros["ssm"] == () and len(zeros["conv"]) == 7
    assert {(c.shape, c.dtype) for c in zeros["conv"]} \
        == {((2, 128, 2048), jnp.dtype(jnp.bfloat16))}
    # at the published widths: 57 KB a lane, 7.3 MB in all
    assert pool.nbytes == 7 * 2 * 2048 * 2 * 128 == 7_340_032


def test_dead_lanes_leave_their_tail_untouched(lm):
    """A finished lane's tail stays as its last step left it while the
    other lanes decode on, until an admission replaces it."""
    _, model, params = lm
    eng = new_engine(model, params)
    short = eng.submit([3, 4, 5, 6, 7], max_new_tokens=3)
    long = eng.submit([9, 8, 7, 6, 5, 4, 3], max_new_tokens=16)
    while short.seq.status != "finished":
        eng.step()
    slot = next(i for i in range(3)
                if eng.scheduler.slots[i] is not long.seq
                and np.abs(eng.recurrent_state(i)["conv"][0]).max() > 0)
    before = eng.recurrent_state(slot)["conv"]
    other = long.seq.slot
    moving = eng.recurrent_state(other)["conv"]
    eng.run_until_idle()
    for b, a in zip(before, eng.recurrent_state(slot)["conv"]):
        np.testing.assert_array_equal(b, a)
    assert any(np.abs(m - a).max() > 0 for m, a in zip(
        moving, eng.recurrent_state(other)["conv"]))


def test_expert_layer_without_a_shared_expert(lm):
    """`num_shared_experts` 0: no `shared` leaves and no branch — the
    layer's output is its routed sum alone, the reference's, and what
    the same layer with a shared expert gives less that expert."""
    config, _, params = lm
    p = params["block_3_moe"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 11, 64)),
                    jnp.float32)
    bare = ExpertLayer(num_experts=8, experts_held=(0, 8), top_k=3,
                       width=32, num_shared_experts=0)
    assert "shared" not in jax.eval_shape(
        bare.init, jax.random.PRNGKey(0), x)["params"]
    y, counts = bare.apply({"params": p}, x)
    want, _ = ref.expert_layer(x[0], p, config)
    # float32 outputs of some hundredths, summed in another order
    # (4e-8 measured)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert int(counts[:8].sum()) == int(counts[8]) == int(counts[9])
    with_shared = ExpertLayer(num_experts=8, experts_held=(0, 8), top_k=3,
                              width=32)
    shared = {k: {"kernel": 0.05 * jnp.ones_like(p["experts_gate"]
                                                 ["kernel"][0])
                  if k != "down" else 0.05 * jnp.ones((32, 64))}
              for k in ("gate", "up", "down")}
    y2, _ = with_shared.apply({"params": dict(p, shared=shared)}, x)
    only = ref.swiglu(x[0], shared["gate"]["kernel"],
                      shared["up"]["kernel"], shared["down"]["kernel"],
                      "f32")
    np.testing.assert_allclose(np.asarray(y2[0]) - np.asarray(only),
                               np.asarray(y[0]), atol=1e-5, rtol=0)


@pytest.mark.parametrize("gather", [1, 4])
def test_grouped_query_kernel_at_head_dim_64(gather):
    """`_kernel_gqa` in the interpreter at the cell's head geometry —
    32 query heads over 8 KV heads of 64, so a KV head's columns are
    half a lane tile of the staged block — against the XLA read over
    the same pool: lanes at a block's edge, inside one, over several,
    and a dead lane at 0."""
    rng = np.random.default_rng(5)
    lanes, h, g, d, bs, mb = 4, 32, 8, 64, 8, 6
    nb = lanes * mb + 1
    q = jnp.asarray(rng.normal(size=(lanes, h, d)), jnp.float32)
    nk, nv = (jnp.asarray(rng.normal(size=(lanes, g, d)), jnp.float32)
              for _ in range(2))
    pool = jnp.asarray(rng.normal(size=(2, 2, nb, bs, g * d)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(lanes * mb).reshape(lanes, mb),
                         jnp.int32)
    ctx = jnp.asarray([8, 3, 41, 0], jnp.int32)
    got = paged_decode_pallas(
        q.reshape(lanes, h * d), nk.reshape(lanes, g * d),
        nv.reshape(lanes, g * d), pool, tables, ctx, layer=1, head_dim=d,
        block_gather=gather, interpret=True, q_per_kv=h // g)
    want = paged_decode_attention(q, nk, nv, pool, tables, ctx, layer=1,
                                  impl="xla")
    # the same bf16 pool read in float32 by both: the kernel's online
    # softmax against one softmax over outputs of about 1 (5e-7
    # measured); a query head on the wrong KV head reads tenths off
    np.testing.assert_allclose(np.asarray(got).reshape(lanes, h, d),
                               np.asarray(want), atol=2e-5, rtol=0)


@pytest.mark.parametrize("kw,feature", [
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(chunked_prefill=True), "chunked_prefill"),
    (dict(speculative_decoding=True), "speculative_decoding"),
    (dict(kv_host_tier=1 << 20), "kv_host_tier"),
])
def test_engine_refuses_what_a_tail_cannot_serve(lm, kw, feature):
    """A tail inside a sequence is no more kept than a scan state is:
    the same features refused, for the same reason."""
    _, model, params = lm
    with pytest.raises(NotImplementedError, match=feature) as err:
        new_engine(model, params, **kw)
    assert "snapshot of the recurrent state" in str(err.value)
