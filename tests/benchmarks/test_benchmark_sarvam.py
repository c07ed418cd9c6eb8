"""The latent-attention cell (`sarvam_serve_decode`) at toy widths on
the suite's CPU device — a whole run through `run.py`'s `run()`,
untraced and traced, and the control that has to come out as not
correct — and each count of `harness/flops_latent.py` against the same
count by hand at the published widths.

Nothing here is a speed number.  The toy: hidden 64, 8 heads of 32 +
16 (values 32) over a latent of 128 — wider than the hidden size, as
the published 64 x 128 of values over 4096 is, so that attention is as
large a part of the residual stream as at the published widths and the
latent's precision shows in the logits — the published YaRN constants
over an original context of 32, 8 experts top-2 (4 held) and a shared
one, the first of 4 FFNs dense, bfloat16 as the cell runs."""

import argparse
import copy
import json

import jax
import pytest

from _bench_toy import bench_run
from benchmarks.harness import device, flops_latent
from benchmarks.harness import layer_metrics_latent as readers

CELL = "sarvam_serve_decode"

#: toy limits, set as the chip's are.  Sound runs over seeds 11 and 12
#: (420 to 440 served tokens each): the gap's 99th percentile over the
#: positions clear of a near-tie 4e-5 to 8e-4, its mean over every
#: position 1.2e-5 to 4.1e-5; the control (the latent rows in fp8)
#: 0.0040 and 1.4e-4 to 1.8e-4.
LIMITS = {"served_logit_gap_p99": {"limit": 0.002},
          "served_logit_gap_mean": {"limit": 0.00008},
          "routing_near_tie_share": {"limit": 0.35, "epsilon": 0.002}}


def toy(limits=None):
    manifest, entry, config, traffic, real = bench_run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(
        vocab_size=512, hidden_size=64, num_attention_heads=8,
        head_dim=144, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, q_head_dim=48, v_head_dim=32,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, experts_held=[2, 4], num_hidden_layers=4)
    config["rope_scaling"]["original_max_position_embeddings"] = 32
    config["engine"] = dict(max_slots=4, block_size=8, max_context=128,
                            prefill_buckets=[32, 64, 128])
    traffic.update(
        clients=4, deck=16, check_requests=24, trace_lead_s=0.1,
        trace_seconds=0.3,
        prompt_len=dict(dist="log_uniform", low=10, high=60),
        max_new_tokens=dict(dist="uniform", low=12, high=24))
    return manifest, entry, config, traffic, limits or real


def one_run(seed=7, seconds=1.0, trace=0, limits=LIMITS):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace)
    peaks = device.load_peaks()["TPU v5 lite"]
    return bench_run.run(args, jax.devices()[:1], peaks, files=toy(limits))


@pytest.mark.parametrize("seed", [7, 2**31 + 1017])
def test_rehearsal_of_a_whole_run(seed):
    line = json.loads(json.dumps(one_run(seed=seed)))
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {
        "served_logit_gap_p99", "served_logit_gap_mean",
        "routing_near_tie_share", "served_tokens_compared",
        "moe_dropped_assignments"}
    assert line["compared"]["moe_dropped_assignments"]["value"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert {"ttft_p50_ms", "itl_p95_ms"} <= set(line["detail"])
    kv = line["detail"]["kv"]
    # one bfloat16 row of 128 + 16 columns a token a layer, four
    # layers; 4 lanes x 128 positions + the null block, stored 256 wide
    assert kv["rows_per_token"] == 1 and kv["row_bytes"] == 4 * 144 * 2
    assert kv["pool_bytes_logical"] == 4 * (4 * 128 + 8) * 144 * 2
    assert kv["pool_bytes_physical"] == 4 * (4 * 128 + 8) * 256 * 2
    assert kv["rounds"] > 0
    assert line["detail"]["moe"]["dropped"] == 0


def test_rehearsal_of_a_traced_run():
    """`--trace 1`: the counters' metrics find something to read on the
    CPU too; the shares of a device trace find none and are left out."""
    line = json.loads(json.dumps(one_run(seed=9, trace=1)))
    manifest = toy()[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  CELL)}
    assert {"serve_mfu_latent", "decode_hbm_roofline_latent",
            "latent_decode_roofline", "latent_bytes_share",
            "decode_step_device_ms", "prefill_step_device_ms"} <= mine
    assert set(line["metrics"]) <= mine
    assert {"moe_tokens_per_expert_mean", "moe_load_max_over_mean",
            "decode_lanes_mean", "latent_bytes_share"} \
        <= set(line["metrics"])
    assert 0 < line["metrics"]["latent_bytes_share"]["value"] < 100
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    assert line["correct"] is True, line["compared"]


def test_the_fp8_latent_control_comes_out_as_not_correct():
    """The reference over latent rows cached one precision below the
    configuration's, judged like the program: over the toy's limits by
    both numbers."""
    manifest, entry, config, traffic, limits = toy(LIMITS)
    driver = bench_run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, jax.devices()[:1], 11)
    driver.setup()
    driver.window(1.0, None)
    driver.release()
    epsilon = limits["routing_near_tie_share"]["epsilon"]
    sound, n = driver.gaps(driver.sample(), epsilon=epsilon)
    control, _ = driver.gaps(driver.sample(), "fp8", epsilon=epsilon)
    for name, key in (("served_logit_gap_p99", "gap_p99"),
                      ("served_logit_gap_mean", "gap_mean")):
        limit = limits[name]["limit"]
        assert n > 100 and sound[key] <= limit < control[key], (
            name, sound, control)


# --- the configuration and the counts, by hand ----------------------------

@pytest.fixture(scope="module")
def published():
    return bench_run.load_json("configs", "sarvam_105b_ep8_serve.json")


def test_every_line_of_the_manifest_fits_its_200_characters():
    """The driver refuses `BENCHMARK.json` before any run over one text
    too long (PR 37's first hand-in: this configuration's `why` at 211
    characters; `test_benchmark_harness` holds the cells' alone)."""
    manifest = bench_run.load_cell(CELL)[0]
    texts = [(e["name"], key, e[key])
             for group, keys in (("configs", ("why", "source", "file")),
                                 ("workloads", ("why",)),
                                 ("per_layer", ("layer",)))
             for e in manifest[group] for key in keys]
    texts += [("command", i, w) for i, w in enumerate(manifest["command"])]
    assert any(name == "sarvam_105b_ep8_serve" for name, _, _ in texts)
    for name, key, text in texts:
        assert 1 <= len(text) <= 200, (name, key, len(text))
        assert text.isprintable() and text.isascii(), (name, key)
    for entry in manifest["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert len(entry["reduced"]) <= 16


def test_the_configuration_keeps_the_published_widths(published):
    c = published
    catalog = dict(
        first_k_dense_replace=1, head_dim=576, hidden_size=4096,
        intermediate_size=16384, kv_lora_rank=512,
        max_position_embeddings=131072, moe_intermediate_size=2048,
        num_attention_heads=64, num_experts=128, num_experts_per_tok=8,
        num_shared_experts=1, q_head_dim=192, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=10000,
        routed_scaling_factor=2.5, v_head_dim=128, default_theta=10000,
        model_type="sarvam_mla", hidden_act="silu", use_qk_norm=True,
        moe_router_enable_expert_bias=True, tie_word_embeddings=False,
        attn_implementation=None,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=4096,
                          type="deepseek_yarn"))
    assert {k: c[k] for k in catalog} == catalog
    assert c["reduced"] == ["num_hidden_layers", "experts_held",
                            "vocab_size"]
    # the floors: four layers behind the dense one, 16 >= 8 experts, an
    # eighth of the vocabulary
    assert c["num_hidden_layers"] == 5 and c["experts_held"] == [0, 16]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"] == 262144
    assert c["published"]["num_hidden_layers"] == 32
    assert c["deployment"]["chips_sharing_a_layer"] == 8
    assert set(c["assumed"]) >= {"use_qk_norm", "router", "rotary_pairing"}
    assert c["precision"]["control"].startswith("fp8_e4m3")
    assert c["engine"]["max_slots"] == 128
    assert max(c["engine"]["prefill_buckets"]) == c["engine"]["max_context"]


def test_parameter_counts_by_hand(published):
    c = published
    # W_q 4096 x 64*192, W_kva 4096 x 576, W_kvb 512 x 64*256,
    # W_o 64*128 x 4096
    assert flops_latent.attention_params(c) == 50_331_648 + 2_359_296 \
        + 8_388_608 + 33_554_432 == 94_633_984
    assert flops_latent.dense_ffn_params(c) == 3 * 4096 * 16384 \
        == 201_326_592
    assert flops_latent.expert_params(c) == 3 * 4096 * 2048 == 25_165_824
    assert flops_latent.router_params(c) == 4096 * 128
    # ISSUE 37: 295.96M in the dense layer, 522.97M in each of the four
    # sparse ones (shared + router + 16 held experts), 268.44M in the
    # embedding and the head: 2,656M parameters, 5.31 GB in bfloat16
    sparse = 94_633_984 + 25_165_824 + 524_288 + 16 * 25_165_824
    total = (94_633_984 + 201_326_592) + 4 * sparse + 2 * 32768 * 4096
    assert flops_latent.held_params(c) == total == 2_656_305_152
    # everything but the embedding table and the routed experts
    round_params = (32768 * 4096 + 5 * 94_633_984 + 201_326_592
                    + 4 * (524_288 + 25_165_824))
    assert flops_latent.decode_round_weight_bytes(c) == 2 * round_params
    assert flops_latent.expert_bytes(c) == 2 * 25_165_824


def test_the_engine_counts_the_same_parameters(published):
    """The module `from_config` builds at the published widths holds
    those parameters and the norm scales and biases beside them."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.serving.generation import DecoderLM
    model = DecoderLM.from_config(published, param_dtype=jnp.bfloat16)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.arange(8)[None])["params"]
    leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
    matrices = sum(v.size for p, v in leaves
                   if str(getattr(p[-1], "key", p[-1]))
                   in ("kernel", "embedding"))
    assert matrices == flops_latent.held_params(published)
    rest = sum(v.size for _, v in leaves) - matrices
    # per layer 4096 + 192 + 512 + 4096 norm scales, 128 expert biases
    # in the sparse ones, the final norm
    assert rest == 5 * (4096 + 192 + 512 + 4096) + 4 * 128 + 4096
    assert model.kv_geometry() == (5, 1, 576, 1)


def test_operations_by_hand(published):
    c = published
    assert flops_latent.absorbed_flops_per_row(c) == 2 * 64 * (576 + 512) \
        == 139_264
    assert flops_latent.expanded_flops_per_position(c) \
        == 2 * 64 * (192 + 128)
    matmuls = 2 * (5 * 94_633_984 + 201_326_592
                   + 4 * (524_288 + 25_165_824))
    head = 2 * 4096 * 32768
    assert flops_latent.token_flops(c, 2600, True, True) == \
        matmuls + 5 * 139_264 * 2600 + head
    assert flops_latent.token_flops(c, 100, False, False) == \
        matmuls + 5 * 40_960 * 100
    # a prompt of 3 (expanded) and one decoded token at context 9
    # (absorbed), 5 assignments on held experts
    want = (3 * matmuls + 5 * 40_960 * (1 + 2 + 3) + head
            + matmuls + 5 * 139_264 * 9 + head + 5 * 2 * 25_165_824)
    assert flops_latent.serve_flops(c, [3], [9], 5) == want


def test_latent_bytes_by_hand(published):
    c = published
    assert flops_latent.latent_row_bytes(c) == 1152
    assert flops_latent.latent_bytes(c, [50, 2600]) == 5 * 1152 * 2650
    assert flops_latent.latent_flops(c, [50, 2600]) == 5 * 139_264 * 2650
    # 121 operations a byte: half the v5e's ridge
    assert flops_latent.latent_flops(c, [1]) \
        / flops_latent.latent_bytes(c, [1]) == pytest.approx(120.9, abs=0.1)


def test_readers_by_hand_and_with_nothing_to_read(published):
    class NoTrace:
        window_s = 1.0

        def program(self, name):
            return 0, 0.0

        def ops(self, pattern):
            return 0, 0.0
    peaks = device.load_peaks()["TPU v5 lite"]
    records = [dict(prompt=[0] * 10, stamps=[0.5, 1.5, 2.5])]
    window = dict(records=records, t_open=1.0, t_close=2.0,
                  kv=dict(row_bytes=5760, rounds=2),
                  moe=dict(window=dict(loads_decode=3)))
    ctx = dict(trace=NoTrace(), traced=(1.0, 3.0), window=window,
               config=published, peaks=peaks, chips=1)
    # tokens 1 and 2 of the request come after the window's opening (the
    # second in the drain, which the counters cover too), at contexts
    # 11 and 12: 23 cached rows of 5,760 B beside two rounds of weights
    # and three expert loads
    assert readers.window_decode_contexts(ctx) == [11, 12]
    weights = 2 * flops_latent.decode_round_weight_bytes(published) \
        + 3 * flops_latent.expert_bytes(published)
    assert readers.latent_bytes_share(ctx) == pytest.approx(
        100 * 23 * 5760 / (23 * 5760 + weights))
    # a trace without the kernel's events, the program's or the counts
    for read in (readers.serve_mfu_latent,
                 readers.decode_hbm_roofline_latent,
                 readers.latent_decode_roofline):
        assert read(ctx) is None
    # a program without the pool's gauges (the parent) or the counters
    bare = dict(ctx, window=dict(records=records, t_open=1.0, t_close=2.0))
    for read in (readers.serve_mfu_latent,
                 readers.decode_hbm_roofline_latent,
                 readers.latent_decode_roofline,
                 readers.latent_bytes_share):
        assert read(bare) is None
