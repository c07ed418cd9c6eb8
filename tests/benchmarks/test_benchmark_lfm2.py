"""The LFM2 cell (`lfm2_serve_decode`) at toy widths on the suite's CPU
device — a whole run through `run.py`'s `run()`, untraced and traced,
and the control that has to come out as not correct — and each count of
`harness/flops_conv.py` against the same count by hand at the published
widths.

Nothing here is a speed number.  The toy: hidden 64, six layers `conv,
full_attention, conv, conv, full_attention, conv`, the first dense (96
wide), 8 query / 2 KV heads of 8, 8 experts of 32 top-3 held whole, a
convolution of 3 taps, bfloat16 as the cell runs."""

import argparse
import copy
import json

import jax
import pytest

from _bench_toy import bench_run
from benchmarks.harness import device, flops_conv
from benchmarks.harness import layer_metrics_conv as readers

CELL = "lfm2_serve_decode"

#: toy limits, set as the chip's are, from readings on the CPU at seeds
#: 7, 9, 11, 12, 13 and 2**31 + 1017 (which requests a window finishes,
#: and so the sample, varies from run to run): sound runs read a p99 of
#: 0-0.0011, a mean of 0-1.3e-4, a near-tie share of 0.11-0.20 at this
#: epsilon (the toy's 4th and 5th of 8 scores lie some 0.002 apart) and
#: tails of 0.0030-0.0037; the fp8 control 0.0069-0.012, 7.1e-4 and
#: 0.062 on the seed the control's test serves (11)
LIMITS = {"served_logit_gap_p99": {"limit": 0.003},
          "served_logit_gap_mean": {"limit": 0.0003},
          "routing_near_tie_share": {"limit": 0.4, "epsilon": 0.0005},
          "conv_tail_gap_worst": {"limit": 0.02}}


def toy(limits=None):
    manifest, entry, config, traffic, real = bench_run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(
        vocab_size=512, hidden_size=64, num_hidden_layers=6,
        layer_types=["conv", "full_attention", "conv", "conv",
                     "full_attention", "conv"],
        num_dense_layers=1, num_attention_heads=8, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=3)
    config["engine"] = dict(max_slots=4, block_size=8, max_context=128,
                            prefill_buckets=[32, 64, 128])
    traffic.update(
        clients=4, deck=16, check_requests=3, trace_lead_s=0.1,
        trace_seconds=0.3,
        prompt_len=dict(dist="log_uniform", low=10, high=60),
        max_new_tokens=dict(dist="uniform", low=24, high=48))
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
        "routing_near_tie_share", "conv_tail_gap_worst",
        "served_tokens_compared", "moe_dropped_assignments"}
    assert line["compared"]["moe_dropped_assignments"]["value"] == 0
    assert line["compared"]["conv_tail_gap_worst"]["value"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    state = line["detail"]["state"]
    # 4 lanes of 4 convolution layers: two bf16 rows of 64 columns each
    # and no scan state
    assert state["bytes"] == 4 * 4 * 2 * 64 * 2
    assert state["resets"] > 0 and state["rounds"] > 0
    assert line["detail"]["moe"]["dropped"] == 0


def test_rehearsal_of_a_traced_run():
    """`--trace 1`: the counters' metrics find something to read on the
    CPU too; the shares of a device trace find none and are left out."""
    line = json.loads(json.dumps(one_run(seed=9, trace=1)))
    manifest = toy()[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  CELL)}
    assert {"serve_mfu_conv", "decode_hbm_roofline_conv",
            "paged_decode_gqa_roofline_conv", "prefill_step_device_ms",
            "moe_tokens_per_expert_mean", "decode_lanes_mean"} <= mine
    assert set(line["metrics"]) <= mine
    assert {"moe_tokens_per_expert_mean", "moe_load_max_over_mean",
            "decode_lanes_mean", "serve_mfu_conv"} <= set(line["metrics"])
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    # the tracer was started and stopped with the engine held
    assert set(line["detail"]["trace_edges_held_s"]) == {"start", "stop"}
    assert line["correct"] is True, line["compared"]


@pytest.fixture(scope="module")
def served():
    """One window's driver, released: what the control judges."""
    manifest, entry, config, traffic, limits = toy(LIMITS)
    driver = bench_run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, jax.devices()[:1], 11)
    driver.setup()
    driver.window(1.0, None)
    driver.release()
    return driver, limits


def test_the_fp8_control_comes_out_as_not_correct(served):
    """The reference with e4m3 matmul operands, judged like the
    program: the program within every limit, the control over a limit
    of the logits and over the tails' (the window's sample varies from
    run to run, and with it the control's mean: 1.6e-4 to 7.1e-4
    against the toy's 3e-4, so it need not fail both)."""
    driver, limits = served
    epsilon = limits["routing_near_tie_share"]["epsilon"]
    sound, n = driver.gaps(driver.sample(), epsilon=epsilon)
    control, _ = driver.gaps(driver.sample(), "fp8", epsilon=epsilon)
    over = []
    for name, key in (("served_logit_gap_p99", "gap_p99"),
                      ("served_logit_gap_mean", "gap_mean")):
        limit = limits[name]["limit"]
        assert n > 100 and sound[key] <= limit, (name, sound)
        over.append(control[key] > limit)
    assert any(over), (sound, control)
    limit = limits["conv_tail_gap_worst"]["limit"]
    tail, tails = driver.state_gap()
    tail_control, _ = driver.state_gap("fp8")
    # the one convolution layer in front of the first expert layer
    assert tails == len(driver.probes) * 1
    # every convolution layer's tail, the one above first
    gaps = driver.tail_gaps()
    assert [len(row) for row in gaps] == [4] * len(driver.probes)
    assert max(row[0] for row in gaps) == tail
    assert 0 < tail <= limit < tail_control, (tail, tail_control)


# --- the counts, by hand, at the published widths -------------------------

@pytest.fixture(scope="module")
def published():
    return bench_run.load_json("configs", "lfm2_24b_a2b_serve.json")


def test_the_configuration_keeps_the_published_widths(published):
    c = published
    catalog = dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        conv_L_cache=3, intermediate_size=11776, moe_intermediate_size=1536,
        num_experts=64, num_experts_per_tok=4, vocab_size=65536,
        routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
        conv_bias=False, model_type="lfm2_moe")
    assert {k: c[k] for k in catalog} == catalog
    assert c["rope_parameters"] == {"rope_theta": 1000000,
                                    "rope_type": "default"}
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "num_dense_layers"]
    assert c["num_hidden_layers"] == len(c["layer_types"]) == 9
    assert c["layer_types"].count("conv") == 7
    assert c["layer_types"][1::4] == ["full_attention"] * 2
    assert c["num_dense_layers"] == 1
    for key in ("deployment", "assumed", "precision", "engine",
                "features_off", "published"):
        assert c[key]


def test_the_engine_settings_admit_the_traffic(published):
    """What the engine's constructor asks of its settings — the largest
    prefill bucket covers `max_context`, a preempted lane's re-prefill —
    and a lane for every client, room for the longest request."""
    e = published["engine"]
    traffic = bench_run.load_json("traffic", "closed_decode_conv_128.json")
    assert max(e["prefill_buckets"]) == e["max_context"]
    assert e["max_context"] % e["block_size"] == 0
    assert e["max_context"] >= (traffic["prompt_len"]["high"]
                                + traffic["max_new_tokens"]["high"])
    assert e["max_slots"] == traffic["clients"]


def test_the_model_reads_the_configuration(published):
    """`HybridLM.from_config` at the published widths, from the file."""
    from analytics_zoo_tpu.serving.generation import HybridLM
    model = HybridLM.from_config(published)
    assert model.pattern == "CD*ECECECE*ECECECE"
    assert model.kv_geometry() == (2, 8, 64)
    assert model.state_geometry()[:2] == (7, None)
    assert model.state_geometry()[2][0] == (2, 2048)


def test_parameter_counts_by_hand(published):
    c = published
    # in_proj 2048 x 6144, out_proj 2048 x 2048, 3 taps, the norm
    assert flops_conv.conv_params(c) == 2048 * 6144 + 2048 * 2048 \
        + 3 * 2048 + 2048 == 16_785_408
    # q and o 2048 x 2048, k and v 2048 x 512, q/k norms of 64, the norm
    assert flops_conv.attention_params(c) == 2 * 2048 * 2048 \
        + 2 * 2048 * 512 + 2 * 64 + 2048 == 10_487_936
    assert flops_conv.dense_ffn_params(c) == 3 * 2048 * 11776 + 2048
    assert flops_conv.expert_params(c) == 3 * 2048 * 1536 == 9_437_184
    assert flops_conv.expert_layer_params(c) == 2048 * 64 + 64 + 2048
    total = (7 * 16_785_408 + 2 * 10_487_936 + 3 * 2048 * 11776 + 2048
             + 8 * (2048 * 64 + 64 + 2048 + 64 * 9_437_184)
             + 2 * 65536 * 2048 + 2048)
    assert flops_conv.total_params(c) == total
    # the cut by hand: 5,312M parameters, 10.62 GB of bfloat16 weights
    assert round(total / 1e6) == 5312 and round(2 * total / 1e9, 2) == 10.62
    # a decode round reads everything but the embedding table and the
    # routed experts
    assert flops_conv.decode_round_weight_bytes(c) == 2 * (
        total - 65536 * 2048 - 8 * 64 * 9_437_184)


def test_operations_by_hand(published):
    c = published
    conv = 2 * (2048 * 6144 + 2048 * 2048) + 2 * 3 * 2048
    attention = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    dense = 2 * 3 * 2048 * 11776
    router = 2 * 2048 * 64
    head = 2 * 2048 * 65536
    body = 7 * conv + 2 * attention + dense + 8 * router
    assert flops_conv.token_flops(c, 500, True) == \
        body + 2 * 4 * 2048 * 500 + head
    # a prompt of 3 and one decoded token at context 9, 7 assignments
    # on held experts
    want = (3 * body + 2 * 4 * 2048 * (1 + 2 + 3) + head
            + body + 2 * 4 * 2048 * 9 + head + 7 * 2 * 9_437_184)
    assert flops_conv.serve_flops(c, [3], [9], 7) == want
    # by hand: about 1.3 GFLOP a token with its 4 experts, at a
    # context of about 1,000
    per_token = flops_conv.token_flops(c, 1000, True) \
        + 8 * 4 * 2 * 9_437_184
    assert 1.2e9 < per_token < 1.4e9


def test_kv_and_tail_bytes_by_hand(published):
    c = published
    # two attention layers: K and V rows of 8 heads of 64 in bf16
    assert flops_conv.kv_bytes(c, [50, 700]) == 2 * 2 * 8 * 64 * 2 * 750
    assert flops_conv.kv_flops(c, [10]) == 2 * 4 * 2048 * 10
    lane = 7 * 2 * 2048 * 2
    assert flops_conv.tail_bytes(lane, 100) == 2 * 100 * lane
    # a round by hand: about 11 GB with every held expert read and
    # 128 lanes at a context of about 1,150
    whole = (flops_conv.decode_round_weight_bytes(c)
             + 8 * 64 * flops_conv.expert_bytes(c)
             + flops_conv.kv_bytes(c, [1150] * 128))
    assert 10.5e9 < whole < 11.2e9


def test_counter_readers_by_hand(published):
    class Trace:
        window_s = 2.0

        def program(self, name):
            return (4, 0.08) if name == "jit_decode" else (0, 0.0)

        def ops(self, pattern):
            return (8, 0.004)
    records = [dict(prompt=[0] * 100, stamps=[0.5, 1.0, 1.5])]
    moe = dict(tokens=[[1]], held=10, loads_decode=256, loads_prefill=0)
    lane = 7 * 2 * 2048 * 2
    ctx = dict(config=published, trace=Trace(), traced=(0.0, 2.0),
               peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
               chips=1,
               window=dict(moe={"traced": moe}, records=records,
                           state=dict(bytes=128 * lane)))
    need = (4 * flops_conv.decode_round_weight_bytes(published)
            + 256 * flops_conv.expert_bytes(published)
            + flops_conv.kv_bytes(published, [101, 102])
            + 2 * 2 * lane)
    assert readers.decode_hbm_roofline_conv(ctx) == pytest.approx(
        100 * need / 819e9 / 0.08)
    flops = flops_conv.serve_flops(published, [100], [101, 102], 10)
    assert readers.serve_mfu_conv(ctx) == pytest.approx(
        100 * flops / (2.0 * 197e12))
    kv = flops_conv.kv_bytes(published, [101, 102])
    assert readers.paged_decode_gqa_roofline_conv(ctx) == pytest.approx(
        100 * kv / 819e9 / 0.004)
    # the parent's program has no pool gauge for this model: nothing to
    # read, no error
    bare = dict(ctx, window=dict(moe={"traced": moe}, records=records))
    assert readers.decode_hbm_roofline_conv(bare) is None
