"""The expert-layer cell (`kexaone_serve_decode`) at toy widths on the
suite's CPU device — a whole run through `run.py`'s `run()`, untraced
and traced, and the control that has to come out as not correct — and
each count of `harness/flops_moe.py` against the same count by hand.

Nothing here is a speed number.  The toy: hidden 64, 4 query / 2 KV
heads of 16, window 8, 8 experts top-2 (4 held) and a shared one,
layers L L L G, the first FFN dense, bfloat16 as the cell runs."""

import argparse
import copy
import json

import jax
import pytest

from _bench_toy import bench_run
from benchmarks.harness import device, flops_moe
from benchmarks.harness import layer_metrics_moe as readers

CELL = "kexaone_serve_decode"

#: toy limits, set as the chip's are.  Sound runs over seeds 7, 9, 11
#: and 2**31 + 1017 (330 to 420 served tokens each): over the positions
#: whose routing margin is at least the epsilon the gap's 99th
#: percentile reads 0 to 0.0003 (its maximum 0.0006 to 0.0017, and
#: 0.0013 to 0.0114 with the near-ties left in: a flipped expert), with
#: 19 to 23% of the positions under the epsilon — at hidden 64 the
#: router's scores all sit near a half; the fp8 control reads 0.028 to
#: 0.033 there.  The gap's mean over every position reads 4e-6 to
#: 3.5e-5 sound and 0.0014 to 0.0022 under the control.
LIMITS = {"served_logit_gap_p99": {"limit": 0.003},
          "served_logit_gap_mean": {"limit": 0.0003},
          "routing_near_tie_share": {"limit": 0.35, "epsilon": 0.002}}


def toy(limits=None):
    manifest, entry, config, traffic, real = bench_run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(
        vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        experts_held=[2, 4], sliding_window=8, num_hidden_layers=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 3)
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
    # tokens per second alone is held end to end (a closed loop at
    # capacity); the two latencies ride in `detail`
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert {"ttft_p50_ms", "itl_p95_ms"} <= set(line["detail"])
    moe = line["detail"]["moe"]
    assert moe["dropped"] == 0 and moe["assignments_held"] > 0
    # 4 of 8 experts held: about half the assignments stay here
    share = moe["assignments_held"] / (moe["assignments_held"]
                                       + moe["assignments_elsewhere"])
    assert 0.3 < share < 0.7
    assert moe["tokens_per_expert_load"] >= 1


def test_rehearsal_of_a_traced_run():
    """`--trace 1`: the counters' metrics find something to read on the
    CPU too; the shares of a device trace find none and are left out."""
    line = json.loads(json.dumps(one_run(seed=9, trace=1)))
    manifest = toy()[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  CELL)}
    assert set(line["metrics"]) <= mine
    assert {"moe_tokens_per_expert_mean", "moe_load_max_over_mean",
            "decode_lanes_mean"} <= set(line["metrics"])
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    assert line["correct"] is True, line["compared"]


def test_the_fp8_control_comes_out_as_not_correct():
    """The reference in the precision below the configuration's, judged
    like the program: over the toy's limits."""
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


# --- the counts, by hand --------------------------------------------------

@pytest.fixture(scope="module")
def published():
    return bench_run.load_json("configs", "kexaone_236b_ep8_serve.json")


def test_parameter_counts_by_hand(published):
    c = published
    # q 6144x8192, k and v 6144x1024 each, o 8192x6144
    assert flops_moe.attention_params(c) == 2 * 6144 * 8192 \
        + 2 * 6144 * 1024 == 113_246_208
    assert flops_moe.dense_ffn_params(c) == 3 * 6144 * 18432 == 339_738_624
    assert flops_moe.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    assert flops_moe.router_params(c) == 6144 * 128
    # ISSUE 30's table: everything but the embedding table and the
    # routed experts, in bfloat16
    round_params = (19200 * 6144 + 8 * 113_246_208 + 339_738_624
                    + 7 * (6144 * 128 + 37_748_736))
    assert flops_moe.decode_round_weight_bytes(c) == 2 * round_params
    assert flops_moe.expert_bytes(c) == 2 * 37_748_736


def test_token_and_serving_operations_by_hand(published):
    c = published
    proj = 8 * 2 * 113_246_208
    ffn = 2 * 339_738_624 + 7 * 2 * (6144 * 128 + 37_748_736)
    # context 500: the six window layers see 128, the two full ones 500
    products = 4 * 8192 * (6 * 128 + 2 * 500)
    head = 2 * 6144 * 19200
    assert flops_moe.token_flops(c, 500, True) == proj + ffn + products + head
    # under the window every layer sees the whole context
    assert flops_moe.token_flops(c, 100, False) == \
        proj + ffn + 4 * 8192 * 8 * 100
    # a prompt of 3 and one decoded token at context 9, 5 assignments
    # on held experts
    want = (3 * (proj + ffn) + 4 * 8192 * 8 * (1 + 2 + 3) + head
            + (proj + ffn) + 4 * 8192 * 8 * 9 + head
            + 5 * 2 * 37_748_736)
    assert flops_moe.serve_flops(c, [3], [9], 5) == want


def test_kv_bytes_by_hand(published):
    c = published
    row = 2 * 8 * 128 * 2               # K and V, 8 heads of 128, bf16
    # cached contexts 50 and 700: a window layer reads 127 of the 700
    assert flops_moe.kv_bytes(c, [50, 700]) == \
        row * (6 * (50 + 127) + 2 * (50 + 700))
    assert flops_moe.cached_in_sight(c, [700]) == 6 * 127 + 2 * 700
    assert flops_moe.kv_flops(c, [700]) == 4 * 8192 * (6 * 127 + 2 * 700)


def test_counter_readers_by_hand():
    ctx = {"window": {"moe": {"window": dict(
        tokens=[[4, 0, 8], [3, 3, 3]], loads_decode=4, loads_prefill=1)}}}
    assert readers.moe_tokens_per_expert_mean(ctx) == 21 / 5
    # layer 1: 8 over a mean of 4; layer 2: 3 over 3
    assert readers.moe_load_max_over_mean(ctx) == (2.0 + 1.0) / 2
    assert readers.moe_tokens_per_expert_mean({"window": {}}) is None
    assert readers.moe_load_max_over_mean({"window": {}}) is None
