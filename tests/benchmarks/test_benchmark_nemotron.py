"""The hybrid cell (`nemotron3_serve_decode`) at toy widths on the
suite's CPU device — a whole run through `run.py`'s `run()`, untraced
and traced, and the two controls that have to come out as not correct —
and each count of `harness/flops_hybrid.py` against the same count by
hand at the published widths.

Nothing here is a speed number.  The toy: hidden 64, pattern M E M * E
M, 8 state-space heads of 8 in 2 groups with a state of 16, chunks of
8, 4 query / 2 KV heads of 16, 8 experts top-3 (4 held) in a latent
space of 24 with a shared expert of 48, bfloat16 as the cell runs."""

import argparse
import copy
import json

import jax
import pytest

from _bench_toy import bench_run
from benchmarks.harness import device, flops_hybrid
from benchmarks.harness import layer_metrics_hybrid as readers

CELL = "nemotron3_serve_decode"

#: toy limits, set as the chip's are (the readings are in the tests
#: that hold them: sound runs under, each control over by its number)
LIMITS = {"served_logit_gap_p99": {"limit": 0.02},
          "served_logit_gap_mean": {"limit": 0.003},
          "routing_near_tie_share": {"limit": 0.5, "epsilon": 0.002},
          "state_gap_worst_head": {"limit": 0.01}}


def toy(limits=None):
    manifest, entry, config, traffic, real = bench_run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(
        vocab_size=512, hidden_size=64, hybrid_override_pattern="MEM*EM",
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=8, moe_intermediate_size=32,
        moe_latent_size=24, moe_shared_expert_intermediate_size=48,
        n_routed_experts=8, num_experts_per_tok=3, experts_held=[2, 4])
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
        "routing_near_tie_share", "state_gap_worst_head",
        "served_tokens_compared", "moe_dropped_assignments"}
    assert line["compared"]["moe_dropped_assignments"]["value"] == 0
    assert line["compared"]["state_gap_worst_head"]["value"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert {"ttft_p50_ms", "itl_p95_ms"} <= set(line["detail"])
    state = line["detail"]["state"]
    # 4 lanes of 3 state layers: 8 x 8 x 16 float32 + a bf16 tail of 3
    # rows of 128 columns
    assert state["bytes"] == 4 * 3 * (8 * 8 * 16 * 4 + 3 * 128 * 2)
    assert state["resets"] > 0 and state["rounds"] > 0
    assert line["detail"]["moe"]["dropped"] == 0


def test_rehearsal_of_a_traced_run():
    """`--trace 1`: the counters' metrics find something to read on the
    CPU too; the shares of a device trace find none and are left out."""
    line = json.loads(json.dumps(one_run(seed=9, trace=1)))
    manifest = toy()[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  CELL)}
    assert {"serve_mfu_hybrid", "decode_hbm_roofline_hybrid",
            "state_bytes_share", "prefill_step_device_ms"} <= mine
    assert set(line["metrics"]) <= mine
    assert {"moe_tokens_per_expert_mean", "moe_load_max_over_mean",
            "decode_lanes_mean", "state_bytes_share"} \
        <= set(line["metrics"])
    assert 0 < line["metrics"]["state_bytes_share"]["value"] < 100
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    assert line["correct"] is True, line["compared"]


@pytest.fixture(scope="module")
def served():
    """One window's driver, released: what both controls judge."""
    manifest, entry, config, traffic, limits = toy(LIMITS)
    driver = bench_run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, jax.devices()[:1], 11)
    driver.setup()
    driver.window(1.0, None)
    driver.release()
    return driver, limits


def test_the_fp8_control_comes_out_as_not_correct(served):
    """The reference with e4m3 matmul operands, judged like the
    program: over the toy's limits on both logit numbers."""
    driver, limits = served
    epsilon = limits["routing_near_tie_share"]["epsilon"]
    sound, n = driver.gaps(driver.sample(), epsilon=epsilon)
    control, _ = driver.gaps(driver.sample(), "fp8", epsilon=epsilon)
    for name, key in (("served_logit_gap_p99", "gap_p99"),
                      ("served_logit_gap_mean", "gap_mean")):
        limit = limits[name]["limit"]
        assert n > 100 and sound[key] <= limit < control[key], (
            name, sound, control)


def test_the_bf16_state_control_comes_out_as_not_correct(served):
    """The reference with its recurrent state rounded to bfloat16 after
    every step: the state it leaves is over the limit the program's
    float32 pool stays under, and the logit numbers cannot tell (which
    is why the state is compared)."""
    driver, limits = served
    limit = limits["state_gap_worst_head"]["limit"]
    sound, heads = driver.state_gap()
    control, _ = driver.state_gap("bf16_state")
    # the one state layer in front of the first expert layer
    assert heads == len(driver.probes) * 1 * 8
    assert 0 < sound <= limit < control, (sound, control)


# --- the counts, by hand, at the published widths -------------------------

@pytest.fixture(scope="module")
def published():
    return bench_run.load_json("configs",
                               "nemotron3_super_120b_ep4_serve.json")


def test_the_configuration_keeps_the_published_widths(published):
    c = published
    catalog = dict(
        hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64,
        n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
        expand=2, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, n_routed_experts=512, num_experts_per_tok=22,
        moe_intermediate_size=2688, moe_latent_size=1024,
        moe_shared_expert_intermediate_size=5376, routed_scaling_factor=5)
    assert {k: c[k] for k in catalog} == catalog
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "experts_held", "vocab_size",
                            "num_nextn_predict_layers"]
    assert c["hybrid_override_pattern"] == "MEMEMEM*EME" \
        == c["published"]["hybrid_override_pattern"][:11]
    assert len(c["published"]["hybrid_override_pattern"]) == 88
    assert c["experts_held"] == [0, 128] and c["vocab_size"] == 32768
    for key in ("deployment", "assumed", "precision", "engine",
                "features_off"):
        assert c[key]


def test_parameter_counts_by_hand(published):
    c = published
    # in_proj 4096 x (8192 + 10240 + 128), out_proj 8192 x 4096, 5 x
    # 10240 of convolution, 3 x 128 of A_log, D and dt_bias, two norms
    assert flops_hybrid.mamba_params(c) == 4096 * 18560 + 8192 * 4096 \
        + 5 * 10240 + 384 + 8192 + 4096 == 109_640_064
    # q and o 4096 x 4096, k and v 4096 x 256, the norm
    assert flops_hybrid.attention_params(c) == 2 * 4096 * 4096 \
        + 2 * 4096 * 256 + 4096 == 35_655_680
    assert flops_hybrid.expert_params(c) == 2 * 1024 * 2688 == 5_505_024
    # router, two latent projections, the shared expert, bias and norm
    assert flops_hybrid.expert_layer_params(c) == 4096 * 512 \
        + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 512 + 4096 == 54_530_560
    total = (5 * 109_640_064 + 35_655_680
             + 5 * (54_530_560 + 128 * 5_505_024)
             + 2 * 32768 * 4096 + 4096)
    assert flops_hybrid.total_params(c) == total == 4_648_163_712
    # ISSUE 34: 9.30 GB of bfloat16 weights
    assert round(2 * total / 1e9, 2) == 9.30
    # a decode round reads everything but the embedding table and the
    # routed experts
    assert flops_hybrid.decode_round_weight_bytes(c) == 2 * (
        total - 32768 * 4096 - 5 * 128 * 5_505_024)


def test_operations_by_hand(published):
    c = published
    scan = 4 * 8192 * 128
    mamba = 2 * (4096 * 18560 + 8192 * 4096) + 2 * 4 * 10240 + scan
    attention = 2 * (2 * 4096 * 4096 + 2 * 4096 * 256)
    experts = 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376)
    head = 2 * 4096 * 32768
    assert flops_hybrid.scan_flops(c) == scan
    assert flops_hybrid.token_flops(c, 500, True) == \
        5 * mamba + attention + 4 * 4096 * 500 + 5 * experts + head
    assert flops_hybrid.token_flops(c, 100, False) == \
        5 * mamba + attention + 4 * 4096 * 100 + 5 * experts
    # a prompt of 3 and one decoded token at context 9, 7 assignments
    # on held experts
    body = 5 * mamba + attention + 5 * experts
    want = (3 * body + 4 * 4096 * (1 + 2 + 3) + head
            + body + 4 * 4096 * 9 + head + 7 * 2 * 5_505_024)
    assert flops_hybrid.serve_flops(c, [3], [9], 7) == want


def test_state_and_kv_bytes_by_hand(published):
    c = published
    # a lane: 5 layers of 128 x 64 x 128 float32 and 3 rows of 10240 bf16
    lane = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert flops_hybrid.lane_state_bytes(c) == lane == 21_278_720
    # ISSUE 34: 2.72 GB at 128 lanes
    assert round(128 * lane / 1e9, 2) == 2.72
    # read and written by each of 100 decoded tokens' lanes
    assert flops_hybrid.state_bytes(c, 100) == 2 * 100 * lane
    # one attention layer: K and V rows of 2 heads of 128 in bf16
    assert flops_hybrid.kv_bytes(c, [50, 700]) == 2 * 2 * 128 * 2 * 750
    # ISSUE 34's round: 14.4 GB with every held expert read
    whole = (flops_hybrid.decode_round_weight_bytes(c)
             + 5 * 128 * flops_hybrid.expert_bytes(c)
             + flops_hybrid.state_bytes(c, 128))
    assert 14.3e9 < whole < 14.6e9


def test_counter_readers_by_hand(published):
    class Trace:
        planes = {"/host:CPU": {"python": [
            ("azt:generation.decode[l=96,w=0]", 0, 10),
            ("azt:generation.decode[l=32,w=0]", 20, 10)]}}
    moe = dict(tokens=[[1]], held=0, loads_decode=640, loads_prefill=0)
    ctx = dict(config=published, trace=Trace(),
               window=dict(moe={"window": moe},
                           state=dict(bytes=128 * 21_278_720, rounds=2)))
    # 64 of 128 lanes a round: half the pool read and written, beside
    # the round's weights and 320 expert loads
    moved = 2 * 64 * 21_278_720
    weights = flops_hybrid.decode_round_weight_bytes(published) \
        + 320 * 2 * 5_505_024
    assert readers.state_bytes_share(ctx) == pytest.approx(
        100 * moved / (moved + weights))
    assert readers.state_bytes_share(
        dict(ctx, window={"moe": {"window": moe}})) is None
    assert readers.serve_mfu_hybrid(
        dict(ctx, traced=(0, 1), window={"records": []})) is None
