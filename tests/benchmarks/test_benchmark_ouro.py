"""The looped cell (`ouro_serve_decode`) at toy widths on the suite's
CPU device — a whole run through `run.py`'s `run()`, untraced and
traced, and the control that has to come out as not correct — and each
count of `harness/flops_looped.py` against the same count by hand at
the published widths.

Nothing here is a speed number.  The toy: Ouro's keys at hidden 64, 4
heads of 16, 2 layers run 4 times a token (8 cache slots), a
vocabulary of 512, bfloat16 as the cell runs."""

import argparse
import copy
import json

import jax
import pytest

from _bench_toy import bench_run
from benchmarks.harness import device, flops_looped
from benchmarks.harness import layer_metrics_looped as readers

CELL = "ouro_serve_decode"

#: toy limits, set as the chip's are.  Sound runs over seeds 7, 9, 11
#: and 2**31 + 1017 (420 to 455 served tokens each): the gap's 99th
#: percentile 0.0011 to 0.0020, its mean 2.6e-5 to 5.8e-5; the control
#: (the cached keys and values of every slot in fp8) on the same
#: samples 0.0135 to 0.0208 and 8.1e-4 to 1.7e-3.
LIMITS = {"served_logit_gap_p99": {"limit": 0.005},
          "served_logit_gap_mean": {"limit": 0.00015}}


def toy(limits=None):
    manifest, entry, config, traffic, real = bench_run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(
        vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96,
        num_hidden_layers=2, layer_types=["full_attention"] * 2)
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


def test_rehearsal_of_a_whole_run():
    line = json.loads(json.dumps(one_run(seed=2**31 + 1017)))
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {
        "served_logit_gap_p99", "served_logit_gap_mean",
        "served_tokens_compared"}
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert {"ttft_p50_ms", "itl_p95_ms"} <= set(line["detail"])
    kv = line["detail"]["kv"]
    # a bfloat16 key and value of 4 heads of 16 in each of 2 x 4 slots
    assert kv["loop_steps"] == 4 and kv["layer_slots"] == 8
    assert kv["row_bytes"] == 8 * 2 * 64 * 2
    assert kv["rounds"] > 0


def test_rehearsal_of_a_traced_run():
    """`--trace 1`: the counters' metrics find something to read on the
    CPU too; the shares of a device trace find none and are left out."""
    line = json.loads(json.dumps(one_run(seed=9, trace=1)))
    manifest = toy()[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  CELL)}
    assert {"serve_mfu_looped", "decode_hbm_roofline_looped",
            "paged_decode_looped_roofline", "loop_kv_bytes_share",
            "decode_step_device_ms", "decode_lanes_mean",
            "loop_round_ms"} <= mine
    assert set(line["metrics"]) <= mine
    assert {"decode_lanes_mean", "loop_kv_bytes_share",
            "serve_mfu_looped"} <= set(line["metrics"])
    assert 0 < line["metrics"]["loop_kv_bytes_share"]["value"] < 100
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    assert line["correct"] is True, line["compared"]
    assert set(line["detail"]["trace_edges_held_s"]) == {"start", "stop"}


def test_the_tracer_starts_and_stops_with_nothing_in_flight():
    """The profiler's session opens and closes with the engine's lock
    held and what it had in flight collected first: no round runs
    across either edge of the traced window.  The stop runs in a thread
    of its own, the lock still held when it is called."""
    import threading

    from benchmarks.drivers.serve_closed_ouro import QuietEdgesTracer
    seen = []

    class Engine:
        _lock = threading.RLock()

        def _drain(self, reason):
            seen.append(("drain", reason))

    def act(name):
        def inner():
            # held: another thread cannot take the engine's lock
            other = []
            th = threading.Thread(
                target=lambda: other.append(Engine._lock.acquire(False)))
            th.start()
            th.join()
            seen.append((name, other[0]))
        return inner

    tracer = type("Tracer", (), {})()
    tracer.t_stop = None

    def stop():
        act("stop")()
        tracer.t_stop = 1.0      # the lock is held until this is stamped
    tracer.start, tracer.stop = act("start"), stop
    quiet = QuietEdgesTracer(tracer, Engine())
    quiet.start()
    quiet.stop()
    quiet.join()
    assert seen == [("drain", "idle"), ("start", False),
                    ("drain", "idle"), ("stop", False)]
    assert set(quiet.held_s) == {"start", "stop"}


def test_the_fp8_cache_control_comes_out_as_not_correct():
    """The reference over keys and values cached one precision below
    the configuration's, judged like the program: over the toy's limits
    by both numbers."""
    manifest, entry, config, traffic, limits = toy(LIMITS)
    driver = bench_run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, jax.devices()[:1], 11)
    driver.setup()
    driver.window(1.0, None)
    driver.release()
    sound, n = driver.gaps(driver.sample())
    control, _ = driver.gaps(driver.sample(), "fp8")
    for name, key in (("served_logit_gap_p99", "gap_p99"),
                      ("served_logit_gap_mean", "gap_mean")):
        limit = limits[name]["limit"]
        assert n > 100 and sound[key] <= limit < control[key], (
            name, sound, control)


# --- the configuration and the counts, by hand ----------------------------

@pytest.fixture(scope="module")
def published():
    return bench_run.load_json("configs", "ouro_2p6b_serve.json")


def test_the_configuration_is_the_catalogs_with_nothing_cut(published):
    c = published
    catalog = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, max_position_embeddings=65536,
        max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-06,
        rope_scaling=None, rope_theta=1000000, sliding_window=None,
        tie_word_embeddings=False, total_ut_steps=4,
        early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152, layer_types=["full_attention"] * 48)
    assert {k: c[k] for k in catalog} == catalog
    assert c["reduced"] == []
    assert set(c["assumed"]) >= {"attention_bias", "rotary",
                                 "final_norm_between_steps",
                                 "early_exit_gate", "init"}
    assert c["precision"]["control"].startswith("fp8_e4m3")
    assert c["engine"]["max_slots"] == 16
    assert max(c["engine"]["prefill_buckets"]) == c["engine"]["max_context"]
    entry = [e for e in bench_run.load_cell(CELL)[0]["configs"]
             if e["name"] == c["name"]][0]
    assert entry["reduced"] == [] and len(entry["why"]) <= 200


def test_parameter_and_byte_counts_by_hand(published):
    c = published
    # W_q, W_k, W_v, W_o 2048 x 2048 each; gate, up, down 2048 x 5632;
    # four norms of 2048
    assert flops_looped.attention_params(c) == 4 * 2048 * 2048 \
        == 16_777_216
    assert flops_looped.ffn_params(c) == 3 * 2048 * 5632 == 34_603_008
    assert flops_looped.layer_params(c) == 51_388_416
    # 48 layers, embedding and head of 49,152 x 2,048, the final norm:
    # 2,668M parameters, 5.34 GB in bfloat16
    assert flops_looped.held_params(c) == 48 * 51_388_416 \
        + 2 * 49152 * 2048 + 2048 == 2_667_972_608
    # 4 steps x 48 layers = 192 slots of a key and a value of 16 x 128
    assert flops_looped.slots(c) == 192
    assert flops_looped.kv_token_bytes(c) == 192 * 2 * 2048 * 2 \
        == 1_572_864
    # a round reads every layer 4 times, and the head: 19.7 GB
    assert flops_looped.decode_round_weight_bytes(c) == 2 * (
        4 * 48 * 51_388_416 + 2048 * 49152)
    assert 19.7e9 < flops_looped.decode_round_weight_bytes(c) < 19.95e9
    assert flops_looped.kv_bytes(c, [100, 70]) == 170 * 1_572_864
    assert flops_looped.kv_flops(c, [100, 70]) == 170 * 192 * 4 * 2048


def test_the_engine_counts_the_same_parameters(published):
    """The module `from_config` builds at the published widths holds
    those parameters, stacked, and a pool of 192 slots."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.serving.generation import DecoderLM
    model = DecoderLM.from_config(published, param_dtype=jnp.bfloat16)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.arange(8)[None])["params"]
    total = sum(v.size for v in jax.tree_util.tree_leaves(abstract))
    assert total == flops_looped.held_params(published)
    assert abstract["loop_gate"]["kernel"].shape == (48, 2048, 5632)
    assert model.kv_geometry() == (192, 16, 128)


def test_operations_by_hand(published):
    c = published
    matmuls = 2 * 192 * 51_380_224
    head = 2 * 2048 * 49152
    assert flops_looped.token_flops(c, 170, True) == \
        matmuls + 192 * 4 * 2048 * 170 + head
    # a prompt of 3 and one decoded token at context 9
    want = (3 * matmuls + 192 * 4 * 2048 * (1 + 2 + 3) + head
            + matmuls + 192 * 4 * 2048 * 9 + head)
    assert flops_looped.serve_flops(c, [3], [9]) == want
    # one step less halves nothing but the loop's share
    assert flops_looped.serve_flops(c, [], [9], steps=1) == \
        2 * 48 * 51_380_224 + 48 * 4 * 2048 * 9 + head


def test_readers_by_hand_and_with_nothing_to_read(published):
    class NoTrace:
        window_s = 1.0

        def program(self, name):
            return 0, 0.0

        def ops(self, pattern):
            return 0, 0.0
    peaks = device.load_peaks()["TPU v5 lite"]
    records = [dict(prompt=[0] * 10, stamps=[0.5, 1.5, 2.5])]
    kv = dict(row_bytes=1_572_864, loop_steps=4, layer_slots=192,
              rounds=2)
    window = dict(records=records, t_open=1.0, t_close=2.0, kv=kv)
    ctx = dict(trace=NoTrace(), traced=(1.0, 3.0), window=window,
               config=published, peaks=peaks, chips=1)
    # tokens 1 and 2 at contexts 11 and 12: 23 cached positions of
    # 1.5 MiB beside two rounds of weights
    weights = 2 * flops_looped.decode_round_weight_bytes(published)
    assert readers.loop_kv_bytes_share(ctx) == pytest.approx(
        100 * 23 * 1_572_864 / (23 * 1_572_864 + weights))
    assert readers.serve_mfu_looped(ctx) == pytest.approx(
        100 * flops_looped.serve_flops(published, [], [11, 12])
        / peaks["bf16_flops_per_s"])
    for read in (readers.decode_hbm_roofline_looped,
                 readers.paged_decode_looped_roofline):
        assert read(ctx) is None
    # a program without the loop's gauges (the parent)
    bare = dict(ctx, window=dict(records=records, t_open=1.0, t_close=2.0))
    for read in (readers.serve_mfu_looped,
                 readers.decode_hbm_roofline_looped,
                 readers.paged_decode_looped_roofline,
                 readers.loop_kv_bytes_share):
        assert read(bare) is None
