"""The reduction from a profiler trace to numbers, on a small recorded
trace: `data/serve_trace_cut.json` is 134.5 ms of the serving cell's
first traced window on the chip (two decode rounds and two prefills,
32 lanes), cut by `Trace.cut` and kept as it was reduced.

Every expectation is worked out here from the file by plain loops, or
by hand from the shapes, never by the code under test."""

import json
import os

import pytest

from _bench_toy import ROOT  # noqa: F401  (puts the repo on the path)
from benchmarks.harness import flops, layer_metrics, trace_reduce

CUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "serve_trace_cut.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GPT2 = {"hidden_size": 768, "n_block": 12, "intermediate_size": 3072,
        "vocab": 50257}


@pytest.fixture(scope="module")
def raw():
    with open(CUT) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_json(CUT)


def by_hand_union(events):
    """Busy nanoseconds by a sweep over sorted edges, not by merging."""
    edges = []
    for _, start, dur in events:
        if dur > 0:
            edges += [(start, 1), (start + dur, -1)]
    busy, depth, since = 0, 0, None
    for t, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_busy_and_idle_share(raw, trace):
    ops = raw["planes"]["/device:TPU:0"]["XLA Ops"]
    busy = by_hand_union(ops) / 1e9
    assert trace.busy_s == pytest.approx(busy, rel=1e-12)
    assert trace.busy_s == pytest.approx(0.09717848, rel=1e-6)
    assert trace.window_s == pytest.approx(0.134532816)
    # 1 - 0.09717848 / 0.134532816
    assert trace.idle_share == pytest.approx(27.76597, rel=1e-5)


def test_one_programs_device_time(raw, trace):
    modules = raw["planes"]["/device:TPU:0"]["XLA Modules"]
    decodes = [d for n, _, d in modules if n.startswith("jit_decode(")]
    assert trace.program("jit_decode") == (
        2, pytest.approx(sum(decodes) / 1e9))
    # two rounds of 32.35 and 32.43 ms, two prefills of 16.48 and 15.92
    assert sum(decodes) / 1e9 == pytest.approx(0.064778307)
    assert trace.program("jit_prefill")[0] == 2
    assert trace.program("jit_prefill")[1] == pytest.approx(0.032406063)
    assert trace.program("jit__train_step_impl") == (0, 0.0)


def test_one_kernels_device_time(raw, trace):
    """The paged-decode kernel: one call a layer a round, 24 in all."""
    ops = raw["planes"]["/device:TPU:0"]["XLA Ops"]
    mine = [d for n, _, d in ops
            if n.startswith("CausalLM.") and n.endswith("tpu_custom_call")]
    assert len(mine) == 24
    n, seconds = trace.ops(layer_metrics.PAGED_DECODE)
    assert n == 24
    assert seconds == pytest.approx(sum(mine) / 1e9)
    assert seconds == pytest.approx(0.011643759)
    # the LayerNorm kernels are kernels too, and are not counted as it
    every, _ = trace.ops(r"tpu_custom_call$")
    assert every > 24
    assert trace.ops(layer_metrics.BIAS_GELU) == (0, 0.0)


def test_one_roofline_share_by_hand(trace):
    """Two rounds of 32 lanes, every lane at context 300: each of the 24
    calls reads 32 * 300 positions of keys and of values, 768 bf16
    values each: 24 * 9600 * 2 * 768 * 2 = 707,788,800 bytes, 0.864211
    ms at 819 GB/s, over the kernel's 11.643759 ms: 7.4221 %.  (Its
    operations, 24 * 9600 * 4 * 768 = 0.708 GFLOP, are 3.6 us of the
    peak: memory bounds it.)"""
    record = dict(prompt=[0] * 298, stamps=[0.5, 1.5, 1.6])  # ctx 299, 300
    ctx = dict(trace=trace, traced=(1.0, 2.0), chips=1, peaks=V5E,
               config={"model": GPT2},
               window={"records": [dict(record, prompt=[0] * 299,
                                        stamps=[0.5, 1.2, 1.3, 2.5])] * 32})
    prompts, contexts = layer_metrics.traced_serving_work(ctx)
    assert prompts == [] and sorted(set(contexts)) == [300, 301]
    ctx["window"]["records"] = [dict(prompt=[0] * 299,
                                     stamps=[0.5, 1.2, 2.5])] * 64
    assert layer_metrics.traced_serving_work(ctx) == ([], [300] * 64)
    share = layer_metrics.paged_decode_roofline(ctx)
    assert share == pytest.approx(
        100 * (707_788_800 / 819e9) / 0.011643759, rel=1e-9)
    assert share == pytest.approx(7.4221, rel=1e-4)
    assert flops.roofline_share(0.708e9, 707_788_800, 0.011643759,
                                V5E)["bound"] == "memory"


def test_a_reader_with_nothing_to_read_returns_nothing(trace):
    ctx = dict(trace=trace, traced=(1.0, 2.0), chips=1, peaks=V5E,
               config={"model": GPT2,
                       "estimator": {"batch_size": 256, "seq_len": 128}},
               window={"records": []})
    assert layer_metrics.paged_decode_roofline(ctx) is None
    assert layer_metrics.bias_gelu_roofline(ctx) is None
    assert layer_metrics.serve_mfu(ctx) is None
    assert layer_metrics.train_mfu(ctx) is None
    assert layer_metrics.program_ms(ctx, "jit__train_step_impl") is None


def test_breakdown_lists_operations_not_loops(trace):
    out = trace.breakdown()
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "copy.31 copy bf16[12,2,32784,12,64]"
    assert all(len(n) <= 140 for n in names)
    assert all(s > 0 for _, s in out["device_ops"] + out["idle_gaps"])
    # the device waits while the host fetches the round's tokens
    assert out["idle_gaps"][0][0] == "np.asarray(jax.Array)"


@pytest.mark.parametrize("text,want", [
    ("%copy.31 = bf16[12,2,32784,12,64]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[12,2,32784,12,64]{2,4,3,1,0:T(8,128)(2,1)} %kv.1)",
     "copy.31 copy bf16[12,2,32784,12,64]"),
    ("%while.6 = (s32[]{:T(128)}, f32[256,128,768]{2,1,0:T(8,128)}) "
     "while((s32[]{:T(128)}, f32[256,128,768]{2,1,0}) %tuple.178), "
     "condition=%c, body=%b", "while.6 while s32[]"),
    ('%fc1.12 = bf16[32768,3072]{1,0:T(8,128)(2,1)} custom-call('
     'bf16[32768,768]{1,0} %x), custom_call_target="tpu_custom_call"',
     "fc1.12 custom-call bf16[32768,3072] tpu_custom_call"),
    ("np.asarray(jax.Array)", "np.asarray(jax.Array)"),
])
def test_short_names_of_operations(text, want):
    assert trace_reduce.short_op(text) == want
    if want.startswith("while"):
        assert trace_reduce.opcode(want) in trace_reduce.CONTAINERS


def test_flops_from_shapes_by_hand():
    # one block's matmul weights: 4 * 768^2 + 2 * 768 * 3072 = 7,077,888
    assert flops.block_matmul_params(GPT2) == 7_077_888
    # a trained token: 6 * 12 * 7,077,888 + 12 * 12 * 768 * 128
    assert flops.train_token_flops(GPT2, 128) == 509_607_936 + 14_155_776
    # a decoded token at context 300, with its logits
    assert flops.decoder_token_flops(GPT2, 300, True) == (
        2 * 12 * 7_077_888 + 4 * 12 * 768 * 300 + 2 * 768 * 50257)
    # the fused fc1 over a batch of 256 x 128 rows
    assert flops.bias_gelu_flops(32768, GPT2) == 2 * 32768 * 768 * 3072
