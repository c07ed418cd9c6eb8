"""The readers of the `lfm2_moe` configuration's per-layer metrics
(`readers/serve_mfu_conv.py` and its two neighbours are a line each
over these).  `ctx` is `layer_metrics.py`'s; `ctx["window"]["moe"]` is
what `drivers/serve_closed_exaone.py` adds to the window's record (the
program's `generation_moe_*` counters over the window and over its
traced part), `ctx["window"]["state"]` what
`drivers/serve_closed_nemotron.py` adds (the program's
`generation_state_*`, the recurrent pool's bytes among them).  A reader
that finds nothing to read returns None."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops, flops_conv
from benchmarks.harness.layer_metrics import traced_serving_work
from benchmarks.harness.layer_metrics_moe import PAGED_DECODE_GQA, counts


def serve_mfu_conv(ctx: Dict) -> Optional[float]:
    moe = counts(ctx, "traced")
    prompts, contexts = traced_serving_work(ctx)
    if moe is None or (not prompts and not contexts):
        return None
    need = flops_conv.serve_flops(ctx["config"], prompts, contexts,
                                  moe["held"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def decode_bytes(ctx: Dict, rounds: int) -> Optional[Dict[str, float]]:
    """What `rounds` traced decode rounds had to move, by kind: the
    weights of every round, the routed experts the rounds' counts show
    a token for, the keys and values in sight, and the live lanes'
    convolution tails read and written, a lane's at the size the
    program's pool gauge gives (`generation_state_bytes` over the
    slots)."""
    moe = counts(ctx, "traced")
    state = ctx["window"].get("state")
    _, contexts = traced_serving_work(ctx)
    if moe is None or not state or not rounds:
        return None
    config = ctx["config"]
    lane = state["bytes"] / config["engine"]["max_slots"]
    return dict(
        weights=rounds * flops_conv.decode_round_weight_bytes(config),
        experts=moe["loads_decode"] * flops_conv.expert_bytes(config),
        kv=flops_conv.kv_bytes(config, contexts),
        tails=flops_conv.tail_bytes(lane, len(contexts)))


def decode_hbm_roofline_conv(ctx: Dict) -> Optional[float]:
    """Those bytes over peak bandwidth, against `jit_decode`'s device
    time.  Memory bounds the cell: 128 lanes meet 10.6 GB of weights,
    9.7 GB of them experts."""
    rounds, seconds = ctx["trace"].program("jit_decode")
    need = decode_bytes(ctx, rounds)
    if need is None or not seconds:
        return None
    return 100.0 * sum(need.values()) \
        / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def paged_decode_gqa_roofline_conv(ctx: Dict) -> Optional[float]:
    """Keys and values the live contexts of the traced rounds need over
    the two attention layers (head_dim 64, no window) and the two
    products over them, against the grouped-query kernel's device
    time."""
    _, seconds = ctx["trace"].ops(PAGED_DECODE_GQA)
    _, contexts = traced_serving_work(ctx)
    if not seconds or not contexts:
        return None
    config = ctx["config"]
    return flops.roofline_share(
        flops_conv.kv_flops(config, contexts),
        flops_conv.kv_bytes(config, contexts), seconds,
        ctx["peaks"])["share"]
