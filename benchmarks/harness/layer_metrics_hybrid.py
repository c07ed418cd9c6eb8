"""The readers of the hybrid configuration's per-layer metrics
(`readers/serve_mfu_hybrid.py` and its three neighbours are a line each
over these).  `ctx` is `layer_metrics.py`'s; `ctx["window"]["moe"]` is
what `drivers/serve_closed_exaone.py` adds to the window's record (the
program's `generation_moe_*` counters over the window and over its
traced part), `ctx["window"]["state"]` what
`drivers/serve_closed_nemotron.py` adds (the program's
`generation_state_*`).  A reader that finds nothing to read returns
None."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops_hybrid
from benchmarks.harness.layer_metrics import program_ms, traced_serving_work
from benchmarks.harness.layer_metrics_moe import counts
from benchmarks.harness.span_metrics import decode_counts


def serve_mfu_hybrid(ctx: Dict) -> Optional[float]:
    moe = counts(ctx, "traced")
    prompts, contexts = traced_serving_work(ctx)
    if moe is None or (not prompts and not contexts):
        return None
    need = flops_hybrid.serve_flops(ctx["config"], prompts, contexts,
                                    moe["held"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def decode_bytes(ctx: Dict, rounds: int) -> Optional[Dict[str, float]]:
    """What `rounds` traced decode rounds had to move, by kind: the
    weights of every round, the routed experts the rounds' counts show
    a token for, the recurrent state of the live lanes (read and
    written), the keys and values in sight."""
    moe = counts(ctx, "traced")
    _, contexts = traced_serving_work(ctx)
    if moe is None or not rounds:
        return None
    config = ctx["config"]
    return dict(
        weights=rounds * flops_hybrid.decode_round_weight_bytes(config),
        experts=moe["loads_decode"] * flops_hybrid.expert_bytes(config),
        state=flops_hybrid.state_bytes(config, len(contexts)),
        kv=flops_hybrid.kv_bytes(config, contexts))


def decode_hbm_roofline_hybrid(ctx: Dict) -> Optional[float]:
    """Those bytes over peak bandwidth, against `jit_decode`'s device
    time.  Memory bounds the cell: 128 lanes meet 9 GB of weights and
    2.7 GB of state."""
    rounds, seconds = ctx["trace"].program("jit_decode")
    need = decode_bytes(ctx, rounds)
    if need is None or not seconds:
        return None
    return 100.0 * sum(need.values()) \
        / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def state_bytes_share(ctx: Dict) -> Optional[float]:
    """The recurrent state's part of a decode round's bytes, from the
    program's own numbers: the pool's size (`generation_state_bytes`)
    by the share of the lanes a round holds (`decode_lanes_mean`), read
    and written, over that and the weights a round reads (the routed
    experts by the window's loads a decode round); the keys and values
    are under a hundredth and left out."""
    state = ctx["window"].get("state")
    moe = counts(ctx, "window")
    lanes = decode_counts(ctx, 0)
    if not state or moe is None or lanes is None or not state["rounds"]:
        return None
    config = ctx["config"]
    moved = 2.0 * state["bytes"] * lanes / config["engine"]["max_slots"]
    weights = (flops_hybrid.decode_round_weight_bytes(config)
               + moe["loads_decode"] / state["rounds"]
               * flops_hybrid.expert_bytes(config))
    return 100.0 * moved / (moved + weights)


def prefill_step_device_ms(ctx: Dict) -> Optional[float]:
    """Mean device time of one execution of the prefill program, over
    its buckets: where the chunked scan lives."""
    return program_ms(ctx, "jit_prefill")
