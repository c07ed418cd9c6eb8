"""The readers of the expert-layer configuration's per-layer metrics
(`readers/serve_mfu_moe.py` and its four neighbours are a line each over
these).  `ctx` is `layer_metrics.py`'s; `ctx["window"]["moe"]` is what
`drivers/serve_closed_exaone.py` adds to the window's record: the
program's `generation_moe_*` counters over the whole window
(`"window"`) and over its traced part (`"traced"`).  A reader that finds
nothing to read returns None."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops, flops_moe
from benchmarks.harness.layer_metrics import traced_serving_work

#: the grouped-query paged kernel in a trace: no `pl.pallas_call` passes
#: `name=`, so the event carries the scope it was traced under — the
#: decoder's `attn.window` / `attn.full` named scopes (or the module
#: itself, should a later PR drop them)
PAGED_DECODE_GQA = (r"^(attn\.window|attn\.full|DecoderLM)(\.\d+)? "
                    r"custom-call .* tpu_custom_call$")


def counts(ctx: Dict, part: str) -> Optional[Dict]:
    return (ctx["window"].get("moe") or {}).get(part)


def serve_mfu_moe(ctx: Dict) -> Optional[float]:
    moe = counts(ctx, "traced")
    prompts, contexts = traced_serving_work(ctx)
    if moe is None or (not prompts and not contexts):
        return None
    need = flops_moe.serve_flops(ctx["config"], prompts, contexts,
                                 moe["held"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def decode_hbm_roofline(ctx: Dict) -> Optional[float]:
    """What the traced decode rounds had to read — the weights of every
    round (routed experts only where the round's count shows a token),
    the keys and values in sight of every decoded token — over peak
    bandwidth, against `jit_decode`'s device time.  Memory bounds it:
    64 lanes meet 12 GB of weights."""
    moe = counts(ctx, "traced")
    rounds, seconds = ctx["trace"].program("jit_decode")
    _, contexts = traced_serving_work(ctx)
    if moe is None or not rounds or not seconds:
        return None
    config = ctx["config"]
    need = (rounds * flops_moe.decode_round_weight_bytes(config)
            + moe["loads_decode"] * flops_moe.expert_bytes(config)
            + flops_moe.kv_bytes(config, contexts))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def paged_decode_gqa_roofline(ctx: Dict) -> Optional[float]:
    """Keys and values the live contexts of the traced rounds need
    (a window layer: the positions in sight) and the two products over
    them, against the kernel's device time."""
    _, seconds = ctx["trace"].ops(PAGED_DECODE_GQA)
    _, contexts = traced_serving_work(ctx)
    if not seconds or not contexts:
        return None
    config = ctx["config"]
    return flops.roofline_share(
        flops_moe.kv_flops(config, contexts),
        flops_moe.kv_bytes(config, contexts), seconds,
        ctx["peaks"])["share"]


def moe_tokens_per_expert_mean(ctx: Dict) -> Optional[float]:
    """Tokens a held expert computes each time a dispatch reads its
    weights (prefills and decode rounds alike): the reuse a weight read
    gets."""
    moe = counts(ctx, "window")
    loads = moe and moe["loads_decode"] + moe["loads_prefill"]
    return sum(map(sum, moe["tokens"])) / loads if loads else None


def moe_load_max_over_mean(ctx: Dict) -> Optional[float]:
    """The busiest held expert of a layer over the layer's mean, over
    the window's tokens, averaged over the expert layers."""
    moe = counts(ctx, "window")
    layers = [row for row in (moe or {}).get("tokens", []) if sum(row)]
    if not layers:
        return None
    return sum(max(row) * len(row) / sum(row) for row in layers) \
        / len(layers)
