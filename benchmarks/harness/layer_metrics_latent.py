"""The readers of the latent-attention configuration's per-layer
metrics (`readers/serve_mfu_latent.py` and its three neighbours are a
line each over these).  `ctx` is `layer_metrics.py`'s;
`ctx["window"]["moe"]` is what `drivers/serve_closed_exaone.py` adds to
the window's record (the program's `generation_moe_*` counters over the
window and over its traced part), `ctx["window"]["kv"]` what
`drivers/serve_closed_sarvam.py` adds (the program's
`generation_kv_row_bytes` and the pool's sizes).  A reader that finds
nothing to read returns None."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops, flops_latent
from benchmarks.harness.layer_metrics import traced_serving_work
from benchmarks.harness.layer_metrics_moe import counts

#: the latent decode kernel in a trace: its `pl.pallas_call` passes no
#: `name=`, so the event carries the scope it was traced under, the
#: decoder's `attn.latent`
LATENT_DECODE = r"^attn\.latent(\.\d+)? custom-call .* tpu_custom_call$"


def serve_mfu_latent(ctx: Dict) -> Optional[float]:
    moe = counts(ctx, "traced")
    prompts, contexts = traced_serving_work(ctx)
    if moe is None or (not prompts and not contexts):
        return None
    need = flops_latent.serve_flops(ctx["config"], prompts, contexts,
                                    moe["held"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def decode_bytes(config: Dict, rounds: int, expert_loads: int,
                 contexts, row_bytes: Optional[float] = None
                 ) -> Dict[str, float]:
    """What `rounds` decode rounds had to read, by kind: the weights of
    every round, the routed experts the rounds' counts show a token for
    (`expert_loads`), the cached latent rows of every decoded token
    (`contexts`: the cached positions each read; `row_bytes` a cached
    token over all layers: the configuration's by default)."""
    return dict(
        weights=rounds * flops_latent.decode_round_weight_bytes(config),
        experts=expert_loads * flops_latent.expert_bytes(config),
        latent=(flops_latent.latent_bytes(config, contexts)
                if row_bytes is None else float(row_bytes) * sum(contexts)))


def decode_hbm_roofline_latent(ctx: Dict) -> Optional[float]:
    """What the traced decode rounds had to read over peak bandwidth,
    against `jit_decode`'s device time: 128 lanes meet 5 GB of weights
    and some 2 GB of live rows."""
    moe = counts(ctx, "traced")
    rounds, seconds = ctx["trace"].program("jit_decode")
    _, contexts = traced_serving_work(ctx)
    if moe is None or not rounds or not seconds:
        return None
    need = decode_bytes(ctx["config"], rounds, moe["loads_decode"],
                        contexts)
    return 100.0 * sum(need.values()) \
        / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def latent_decode_roofline(ctx: Dict) -> Optional[float]:
    """The cached rows the live contexts of the traced rounds need and
    the absorbed products over them, against the latent kernel's device
    time: 121 operations a byte, half the chip's ridge, so memory
    bounds it."""
    _, seconds = ctx["trace"].ops(LATENT_DECODE)
    _, contexts = traced_serving_work(ctx)
    if not seconds or not contexts:
        return None
    config = ctx["config"]
    return flops.roofline_share(
        flops_latent.latent_flops(config, contexts),
        flops_latent.latent_bytes(config, contexts), seconds,
        ctx["peaks"])["share"]


def window_decode_contexts(ctx: Dict):
    """Contexts of the tokens decoded from the window's opening until
    its last request has ended — the stretch the program's counters in
    the window's record cover, the clients' drain in it — from the
    clients' records (`traced_serving_work` over that stretch)."""
    return traced_serving_work(
        dict(ctx, traced=(ctx["window"]["t_open"], float("inf"))))[1]


def latent_bytes_share(ctx: Dict) -> Optional[float]:
    """The cached rows' part of the bytes the decode rounds had to
    read from the window's opening to its last request's end, from the
    program's own numbers — its row size (`generation_kv_row_bytes`),
    its decode rounds, its expert loads — and the contexts of the
    tokens it served: how much of a step is the new mechanism's."""
    kv = ctx["window"].get("kv")
    moe = counts(ctx, "window")
    if not kv or moe is None or not kv.get("rounds"):
        return None
    need = decode_bytes(ctx["config"], kv["rounds"], moe["loads_decode"],
                        window_decode_contexts(ctx), kv["row_bytes"])
    return 100.0 * need["latent"] / sum(need.values())
