"""The readers of the looped configuration's per-layer metrics
(`readers/serve_mfu_looped.py` and its three neighbours are a line
each over these).  `ctx` is `layer_metrics.py`'s; `ctx["window"]["kv"]`
is what `drivers/serve_closed_ouro.py` adds to the window's record: the
program's own gauges of what a token holds (`generation_kv_row_bytes`,
`generation_loop_steps`, `generation_kv_layer_slots`) and the decode
rounds of the window.  A program without the looped form has no such
gauges, and every reader here then returns None."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops, flops_looped
from benchmarks.harness.layer_metrics import traced_serving_work
from benchmarks.harness.layer_metrics_latent import window_decode_contexts

#: the paged decode kernel of the looped stack in a trace: its
#: `pl.pallas_call` passes no `name=`, so the event carries the scope it
#: was traced under, the stack's `attn.loop`
PAGED_DECODE_LOOP = r"^attn\.loop(\.\d+)? custom-call .* tpu_custom_call$"


def loop(ctx: Dict) -> Optional[Dict]:
    """The program's numbers of the loop, or None where it has none."""
    kv = ctx["window"].get("kv") or {}
    if not kv.get("loop_steps") or not kv.get("layer_slots"):
        return None
    return kv


def serve_mfu_looped(ctx: Dict) -> Optional[float]:
    kv = loop(ctx)
    prompts, contexts = traced_serving_work(ctx)
    if kv is None or (not prompts and not contexts):
        return None
    need = flops_looped.serve_flops(ctx["config"], prompts, contexts,
                                    kv["loop_steps"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def decode_bytes(config: Dict, kv: Dict, rounds: int, contexts
                 ) -> Dict[str, float]:
    """What `rounds` decode rounds had to read, by kind: the weights
    (every layer once a step, the head) and the cached rows of every
    decoded token over all the pool's slots."""
    return dict(
        weights=rounds * flops_looped.decode_round_weight_bytes(
            config, kv["loop_steps"]),
        rows=float(kv["row_bytes"]) * sum(contexts))


def decode_hbm_roofline_looped(ctx: Dict) -> Optional[float]:
    """What the traced decode rounds had to read over peak bandwidth,
    against `jit_decode`'s device time: 16 lanes meet 19.7 GB of
    weight reads and some 4 GB of live rows a round."""
    kv = loop(ctx)
    rounds, seconds = ctx["trace"].program("jit_decode")
    _, contexts = traced_serving_work(ctx)
    if kv is None or not rounds or not seconds:
        return None
    need = decode_bytes(ctx["config"], kv, rounds, contexts)
    return 100.0 * sum(need.values()) \
        / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def paged_decode_looped_roofline(ctx: Dict) -> Optional[float]:
    """The keys and values the live contexts of the traced rounds need
    over every slot, and the two products over them, against the paged
    kernel's device time under `attn.loop`: 4 * h * d operations and
    4 * g * d bytes a position a slot, so memory bounds it."""
    kv = loop(ctx)
    _, seconds = ctx["trace"].ops(PAGED_DECODE_LOOP)
    _, contexts = traced_serving_work(ctx)
    if kv is None or not seconds or not contexts:
        return None
    config = ctx["config"]
    return flops.roofline_share(
        flops_looped.kv_flops(config, contexts, kv["layer_slots"]),
        flops_looped.kv_bytes(config, contexts, kv["layer_slots"]),
        seconds, ctx["peaks"])["share"]


def loop_kv_bytes_share(ctx: Dict) -> Optional[float]:
    """The step-indexed rows' part of the bytes the decode rounds had
    to read from the window's opening to its last request's end, from
    the program's own numbers — its row size over all slots, its loop
    steps, its decode rounds — and the contexts of the tokens it
    served."""
    kv = loop(ctx)
    if kv is None or not kv.get("rounds"):
        return None
    need = decode_bytes(ctx["config"], kv, kv["rounds"],
                        window_decode_contexts(ctx))
    return 100.0 * need["rows"] / sum(need.values())
