"""What the per-layer readers share: each reader in `readers/` is a few
lines over these.  `ctx` is what `run.py` hands a reader: `trace` (the
reduced trace), `traced` (the traced part of the window on the clients'
clock: start, stop), `window` (the driver's own record of the window),
`config`, `traffic`, `peaks` (the row of the device's kind), `chips`.

A reader that finds nothing to read returns None, and the metric is
left out of the line; a share never reads 0 for want of events."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import flops, stats


#: how the two Mosaic kernels that have a roofline here show in a trace
#: today: no `pl.pallas_call` of the program passes `name=`, so a kernel
#: is a `tpu_custom_call` named after the Flax module that calls it —
#: the decoder itself for the paged-decode kernel, `fc1` for the fused
#: bias-GELU dense (PERF.md, Open questions, asks the `tracing` issue
#: for kernel names of their own)
PAGED_DECODE = r"^CausalLM(\.\d+)? custom-call .* tpu_custom_call$"
BIAS_GELU = r"^fc1(\.\d+)? custom-call .* tpu_custom_call$"


def program_ms(ctx: Dict, program: str) -> Optional[float]:
    """Mean device time of one execution of a compiled program."""
    n, seconds = ctx["trace"].program(program)
    return 1e3 * seconds / n if n else None


def window_percentile(ctx: Dict, key: str, q: float) -> Optional[float]:
    return stats.percentile(ctx["window"].get(key) or [], q)


def traced_serving_work(ctx: Dict):
    """(prompt lengths prefilled, contexts of the tokens decoded) inside
    the traced part of the window, from the clients' records: a
    request's first token comes from its prefill, token j >= 1 from a
    decode round at context prompt + j."""
    t0, t1 = ctx["traced"]
    prompts, contexts = [], []
    for r in ctx["window"]["records"]:
        for j, t in enumerate(r["stamps"]):
            if t0 <= t < t1:
                if j == 0:
                    prompts.append(len(r["prompt"]))
                else:
                    contexts.append(len(r["prompt"]) + j)
    return prompts, contexts


def serve_mfu(ctx: Dict) -> Optional[float]:
    prompts, contexts = traced_serving_work(ctx)
    if not prompts and not contexts:
        return None
    need = flops.serve_flops(ctx["config"]["model"], prompts, contexts)
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def train_mfu(ctx: Dict) -> Optional[float]:
    tokens = ctx["window"].get("traced_tokens")
    if not tokens:
        return None
    need = tokens * flops.train_token_flops(
        ctx["config"]["model"], int(ctx["config"]["estimator"]["seq_len"]))
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * need / (ctx["trace"].window_s * peak)


def paged_decode_roofline(ctx: Dict) -> Optional[float]:
    """The keys and values the live contexts of the traced rounds need
    (one call a layer a round; a lane at context c reads c positions)
    and the two attention products over them, against the kernel's
    device time.  Memory bounds it at every context length: 4*d
    operations and 4*d bytes a position."""
    _, seconds = ctx["trace"].ops(PAGED_DECODE)
    _, contexts = traced_serving_work(ctx)
    if not seconds or not contexts:
        return None
    model = ctx["config"]["model"]
    share = model["n_block"] / ctx["chips"]    # heads split over chips
    need = flops.roofline_share(
        share * 4.0 * model["hidden_size"] * sum(contexts),
        share * flops.paged_decode_kv_bytes(model, contexts),
        seconds, ctx["peaks"])
    return need["share"]


def bias_gelu_roofline(ctx: Dict) -> Optional[float]:
    """Every call of the fused fc1 + bias + GELU kernel over the
    batch's rows (the backward pass's recomputation is a call like any
    other), against the kernel's device time.  Compute bounds it."""
    n, seconds = ctx["trace"].ops(BIAS_GELU)
    if not seconds:
        return None
    model, est = ctx["config"]["model"], ctx["config"]["estimator"]
    rows = int(est["batch_size"]) * int(est["seq_len"]) // ctx["chips"]
    need = flops.roofline_share(
        n * flops.bias_gelu_flops(rows, model),
        n * flops.bias_gelu_bytes(rows, model), seconds, ctx["peaks"])
    return need["share"]
