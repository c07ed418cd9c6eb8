"""The plain reference of `kexaone_236b_ep8_serve`: K-EXAONE's decoder
(`model_type` `exaone_moe`) in `jax.numpy`, float32, matmuls at
`highest` precision; no kernel, no cache, no batching, nothing imported
from the program.  It reads the configuration by its published keys.

With `h` the residual stream `[t, hidden]`, for layer `l`:

    h = h + Attn_l(RMSNorm(h));  h = h + FFN_l(RMSNorm(h))
    logits = RMSNorm(h) . W_head

`Attn_l`: q, k, v projections (no bias) to `num_attention_heads` /
`num_key_value_heads` heads of `head_dim`; RMSNorm over each head of q
and k (learned scale); on `sliding_attention` layers rotary positions
(rotate-half over the whole head, `rope_theta`), none on
`full_attention` layers; query head i reads KV head i // (q heads / KV
heads); key j is visible to query p iff j <= p and, on a sliding layer,
p - j < `sliding_window`; softmax in float32; output projection.

`FFN_l` of a `dense` layer: down(silu(gate x) * up x).  Of a `sparse`
layer: s = sigmoid(W_r x) over all `num_experts`; T = the
`num_experts_per_tok` largest of s + b (b the score-correction bias);
w_i = `routed_scaling_factor` * s_i / sum_{j in T} s_j
(`norm_topk_prob`); y = sum_{i in T, i held} w_i E_i(x) + E_shared(x),
each E the gated-SiLU form at `moe_intermediate_size`.  `experts_held`
= (first id, count) is the share of a chip of an expert-parallel
deployment: the sum runs over the picked experts it holds, the shared
expert is whole, and what the absent experts would have added is left
out (model-configs guide, section 4).

Assumed, because `config.json` does not settle them (the configuration
file lists them too): the pre-norm residual order; q/k RMSNorm per head
and rotary on sliding layers only (EXAONE 4.0's convention); the
score-correction bias.  Left out: the multi-token-prediction module
(`num_nextn_predict_layers`), which drafts tokens and does not enter
the next-token distribution.

Computed a layer, a projection and an expert at a time, each weight
turned to float32 as it is used, so that the whole fits beside 12 GB of
bfloat16 weights on the chip.  `mode` is the precision of the matmul
operands and is what the control lowers: "f32" (the reference) or "fp8"
(e4m3, per-tensor scale); products always accumulate in float32.

Routing near-ties.  bfloat16 activations flip a top-k choice where the
k-th and (k+1)-th selection scores are close, and a flipped expert
moves a logit by more than any limit the fp8 control can be held over.
So `forward` also returns, per position, the reference's own smallest
margin between the k-th and the (k+1)-th selection score over the
sparse layers in which either of the two is a held expert (`inf` where
none is); the caller leaves positions under its epsilon out of the
logit comparison and compares their share instead.  The margin is the
reference's alone: nothing of the program's routing enters it."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8")
SLIDING = "sliding_attention"
SPARSE = "sparse"


def _lower(x, mode: str):
    """`x` as the matmul of `mode` sees it."""
    x = x.astype(jnp.float32)
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def matmul(x, w, mode: str):
    return jnp.matmul(_lower(x, mode), _lower(w, mode),
                      precision="highest")


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rotary(x, theta: float):
    """x [t, heads, d] at positions 0..t-1: pair (i, i + d/2) turns by
    position * theta ** (-2i / d)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                   "window", "theta", "eps", "mode"))
def attention(x, p: Dict, *, heads: int, kv_heads: int, head_dim: int,
              window: Optional[int], theta: float, eps: float, mode: str):
    """x [t, hidden] (already normalised) -> [t, hidden]; `window` None
    on a full layer.  p: q, k, v, o kernels, q_norm and k_norm scales."""
    t = x.shape[0]
    q = matmul(x, p["q"], mode).reshape(t, heads, head_dim)
    k = matmul(x, p["k"], mode).reshape(t, kv_heads, head_dim)
    v = matmul(x, p["v"], mode).reshape(t, kv_heads, head_dim)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if window is not None:
        q, k = rotary(q, theta), rotary(k, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _lower(q, mode), _lower(k, mode),
                        precision="highest") / math.sqrt(head_dim)
    pos = jnp.arange(t)
    keep = pos[None, :] <= pos[:, None]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    a = jnp.einsum("hqk,khd->qhd", _lower(probs, mode), _lower(v, mode),
                   precision="highest").reshape(t, heads * head_dim)
    return matmul(a, p["o"], mode)


@partial(jax.jit, static_argnames=("mode",))
def gated(x, gate, up, down, mode: str):
    """down(silu(gate x) * up x)."""
    return matmul(jax.nn.silu(matmul(x, gate, mode)) * matmul(x, up, mode),
                  down, mode)


@partial(jax.jit, static_argnames=("mode",))
def _one_expert(x, gate, up, down, e, mode: str):
    return gated(x, gate[e], up[e], down[e], mode)


@partial(jax.jit, static_argnames=("top_k", "scale", "normalise", "mode"))
def route(x, router, bias, *, top_k: int, scale: float, normalise: bool,
          mode: str):
    """x [t, hidden] -> (weights [t, E]: w_i of the picked experts, 0
    elsewhere; the picked ids [t, k], best first; the (k+1)-th id [t];
    the margin [t] between the k-th and (k+1)-th selection score)."""
    score = jax.nn.sigmoid(matmul(x, router, mode))
    select = score + bias.astype(jnp.float32)
    top, ids = jax.lax.top_k(select, top_k + 1)
    picked = ids[:, :top_k]
    own = jnp.take_along_axis(score, picked, axis=-1)
    if normalise:
        own = own / own.sum(-1, keepdims=True)
    weights = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], picked].set(own * scale)
    return weights, picked, ids[:, top_k], top[:, top_k - 1] - top[:, top_k]


def held_of(config: Dict, experts_held=None) -> Tuple[int, int]:
    held = experts_held if experts_held is not None \
        else config.get("experts_held")
    return tuple(held) if held is not None else (0, config["num_experts"])


def expert_layer(x, p: Dict, config: Dict, *, experts_held=None,
                 shared: bool = True, mode: str = "f32"):
    """A sparse layer's FFN over x [t, hidden] (already normalised):
    (y [t, hidden], margin [t]).  `experts_held` (first id, count) is
    the share computed (default: the configuration's, else all);
    `shared=False` leaves the shared expert out, so that the shares of
    a layer can be added with it counted once."""
    first, count = held_of(config, experts_held)
    weights, picked, runner_up, margin = route(
        x, p["router"]["kernel"], p["bias"],
        top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]),
        normalise=bool(config["norm_topk_prob"]), mode=mode)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(count):              # an expert at a time
        out = _one_expert(x, p["experts_gate"]["kernel"],
                          p["experts_up"]["kernel"],
                          p["experts_down"]["kernel"], e, mode)
        y = y + weights[:, first + e, None] * out
    if shared:
        s = p["shared"]
        y = y + gated(x, s["gate"]["kernel"], s["up"]["kernel"],
                      s["down"]["kernel"], mode)

    def is_held(ids):
        return (ids >= first) & (ids < first + count)
    at_stake = is_held(picked[:, -1]) | is_held(runner_up)
    return y, jnp.where(at_stake, margin, jnp.inf)


def _layer(params: Dict, i: int) -> Dict:
    pre = f"block_{i}_"
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def forward(params: Dict, tokens, config: Dict, *, mode: str = "f32",
            experts_held=None, rows: Optional[slice] = None):
    """tokens [t] -> (logits [rows, vocab], margin [t]): position i
    holds the scores of the token that follows tokens[:i + 1]; causal,
    so padding after the last real token changes nothing before it.
    `rows` picks the positions whose logits are wanted (all by
    default).  `margin` is described at the top of the file."""
    eps = float(config["rms_norm_eps"])
    x = params["token_embed"]["embedding"][tokens].astype(jnp.float32)
    margin = jnp.full((x.shape[0],), jnp.inf)
    kinds = zip(config["layer_types"], config["mlp_layer_types"])
    for i, (attn_kind, ffn_kind) in enumerate(kinds):
        p = _layer(params, i)
        x = x + attention(
            rms_norm(x, p["attn_norm"]["scale"], eps),
            {"q": p["q"]["kernel"], "k": p["k"]["kernel"],
             "v": p["v"]["kernel"], "o": p["o"]["kernel"],
             "q_norm": p["q_norm"]["scale"],
             "k_norm": p["k_norm"]["scale"]},
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            window=(int(config["sliding_window"])
                    if attn_kind == SLIDING else None),
            theta=float(config["rope_parameters"]["rope_theta"]),
            eps=eps, mode=mode)
        f_in = rms_norm(x, p["ffn_norm"]["scale"], eps)
        if ffn_kind == SPARSE:
            f, m = expert_layer(f_in, p["moe"], config,
                                experts_held=experts_held, mode=mode)
            margin = jnp.minimum(margin, m)
        else:
            mlp = p["mlp"]
            f = gated(f_in, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                      mlp["down"]["kernel"], mode)
        x = x + f
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    if rows is not None:
        x = x[rows]
    return matmul(x, params["lm_head"]["kernel"], mode), margin
