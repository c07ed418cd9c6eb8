"""The plain reference of `lfm2_24b_a2b_serve`: the `lfm2_moe` decoder
(LFM2-24B-A2B: gated short-convolution layers, grouped-query attention
layers, SwiGLU routed experts) in `jax.numpy`, float32, matmuls at
`highest` precision; no kernel, no cache, no batching, nothing imported
from the program.  It reads the configuration by its published keys.

With `h` the residual stream `[t, hidden]`, layer `i` of kind
`layer_types[i]` is two pre-norm residual sub-layers:

    h = h + Op_i(RMSNorm_op,i(h));   h = h + FFN_i(RMSNorm_ffn,i(h))
    logits = RMSNorm_f(h) . W_head

`conv`, the gated short convolution.  `[B | C | x] = u W_in` (three
parts of `hidden_size`, in that order), `v = B * x`, `z_t = sum_{j <
L} w_j * v_{t - L + 1 + j}` over `conv_L_cache` = L positions (a causal
depthwise convolution, w_{L-1} on the current position, zeros before
the first), `out = (C * z) W_out`.  No bias (`conv_bias` false).  The
tail a lane carries is the last L - 1 rows of `v`.

`full_attention`: q, k, v projections (no bias) to
`num_attention_heads` / `num_key_value_heads` heads of
`hidden_size / num_attention_heads`; an RMSNorm over each head of q and
of k (learned scale a layer); rotary positions over the whole head, the
half-split pairing (i, i + d/2) turning by position * theta ** (-2i /
d), theta = `rope_parameters.rope_theta`; query head i reads KV head i
// (q heads / KV heads); causal softmax in float32 at scale head_dim **
-0.5; output projection.

FFN: below `num_dense_layers`, `w2(silu(w1 u) * w3 u)` at
`intermediate_size`.  After them, experts: s = sigmoid(u W_r) over all
`num_experts`; T = the `num_experts_per_tok` largest of s + b (b the
expert bias, `use_expert_bias`); w_i = `routed_scaling_factor` * s_i /
(sum_{j in T} s_j + 1e-6); expert e: f_e(u) = (silu(u G_e) * (u U_e))
D_e at `moe_intermediate_size`; y = sum_{i in T, i held} w_i f_i(u).
No shared expert.  `experts_held` = (first id, count), where the
configuration gives one, is a chip's share: the sum runs over the
picked experts it holds and what the others would add is left out
(model-configs guide, section 4); the `lfm2_24b_a2b_serve` cell holds
all 64.

Departures from the published code, none of which moves a logit by
more than float32 rounding: the program normalises the picked scores
without the published 1e-6 (a relative 5e-7 of the weights), which
this reference keeps; the published code zeroes the inputs of padded
positions before the convolution, which a causal convolution over the
real positions never reads.  Assumed, because `config.json` does not
settle them (the configuration's file lists them with their reasons):
an untied head, the final norm after the last layer.

Computed a layer, a projection and an expert at a time, each weight
turned to float32 as it is used, so that the whole fits beside 10.6 GB
of bfloat16 weights on the chip.  `mode` is what a control lowers:
"f32" (the reference) or "fp8" (e4m3 matmul operands, per-tensor
scale, products summed in float32).

Routing near-ties: `forward` also returns, per position, the
reference's own smallest margin between the k-th and the (k+1)-th
selection score over the expert layers in which either of the two is a
held expert (`inf` where none is), as `exaone_moe_ref` does; the caller
leaves positions under its epsilon out of the logit comparison and
compares their share instead.  And each `conv` layer's tail after
`length` tokens, for the comparison with the tail the program left in
the lane's slot."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8")
CONV, ATTENTION = "conv", "full_attention"


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


@partial(jax.jit, static_argnames=("mode",))
def short_conv(u, p: Dict, length, *, mode: str):
    """u [t, hidden] (already normalised) -> (out [t, hidden], the tail
    after `length` positions: the L - 1 rows of v before position
    `length`, zeros where the sequence is shorter [L - 1, hidden])."""
    t, d = u.shape
    B, C, x = jnp.split(matmul(u, p["in_proj"], mode), 3, axis=-1)
    v = B * x
    w = p["conv_kernel"].astype(jnp.float32)            # [L, hidden]
    L = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((L - 1, d), jnp.float32), v])
    z = sum(padded[j:j + t] * w[j] for j in range(L))
    at = length - (L - 1) + jnp.arange(L - 1)
    tail = jnp.where((at >= 0)[:, None], v[jnp.clip(at, 0, t - 1)], 0.0)
    return matmul(C * z, p["out_proj"], mode), tail


def rotate(x, theta: float):
    """Rotary positions 0.. over the whole head of x [t, heads, d], the
    half-split pairing."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv   # [t, d/2]
    cos = jnp.cos(angle)[:, None]
    sin = jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps",
                                   "mode"))
def attention(u, p: Dict, *, heads: int, kv_heads: int, theta: float,
              eps: float, mode: str):
    """u [t, hidden] (already normalised) -> [t, hidden]."""
    t, d = u.shape
    hd = d // heads
    q = matmul(u, p["q"], mode).reshape(t, heads, hd)
    k = matmul(u, p["k"], mode).reshape(t, kv_heads, hd)
    v = matmul(u, p["v"], mode).reshape(t, kv_heads, hd)
    q = rotate(rms_norm(q, p["q_norm"], eps), theta)
    k = rotate(rms_norm(k, p["k_norm"], eps), theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _lower(q, mode), _lower(k, mode),
                        precision="highest") / math.sqrt(hd)
    pos = jnp.arange(t)
    keep = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    a = jnp.einsum("hqk,khd->qhd", _lower(probs, mode), _lower(v, mode),
                   precision="highest").reshape(t, heads * hd)
    return matmul(a, p["o"], mode)


@partial(jax.jit, static_argnames=("mode",))
def swiglu(x, gate, up, down, mode: str):
    """(silu(x gate) * (x up)) down."""
    return matmul(jax.nn.silu(matmul(x, gate, mode)) * matmul(x, up, mode),
                  down, mode)


@partial(jax.jit, static_argnames=("mode",))
def _one_expert(x, gate, up, down, e, mode: str):
    return swiglu(x, gate[e], up[e], down[e], mode)


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
        own = own / (own.sum(-1, keepdims=True) + 1e-6)
    weights = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], picked].set(own * scale)
    return weights, picked, ids[:, top_k], top[:, top_k - 1] - top[:, top_k]


def held_of(config: Dict, experts_held=None) -> Tuple[int, int]:
    held = experts_held if experts_held is not None \
        else config.get("experts_held")
    return tuple(held) if held is not None else (0, config["num_experts"])


def expert_layer(x, p: Dict, config: Dict, *, experts_held=None,
                 mode: str = "f32"):
    """An expert FFN over x [t, hidden] (already normalised): (y [t,
    hidden], margin [t]).  `experts_held` (first id, count) is the
    share computed (default: the configuration's, else all)."""
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

    def is_held(ids):
        return (ids >= first) & (ids < first + count)
    at_stake = is_held(picked[:, -1]) | is_held(runner_up)
    return y, jnp.where(at_stake, margin, jnp.inf)


def _block(params: Dict, i: int) -> Dict:
    """The program's leaves of sub-layer `i` (layer i // 2's operator
    for even i, its FFN for odd), by their names without the prefix."""
    pre = f"block_{i}_"
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def forward(params: Dict, tokens, config: Dict, *, mode: str = "f32",
            experts_held=None, rows: Optional[slice] = None,
            length: Optional[int] = None):
    """tokens [t] -> (logits [rows, vocab], margin [t], tails): position
    i holds the scores of the token that follows tokens[:i + 1]; causal,
    so padding after the last real token changes nothing before it.
    `rows` picks the positions whose logits are wanted (all by
    default).  `margin` is described at the top of the file.  `tails`:
    each `conv` layer's last L - 1 rows of v before position `length`
    (all of the tokens by default), [L - 1, hidden]."""
    eps = float(config["norm_eps"])
    rope = config.get("rope_parameters") or {
        "rope_theta": config["rope_theta"]}
    x = params["token_embed"]["embedding"][tokens].astype(jnp.float32)
    t = x.shape[0]
    margin = jnp.full((t,), jnp.inf)
    length = jnp.int32(t if length is None else length)
    tails = []
    for i, kind in enumerate(config["layer_types"]):
        op, ffn = _block(params, 2 * i), _block(params, 2 * i + 1)
        u = rms_norm(x, op["norm"]["scale"], eps)
        if kind == CONV:
            c = op["conv"]
            out, tail = short_conv(
                u, {"in_proj": c["in_proj"]["kernel"],
                    "out_proj": c["out_proj"]["kernel"],
                    "conv_kernel": c["conv_kernel"]}, length, mode=mode)
            tails.append(tail)
        elif kind == ATTENTION:
            out = attention(
                u, {"q": op["q"]["kernel"], "k": op["k"]["kernel"],
                    "v": op["v"]["kernel"], "o": op["o"]["kernel"],
                    "q_norm": op["q_norm"]["scale"],
                    "k_norm": op["k_norm"]["scale"]},
                heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                theta=float(rope["rope_theta"]), eps=eps, mode=mode)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        x = x + out
        u = rms_norm(x, ffn["norm"]["scale"], eps)
        if i < int(config["num_dense_layers"]):
            f = ffn["ffn"]
            out = swiglu(u, f["gate"]["kernel"], f["up"]["kernel"],
                         f["down"]["kernel"], mode)
        else:
            out, m = expert_layer(u, ffn["moe"], config,
                                  experts_held=experts_held, mode=mode)
            margin = jnp.minimum(margin, m)
        x = x + out
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    if rows is not None:
        x = x[rows]
    head = params["lm_head"]["kernel"]
    logits = (matmul(x, head, mode) if x.shape[0]       # (no rows: the
              else jnp.zeros((0, head.shape[1])))       # tails alone)
    return logits, margin, tails
