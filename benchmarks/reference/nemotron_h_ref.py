"""The plain reference of `nemotron3_super_120b_ep4_serve`: the
`nemotron_h` decoder (Nemotron 3 Super: Mamba-2 state-space layers,
attention layers, latent routed experts) in `jax.numpy`, float32,
matmuls at `highest` precision; no kernel, no cache, no chunks, no
batching, nothing imported from the program.  It reads the
configuration by its published keys.

With `h` the residual stream `[t, hidden]`, every layer `i` of kind
`hybrid_override_pattern[i]` is ONE sub-layer:

    h = h + Mixer_i(RMSNorm_i(h));   logits = RMSNorm_f(h) . W_head

`M`, Mamba-2.  `[z | xBC | dt] = u W_in`, widths `d_inner` | `d_inner +
2 G S` | heads (`d_inner` = `mamba_num_heads` * `mamba_head_dim`, `G` =
`n_groups`, `S` = `ssm_state_size`).  `xBC = silu(conv(xBC) + b)`, a
causal depthwise convolution over `conv_kernel` positions; `xBC = [x |
B | C]`, x as [heads, head_dim], B and C as [G, S], heads/G heads
sharing a group.  `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`.
For head `h` of group `g`, state `H_h` [head_dim, S], AS THE PLAIN
RECURRENCE OVER TIME (a `lax.scan` over positions):

    H_h(t) = exp(dt_h(t) A_h) H_h(t-1) + dt_h(t) x_h(t) (x) B_g(t)
    y_h(t) = H_h(t) C_g(t) + D_h x_h(t)

`y = GroupRMSNorm(y * silu(z))` (over each of the G groups of d_inner/G
columns, learned scale, the gate before the norm), `out = y W_out`.

`*`, attention: q, k, v projections (no bias) to `num_attention_heads`
/ `num_key_value_heads` heads of `head_dim`, NO positional encoding and
no q/k norms, query head i reads KV head i // (q heads / KV heads),
causal softmax in float32 at scale head_dim ** -0.5, output projection.

`E`, latent experts: s = sigmoid(u W_r) over all `n_routed_experts`; T
= the `num_experts_per_tok` largest of s + b; w_i =
`routed_scaling_factor` * s_i / sum_{j in T} s_j; l = u W_li (hidden ->
`moe_latent_size`); expert e: f_e(l) = relu(l U_e)^2 D_e; y = (sum_{i
in T, i held} w_i f_i(l)) W_lo + relu(u V_up)^2 V_down (the shared
expert, `moe_shared_expert_intermediate_size` wide, at the hidden
size).  `experts_held` = (first id, count) is the share of a chip of an
expert-parallel deployment: the sum runs over the picked experts it
holds, the router, both latent projections and the shared expert are
whole, and what the absent experts would have added is left out
(model-configs guide, section 4).

Assumed, because `config.json` does not settle them (the
configuration's file lists them with their reasons): no positions in
the attention layers, the order of the two splits, dt not clipped, the
state in float32.  Left out: the multi-token-prediction module.

Computed a layer, a projection and an expert at a time, each weight
turned to float32 as it is used, so that the whole fits beside 9 GB of
bfloat16 weights on the chip.  `mode` is what a control lowers: "f32"
(the reference), "fp8" (e4m3 matmul operands, per-tensor scale,
products summed in float32) or "bf16_state" (everything float32, the
recurrent state rounded to bfloat16 after every step: a state pool one
precision below the one the configuration states).

Routing near-ties: `forward` also returns, per position, the
reference's own smallest margin between the k-th and the (k+1)-th
selection score over the `E` layers in which either of the two is a
held expert (`inf` where none is), as `exaone_moe_ref` does; the caller
leaves positions under its epsilon out of the logit comparison and
compares their share instead.  With `length` it also returns each `M`
layer's state after `length` tokens, for the comparison with the state
the program left in the lane's slot."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8", "bf16_state")
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def _lower(x, mode: str):
    """`x` as the matmul of `mode` sees it."""
    x = x.astype(jnp.float32)
    if mode in ("f32", "bf16_state"):
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "eps", "mode"))
def mamba(u, p: Dict, length, *, heads: int, head_dim: int, groups: int,
          state: int, eps: float, mode: str):
    """u [t, hidden] (already normalised) -> (out [t, hidden], the
    state after `length` positions [heads, head_dim, state])."""
    t = u.shape[0]
    inner = heads * head_dim
    zxd = matmul(u, p["in_proj"], mode)
    z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * groups * state], -1)
    w = p["conv_kernel"].astype(jnp.float32)            # [k, channels]
    k = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc])
    conv = sum(padded[j:j + t] * w[j] for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32))
    x, B, C = jnp.split(xbc, [inner, inner + groups * state], -1)
    x = x.reshape(t, heads, head_dim)
    rep = heads // groups
    B = jnp.repeat(B.reshape(t, groups, state), rep, axis=1)  # [t, H, S]
    C = jnp.repeat(C.reshape(t, groups, state), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    # a position at or past `length` does not advance the state: its
    # output is no position's that anyone reads (causal)
    dt = jnp.where((jnp.arange(t) < length)[:, None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)

    def step(H, now):
        x_t, B_t, C_t, dt_t = now
        H = (jnp.exp(dt_t * A)[:, None, None] * H
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if mode == "bf16_state":
            # (not a cast there and back, which XLA folds away on the
            # chip: the rounding as an operation of its own)
            H = jax.lax.reduce_precision(H, exponent_bits=8,
                                         mantissa_bits=7)
        y = jnp.einsum("hps,hs->hp", H, C_t, precision="highest") \
            + D[:, None] * x_t
        return H, y

    last, y = jax.lax.scan(
        step, jnp.zeros((heads, head_dim, state), jnp.float32),
        (x, B, C, dt))
    y = y.reshape(t, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(t, groups, inner // groups),
                 p["norm_scale"].reshape(groups, inner // groups), eps)
    return matmul(y.reshape(t, inner), p["out_proj"], mode), last


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "mode"))
def attention(u, p: Dict, *, heads: int, kv_heads: int, head_dim: int,
              mode: str):
    """u [t, hidden] (already normalised) -> [t, hidden]."""
    t = u.shape[0]
    q = matmul(u, p["q"], mode).reshape(t, heads, head_dim)
    k = matmul(u, p["k"], mode).reshape(t, kv_heads, head_dim)
    v = matmul(u, p["v"], mode).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _lower(q, mode), _lower(k, mode),
                        precision="highest") / math.sqrt(head_dim)
    pos = jnp.arange(t)
    keep = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    a = jnp.einsum("hqk,khd->qhd", _lower(probs, mode), _lower(v, mode),
                   precision="highest").reshape(t, heads * head_dim)
    return matmul(a, p["o"], mode)


@partial(jax.jit, static_argnames=("mode",))
def squared_relu_mlp(x, up, down, mode: str):
    """relu(x up)^2 down."""
    return matmul(relu2(matmul(x, up, mode)), down, mode)


@partial(jax.jit, static_argnames=("mode",))
def _one_expert(x, up, down, e, mode: str):
    return squared_relu_mlp(x, up[e], down[e], mode)


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
    return tuple(held) if held is not None \
        else (0, config["n_routed_experts"])


def expert_layer(x, p: Dict, config: Dict, *, experts_held=None,
                 shared: bool = True, mode: str = "f32"):
    """An `E` layer over x [t, hidden] (already normalised): (y [t,
    hidden], margin [t]).  `experts_held` (first id, count) is the
    share computed (default: the configuration's, else all);
    `shared=False` leaves the shared expert out, so that the shares of
    a layer can be added with it counted once."""
    first, count = held_of(config, experts_held)
    weights, picked, runner_up, margin = route(
        x, p["router"]["kernel"], p["bias"],
        top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]),
        normalise=bool(config["norm_topk_prob"]), mode=mode)
    latent = matmul(x, p["latent_in"]["kernel"], mode)
    mixed = jnp.zeros(latent.shape, jnp.float32)
    for e in range(count):              # an expert at a time
        out = _one_expert(latent, p["experts_up"]["kernel"],
                          p["experts_down"]["kernel"], e, mode)
        mixed = mixed + weights[:, first + e, None] * out
    y = matmul(mixed, p["latent_out"]["kernel"], mode)
    if shared:
        s = p["shared"]
        y = y + squared_relu_mlp(x, s["up"]["kernel"], s["down"]["kernel"],
                                 mode)

    def is_held(ids):
        return (ids >= first) & (ids < first + count)
    at_stake = is_held(picked[:, -1]) | is_held(runner_up)
    return y, jnp.where(at_stake, margin, jnp.inf)


def _layer(params: Dict, i: int) -> Dict:
    pre = f"block_{i}_"
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def forward(params: Dict, tokens, config: Dict, *, mode: str = "f32",
            experts_held=None, rows: Optional[slice] = None,
            length: Optional[int] = None):
    """tokens [t] -> (logits [rows, vocab], margin [t], states): position
    i holds the scores of the token that follows tokens[:i + 1]; causal,
    so padding after the last real token changes nothing before it.
    `rows` picks the positions whose logits are wanted (all by
    default).  `margin` is described at the top of the file.  `states`:
    each `M` layer's state [heads, head_dim, state] after the first
    `length` tokens (after all of them by default)."""
    eps = float(config["norm_eps"])
    x = params["token_embed"]["embedding"][tokens].astype(jnp.float32)
    t = x.shape[0]
    margin = jnp.full((t,), jnp.inf)
    length = jnp.int32(t if length is None else length)
    states = []
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        p = _layer(params, i)
        u = rms_norm(x, p["norm"]["scale"], eps)
        if kind == MAMBA:
            m = p["mixer"]
            out, last = mamba(
                u, {"in_proj": m["in_proj"]["kernel"],
                    "out_proj": m["out_proj"]["kernel"],
                    **{k: m[k] for k in ("conv_kernel", "conv_bias",
                                         "dt_bias", "A_log", "D",
                                         "norm_scale")}},
                length, heads=int(config["mamba_num_heads"]),
                head_dim=int(config["mamba_head_dim"]),
                groups=int(config["n_groups"]),
                state=int(config["ssm_state_size"]), eps=eps, mode=mode)
            states.append(last)
        elif kind == ATTENTION:
            out = attention(
                u, {k: p[k]["kernel"] for k in "qkvo"},
                heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]), mode=mode)
        elif kind == EXPERTS:
            out, m = expert_layer(u, p["moe"], config,
                                  experts_held=experts_held, mode=mode)
            margin = jnp.minimum(margin, m)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        x = x + out
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    if rows is not None:
        x = x[rows]
    return matmul(x, params["lm_head"]["kernel"], mode), margin, states
