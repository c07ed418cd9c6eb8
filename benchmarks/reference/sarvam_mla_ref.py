"""The plain reference of `sarvam_105b_ep8_serve`: sarvam-105b's decoder
(`model_type` `sarvam_mla`: DeepSeek-V2's latent attention in front of
sigmoid-routed experts) in `jax.numpy`, float32, matmuls at `highest`
precision; no kernel, no cache, no batching, nothing imported from the
program.  It reads the configuration by its published keys.

With `h` the residual stream `[t, hidden]`, for layer `l`:

    h = h + Attn_l(RMSNorm(h));  h = h + FFN_l(RMSNorm(h))
    logits = RMSNorm(h) . W_head

`Attn_l`, the EXPANDED form only — every latent up-projected, a softmax
a head, which is how the mechanism is published; the program's decode
runs the absorbed form, so it is checked here against mathematics it
does not share.  With u = RMSNorm(h), H = `num_attention_heads`:

    q = W_q u -> [H, q_head_dim], RMSNorm over each head (learned
        scale), split q_nope [qk_nope_head_dim] | q_rope [qk_rope_head_dim]
    [c | k_r] = W_kva u -> kv_lora_rank | qk_rope_head_dim
    c = RMSNorm(c) (learned scale);  k_r, q_rope rotated at the token's
        position, ONE k_r for all heads
    [k_nope_h | v_h] = W_kvb,h c -> qk_nope_head_dim | v_head_dim
    s_h,ij = sigma * [q_nope_h,i | q_rope_h,i] . [k_nope_h,j | k_r,j]
    o_h,i = sum_{j <= i} softmax_j(s_h,ij) v_h,j;  out = W_o [o_1 .. o_H]

The rotation is rotate-half over the `qk_rope_head_dim` columns at
`deepseek_yarn`'s frequencies (`rope_scaling`): f_i = theta^(-2i/d);
ramp_i = clip((i - low) / (high - low), 0, 1) between the dimensions
that turn `beta_fast` and `beta_slow` times over
`original_max_position_embeddings`; inv_freq_i = f_i / factor * ramp_i
+ f_i * (1 - ramp_i); cos and sin times m(mscale) / m(mscale_all_dim),
and sigma = q_head_dim^(-1/2) * m(mscale_all_dim)^2 with m(s) = 0.1 s
ln(factor) + 1.

`FFN_l` of the first `first_k_dense_replace` layers: down(silu(gate x)
* up x) at `intermediate_size`.  Of every later layer: s =
sigmoid(W_r x) over all `num_experts`; T = the `num_experts_per_tok`
largest of s + b (b the expert bias); w_i = `routed_scaling_factor` *
s_i / sum_{j in T} s_j; y = sum_{i in T, i held} w_i E_i(x) +
E_shared(x), each E the gated-SiLU form at `moe_intermediate_size`.
`experts_held` = (first id, count) is the share of a chip of an
expert-parallel deployment: the sum runs over the picked experts it
holds, the shared expert is whole, and what the absent experts would
have added is left out (model-configs guide, section 4).

Assumed, because `config.json` does not settle them (the configuration
file lists them too): `use_qk_norm` read as the RMSNorm over the latent
and the one over each query head; sigmoid scores with normalised top-k
weights and a plain top-k; rotate-half pairing; the pre-norm residual
order.

Computed a layer, a projection and an expert at a time, each weight
turned to float32 as it is used, attention in blocks of `Q_BLOCK` query
rows, so that 5,120 positions fit beside the bfloat16 weights on the
chip.  `mode` is what the control lowers: "f32" (the reference) or
"fp8" — the CACHED LATENT ROW `[c | k_r]` rounded to e4m3 (a scale a
token, as a pool one precision below the configuration's bfloat16 would
hold it) before anything is expanded from it; every product stays
float32.

Routing near-ties: as `exaone_moe_ref` — `forward` also returns, per
position, the reference's own smallest margin between the k-th and the
(k+1)-th selection score over the sparse layers in which either of the
two is a held expert (`inf` where none is)."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8")
#: query rows a block of the attention holds
Q_BLOCK = 512


def matmul(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision="highest")


def lower_rows(x, mode: str):
    """The cached rows `x` [t, width] as a pool of `mode` holds them."""
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30)
    return round_e4m3(x * s) / s


def round_e4m3(x):
    """`x` (|x| <= 448) rounded to the nearest e4m3 value, ties to
    even, by arithmetic: four significant bits down to 2**-6, steps of
    2**-9 below.  Not `x.astype(float8_e4m3fn).astype(float32)`, which
    it equals: the chip's compiler takes a conversion down and straight
    back up for excess precision it may keep (chip readings, PR 37: the
    control's rows came back 8e-7 from where they went and its
    served-token gap read 0.0 at 18,736 positions on 4 seeds)."""
    _, e = jnp.frexp(x)
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(jnp.float32))
    return jnp.round(x / step) * step


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


# --- deepseek_yarn ----------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(d: int, theta: float, scaling: Dict) -> Tuple[int, int]:
    """(low, high), the dimensions the ramp runs between."""
    def dim(turns):
        return (d * math.log(scaling["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(scaling["beta_fast"])), 0),
            min(math.ceil(dim(scaling["beta_slow"])), d - 1))


def yarn_inv_freq(d: int, theta: float, scaling: Optional[Dict]):
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    if not scaling:
        return f
    low, high = yarn_range(d, theta, scaling)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def attention_constants(config: Dict) -> Tuple[float, float]:
    """(the factor on cos and sin, the softmax scale sigma)."""
    scaling = config.get("rope_scaling") or {}
    factor = scaling.get("factor", 1.0)
    all_dim = yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0))
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (yarn_mscale(factor, scaling.get("mscale", 1.0)) / all_dim,
            d ** -0.5 * all_dim ** 2)


def rotary(x, inv_freq, factor: float):
    """x [t, heads, d] at positions 0..t-1, rotate-half: pair
    (i, i + d/2) turns by position * inv_freq[i]."""
    t, _, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None] * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# --- latent attention, expanded ---------------------------------------

@partial(jax.jit, static_argnames=("heads", "nope", "rope", "eps",
                                   "factor"))
def queries(x, p: Dict, inv_freq, *, heads: int, nope: int, rope: int,
            eps: float, factor: float):
    """x [t, hidden] (normalised) -> q [t, heads, nope + rope], the
    rotary part rotated."""
    t = x.shape[0]
    q = rms_norm(matmul(x, p["q"]).reshape(t, heads, nope + rope),
                 p["q_norm"], eps)
    return jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], inv_freq, factor)], -1)


@partial(jax.jit, static_argnames=("rank", "eps", "factor", "mode"))
def cached_rows(x, p: Dict, inv_freq, *, rank: int, eps: float,
                factor: float, mode: str):
    """x [t, hidden] (normalised) -> the rows a cache holds, [c | k_r]
    [t, rank + rope]."""
    row = matmul(x, p["kv_a"])
    c = rms_norm(row[:, :rank], p["kv_a_norm"], eps)
    k_r = rotary(row[:, None, rank:], inv_freq, factor)[:, 0]
    return lower_rows(jnp.concatenate([c, k_r], -1), mode)


@partial(jax.jit, static_argnames=("heads", "nope", "v_dim", "rank"))
def expand(rows, w_b, *, heads: int, nope: int, v_dim: int, rank: int):
    """Cached rows [t, rank + rope] -> keys [t, heads, nope + rope],
    values [t, heads, v_dim]: every latent up-projected, the one rotary
    key repeated to every head."""
    t = rows.shape[0]
    kv = matmul(rows[:, :rank], w_b).reshape(t, heads, nope + v_dim)
    k_r = jnp.broadcast_to(rows[:, None, rank:],
                           (t, heads, rows.shape[1] - rank))
    return jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]


@partial(jax.jit, static_argnames=("sigma",))
def _attend_block(q, k, v, start, *, sigma: float):
    """Query rows q [n, heads, d] at positions start.. over keys and
    values of every position; causal."""
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * sigma
    keep = jnp.arange(k.shape[0])[None, :] \
        <= (start + jnp.arange(q.shape[0]))[:, None]
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")


def attention(x, p: Dict, config: Dict, mode: str = "f32"):
    """x [t, hidden] (already normalised) -> ([t, hidden], the rows a
    cache of `mode` holds for it [t, rank + rope]).  p: q, kv_a, kv_b,
    o kernels, q_norm and kv_a_norm scales."""
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), \
        int(config["qk_rope_head_dim"])
    rank, v_dim = int(config["kv_lora_rank"]), int(config["v_head_dim"])
    eps = float(config["rms_norm_eps"])
    factor, sigma = attention_constants(config)
    inv_freq = yarn_inv_freq(rope, float(config["rope_theta"]),
                             config.get("rope_scaling"))
    q = queries(x, p, inv_freq, heads=heads, nope=nope, rope=rope,
                eps=eps, factor=factor)
    rows = cached_rows(x, p, inv_freq, rank=rank, eps=eps, factor=factor,
                       mode=mode)
    k, v = expand(rows, p["kv_b"], heads=heads, nope=nope, v_dim=v_dim,
                  rank=rank)
    t = x.shape[0]
    out = [_attend_block(q[s:s + Q_BLOCK], k, v, s, sigma=sigma)
           for s in range(0, t, Q_BLOCK)]
    a = jnp.concatenate(out, 0) if len(out) > 1 else out[0]
    return matmul(a.reshape(t, heads * v_dim), p["o"]), rows


# --- the FFNs ---------------------------------------------------------

@jax.jit
def gated(x, gate, up, down):
    """down(silu(gate x) * up x)."""
    return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)


@jax.jit
def _one_expert(x, gate, up, down, e):
    return gated(x, gate[e], up[e], down[e])


@partial(jax.jit, static_argnames=("top_k", "scale"))
def route(x, router, bias, *, top_k: int, scale: float):
    """x [t, hidden] -> (weights [t, E]: w_i of the picked experts, 0
    elsewhere; the picked ids [t, k], best first; the (k+1)-th id [t];
    the margin [t] between the k-th and (k+1)-th selection score)."""
    score = jax.nn.sigmoid(matmul(x, router))
    select = score + bias.astype(jnp.float32)
    top, ids = jax.lax.top_k(select, top_k + 1)
    picked = ids[:, :top_k]
    own = jnp.take_along_axis(score, picked, axis=-1)
    own = own / own.sum(-1, keepdims=True)
    weights = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], picked].set(own * scale)
    return weights, picked, ids[:, top_k], top[:, top_k - 1] - top[:, top_k]


def held_of(config: Dict, experts_held=None) -> Tuple[int, int]:
    held = experts_held if experts_held is not None \
        else config.get("experts_held")
    return tuple(held) if held is not None else (0, config["num_experts"])


def expert_layer(x, p: Dict, config: Dict, *, experts_held=None,
                 shared: bool = True):
    """A sparse layer's FFN over x [t, hidden] (already normalised):
    (y [t, hidden], margin [t]).  `experts_held` (first id, count) is
    the share computed (default: the configuration's, else all);
    `shared=False` leaves the shared expert out, so that the shares of
    a layer can be added with it counted once."""
    first, count = held_of(config, experts_held)
    weights, picked, runner_up, margin = route(
        x, p["router"]["kernel"], p["bias"],
        top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]))
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(count):              # an expert at a time
        out = _one_expert(x, p["experts_gate"]["kernel"],
                          p["experts_up"]["kernel"],
                          p["experts_down"]["kernel"], e)
        y = y + weights[:, first + e, None] * out
    if shared:
        s = p["shared"]
        y = y + gated(x, s["gate"]["kernel"], s["up"]["kernel"],
                      s["down"]["kernel"])

    def is_held(ids):
        return (ids >= first) & (ids < first + count)
    at_stake = is_held(picked[:, -1]) | is_held(runner_up)
    return y, jnp.where(at_stake, margin, jnp.inf)


# --- the model --------------------------------------------------------

def _layer(params: Dict, i: int) -> Dict:
    pre = f"block_{i}_"
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def forward(params: Dict, tokens, config: Dict, *, mode: str = "f32",
            experts_held=None, rows: Optional[slice] = None):
    """tokens [t] -> (logits [rows, vocab], margin [t], cached): position
    i holds the scores of the token that follows tokens[:i + 1]; causal,
    so padding after the last real token changes nothing before it.
    `rows` picks the positions whose logits are wanted (all by
    default).  `margin` is described at the top of the file.  `cached`
    is one [t, rank + rope] array a layer: the rows a cache of `mode`
    holds."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, config, mode, experts_held, rows)


def _forward(params, tokens, config, mode, experts_held, rows):
    eps = float(config["rms_norm_eps"])
    x = params["token_embed"]["embedding"][tokens].astype(jnp.float32)
    margin = jnp.full((x.shape[0],), jnp.inf)
    dense_layers = int(config.get("first_k_dense_replace", 0))
    cached = []
    for i in range(int(config["num_hidden_layers"])):
        p = _layer(params, i)
        a, held = attention(
            rms_norm(x, p["attn_norm"]["scale"], eps),
            {"q": p["q"]["kernel"], "kv_a": p["kv_a"]["kernel"],
             "kv_b": p["kv_b"]["kernel"], "o": p["o"]["kernel"],
             "q_norm": p["q_norm"]["scale"],
             "kv_a_norm": p["kv_a_norm"]["scale"]}, config, mode)
        cached.append(held)
        x = x + a
        f_in = rms_norm(x, p["ffn_norm"]["scale"], eps)
        if i >= dense_layers:
            f, m = expert_layer(f_in, p["moe"], config,
                                experts_held=experts_held)
            margin = jnp.minimum(margin, m)
        else:
            mlp = p["mlp"]
            f = gated(f_in, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                      mlp["down"]["kernel"])
        x = x + f
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    if rows is not None:
        x = x[rows]
    return matmul(x, params["lm_head"]["kernel"]), margin, cached
