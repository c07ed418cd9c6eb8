"""The plain reference of `ouro_2p6b_serve`: Ouro's looped decoder
(`model_type` `ouro`, ByteDance's LoopLM) in `jax.numpy`, float32,
matmuls at `highest` precision; no kernel, no cache, no batching, no
scan: a Python loop over the steps and, inside it, over the layers.
Nothing is imported from the program.  It reads the configuration by
its published keys.

With x the residual stream [t, hidden], L = `num_hidden_layers`,
T = `total_ut_steps`, and the SAME weights of layer l at every step:

    x = E[tokens]
    for s in 0..T-1:
      for l in 0..L-1:
        a = RMSNorm_in_l(x)
        q, k, v = a W_q,l, a W_k,l, a W_v,l      (heads of head_dim)
        q, k = RoPE(q), RoPE(k)                   (rope_theta)
        o = softmax(q k^T / sqrt(head_dim) + causal) v
            -- over the keys and values of application (s, l): formed
               from step s's own hidden state, a cache's slot s*L + l
        x = x + RMSNorm_in2_l(o W_o,l)
        m = RMSNorm_post_l(x)
        x = x + RMSNorm_post2_l(W_down,l(silu(m W_gate,l) * (m W_up,l)))
      x = RMSNorm_final(x)
    logits = x W_head                             (untied)

`early_exit_threshold` 1: no token leaves the loop, the logits are
those of the last step, and the exit gate decides nothing, so it is
not computed.

Departures and readings, each listed under `assumed` in the
configuration file too: no biases on q, k, v, o (the config has no
`attention_bias`); rotate-half RoPE over the whole head; the final norm
between steps feeding the next one, as the published modeling code's
forward has it (the paper writes the loop without it); N(0, 0.02)
weights and unit norm scales, made by the benchmark from `--seed`.

The weights are the program's tree (`loop_<projection>` kernels and
`loop_<norm>` scales stacked over the L layers, `token_embed`,
`final_norm`, `lm_head`), read a layer at a time: ONE jitted layer
function, handed the stacked bfloat16 leaves and the layer's index,
turns that layer's weights to float32 inside, so the chip holds the
bfloat16 tree and one float32 layer.  `mode` is what the control
lowers: "f32" (the reference) or "fp8" — the CACHED ROWS, each key and
each value of every application, rounded to e4m3 (a scale a token a
row, as a pool one precision below the configuration's bfloat16 would
hold them) before attention reads them; every product stays float32."""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.sarvam_mla_ref import round_e4m3

MODES = ("f32", "fp8")
#: the stacked leaves of a layer, as the program names them
PROJECTIONS = ("q", "k", "v", "o", "gate", "up", "down")
NORMS = ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")


def matmul(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision="highest")


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def lower_rows(x, mode: str):
    """Keys or values `x` [t, heads, head_dim] as a pool of `mode` holds
    them: a token's row of heads * head_dim at one scale."""
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    amax = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True)
    s = 448.0 / jnp.maximum(amax, 1e-30)
    return round_e4m3(x * s) / s


def rotary(x, positions, theta: float):
    """Rotate-half over the whole head: x [t, heads, d]; pair (i, i +
    d/2) turns by position * theta ** (-2i / d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv      # [t, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer_weights(params: Dict, i) -> Dict:
    """Layer `i`'s weights out of the stacked leaves, in float32."""
    w = {n: params[f"loop_{n}"]["kernel"][i].astype(jnp.float32)
         for n in PROJECTIONS}
    w.update((n, params[f"loop_{n}"]["scale"][i].astype(jnp.float32))
             for n in NORMS)
    return w


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                   "theta", "eps", "mode"))
def layer(x, params, i, *, heads: int, kv_heads: int, head_dim: int,
          theta: float, eps: float, mode: str):
    """Application of layer `i` to x [t, hidden]: (x after it, the keys
    and values its cache slot holds [t, kv_heads, head_dim] each)."""
    w = _layer_weights(params, i)
    t = x.shape[0]
    pos = jnp.arange(t)
    a = rms_norm(x, w["attn_norm"], eps)
    q = matmul(a, w["q"]).reshape(t, heads, head_dim)
    k = matmul(a, w["k"]).reshape(t, kv_heads, head_dim)
    v = matmul(a, w["v"]).reshape(t, kv_heads, head_dim)
    q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    k, v = lower_rows(k, mode), lower_rows(v, mode)
    r = heads // kv_heads
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, r, axis=1),
                        precision="highest") / jnp.sqrt(float(head_dim))
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, r, axis=1),
                   precision="highest").reshape(t, heads * head_dim)
    x = x + rms_norm(matmul(o, w["o"]), w["attn_post_norm"], eps)
    m = rms_norm(x, w["ffn_norm"], eps)
    f = matmul(jax.nn.silu(matmul(m, w["gate"])) * matmul(m, w["up"]),
               w["down"])
    return x + rms_norm(f, w["ffn_post_norm"], eps), k, v


def forward(params: Dict, tokens, config: Dict, *, mode: str = "f32",
            rows: Optional[slice] = None
            ) -> Tuple[jnp.ndarray, List[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """tokens [t] -> (logits [rows, vocab], cached): position i holds the
    scores of the token that follows tokens[:i + 1]; causal, so padding
    after the last real token changes nothing before it.  `rows` picks
    the positions whose logits are wanted (all by default).  `cached`
    is one (keys, values) pair a cache slot, [t, kv_heads, head_dim]
    each, in slot order: application (step s, layer l) at s * L + l."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, config, mode, rows)


def _forward(params, tokens, config, mode, rows):
    heads = int(config["num_attention_heads"])
    kw = dict(heads=heads,
              kv_heads=int(config.get("num_key_value_heads") or heads),
              head_dim=int(config.get("head_dim")
                           or config["hidden_size"] // heads),
              theta=float(config["rope_theta"]),
              eps=float(config["rms_norm_eps"]), mode=mode)
    stacked = {k: v for k, v in params.items() if k.startswith("loop_")}
    x = params["token_embed"]["embedding"][tokens].astype(jnp.float32)
    cached = []
    for _ in range(int(config["total_ut_steps"])):
        for i in range(int(config["num_hidden_layers"])):
            x, k, v = layer(x, stacked, i, **kw)
            cached.append((k, v))
        x = rms_norm(x, params["final_norm"]["scale"], kw["eps"])
    if rows is not None:
        x = x[rows]
    return matmul(x, params["lm_head"]["kernel"]), cached
