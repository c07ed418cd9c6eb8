"""The plain reference of both configurations: one post-LN transformer
block in `jax.numpy`, float32, matmuls at `highest` precision; no
kernel, no cache, no batching trick, nothing imported from the program.

Both models of the benchmark are stacks of the same block (attention,
residual, LayerNorm, GELU feed-forward, residual, LayerNorm), so one
block with a `causal` switch serves both:

  * `decoder_logits`  — the served decoder (token + position embedding,
    LayerNorm, causal blocks, an output head of its own);
  * `classifier_loss` — the fine-tuned encoder (token + position +
    segment embedding, LayerNorm, blocks, tanh pooler over the first
    token, classifier, mean softmax cross-entropy), with
    `train_steps`, plain Adam over its gradients.

Departures from the published models, shared with the program and
stated in the configuration files: LayerNorm epsilon 1e-6, GELU by its
tanh approximation, post-LN blocks and an untied head in the decoder.

It takes the parameter tree the benchmark made, by the names the
program's modules give their parameters (names are an interface, the
values are the benchmark's).  `mode` is the precision of the matmul
operands and is what the controls lower: "f32" (the reference), "bf16",
"fp8" (e4m3, per-tensor scale) or "int8" (per-tensor scale); products
always accumulate in float32."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EPS = 1e-6
MODES = ("f32", "bf16", "fp8", "int8")


def _lower(x, mode: str):
    """`x` as the matmul of `mode` sees it, straight-through for the
    gradient (the backward pass sees the lowered forward values)."""
    if mode == "f32":
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        if mode == "fp8":
            s = 448.0 / top
            q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
        elif mode == "int8":
            s = 127.0 / top
            q = jnp.round(x * s) / s
        else:
            raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    return x + jax.lax.stop_gradient(q - x)


def dense(x, w, b, mode: str):
    return jnp.matmul(_lower(x, mode), _lower(w, mode),
                      precision="highest") + b


def layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head: int, causal: bool, mode: str):
    """x [b, t, d]; p: qkv/proj/ln1/fc1/fc2/ln2, each kernel+bias or
    scale+bias."""
    b, t, d = x.shape
    hd = d // n_head
    qkv = dense(x, p["qkv"]["kernel"], p["qkv"]["bias"], mode)
    q, k, v = (a.reshape(b, t, n_head, hd) for a in jnp.split(qkv, 3, -1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", _lower(q, mode), _lower(k, mode),
                        precision="highest") / math.sqrt(hd)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", _lower(probs, mode), _lower(v, mode),
                   precision="highest").reshape(b, t, d)
    a = dense(a, p["proj"]["kernel"], p["proj"]["bias"], mode)
    x = layer_norm(x + a, p["ln1"]["scale"], p["ln1"]["bias"])
    f = gelu(dense(x, p["fc1"]["kernel"], p["fc1"]["bias"], mode))
    f = dense(f, p["fc2"]["kernel"], p["fc2"]["bias"], mode)
    return layer_norm(x + f, p["ln2"]["scale"], p["ln2"]["bias"])


# --- the served decoder -----------------------------------------------

def _decoder_layer(params, i: int):
    return {k: params[f"block_{i}_{k}"]
            for k in ("qkv", "proj", "ln1", "fc1", "fc2", "ln2")}


@partial(jax.jit, static_argnames=("n_head", "n_block", "mode"))
def decoder_logits(params, tokens, *, n_head: int, n_block: int,
                   mode: str = "f32"):
    """tokens [t] -> logits [t, vocab]: position i holds the scores of
    the token that follows tokens[:i + 1].  Causal, so padding after
    the last real token changes nothing before it."""
    t = tokens.shape[0]
    x = params["token_embed"]["embedding"][tokens] \
        + params["position_embed"]["embedding"][jnp.arange(t)]
    x = layer_norm(x, params["embed_ln"]["scale"],
                   params["embed_ln"]["bias"])[None]
    for i in range(n_block):
        x = block(x, _decoder_layer(params, i), n_head, True, mode)
    return dense(x[0], params["lm_head"]["kernel"],
                 params["lm_head"]["bias"], mode)


# --- the fine-tuned encoder -------------------------------------------

def _encoder_layer(blocks, i: int):
    pick = lambda tree: jax.tree_util.tree_map(lambda a: a[i], tree)
    return {"qkv": pick(blocks["attn"]["qkv"]),
            "proj": pick(blocks["attn"]["proj"]),
            **{k: pick(blocks[k]) for k in ("ln1", "fc1", "fc2", "ln2")}}


def classifier_loss(params, ids, seg, labels, *, n_head: int,
                    mode: str = "f32"):
    """Mean softmax cross-entropy of the classifier over rows
    ids/seg [b, t] (every position real), labels [b]."""
    enc = params["bert"]
    t = ids.shape[1]
    x = enc["token_embed"]["embedding"][ids] \
        + enc["position_embed"]["embedding"][jnp.arange(t)][None] \
        + enc["segment_embed"]["embedding"][seg]
    x = layer_norm(x, enc["embed_ln"]["scale"], enc["embed_ln"]["bias"])
    n_block = enc["blocks"]["ln1"]["scale"].shape[0]
    for i in range(n_block):
        x = block(x, _encoder_layer(enc["blocks"], i), n_head, False, mode)
    pooled = jnp.tanh(dense(x[:, 0], enc["pooler"]["kernel"],
                            enc["pooler"]["bias"], mode))
    logits = dense(pooled, params["classifier"]["kernel"],
                   params["classifier"]["bias"], mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


@partial(jax.jit, static_argnames=("n_head", "mode"))
def _loss_and_grad(params, ids, seg, labels, *, n_head, mode):
    return jax.value_and_grad(classifier_loss)(
        params, ids, seg, labels, n_head=n_head, mode=mode)


def batch_loss_and_grad(params, batch, *, n_head: int, mode: str = "f32",
                        microbatch: int = 32, rows=None):
    """Loss and gradient of the mean over the batch's rows, taken in
    blocks of `microbatch` rows so that float32 activations fit beside
    nothing else.  `rows` (a slice) restricts the mean to part of the
    batch — what a planted fault computes, never the reference."""
    ids, seg, _ = batch["x"]
    y = batch["y"]
    if rows is not None:
        ids, seg, y = ids[rows], seg[rows], y[rows]
    n = ids.shape[0]
    if n % microbatch:
        raise ValueError(f"{n} rows do not split into blocks of "
                         f"{microbatch}")
    loss, grad = 0.0, None
    for lo in range(0, n, microbatch):
        sl = slice(lo, lo + microbatch)
        l, g = _loss_and_grad(params, jnp.asarray(ids[sl]),
                              jnp.asarray(seg[sl]), jnp.asarray(y[sl]),
                              n_head=n_head, mode=mode)
        w = microbatch / n
        loss = loss + w * l
        grad = (jax.tree_util.tree_map(lambda a: w * a, g) if grad is None
                else jax.tree_util.tree_map(lambda s, a: s + w * a, grad, g))
    return loss, grad


@jax.jit
def _adam(params, grad, mu, nu, step, lr, b1, b2, eps):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grad)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grad)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def train_steps(params, batches, *, n_head: int, lr: float, b1: float,
                b2: float, eps: float, mode: str = "f32",
                microbatch: int = 32, rows=None):
    """Plain Adam over `batches`, one step each.  Returns (losses, the
    first step's gradient, the parameters after the last step)."""
    params = jax.tree_util.tree_map(jnp.asarray, params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses, first = [], None
    for step, batch in enumerate(batches, 1):
        loss, grad = batch_loss_and_grad(
            params, batch, n_head=n_head, mode=mode, microbatch=microbatch,
            rows=rows)
        if first is None:
            first = grad
        losses.append(float(loss))
        params, mu, nu = _adam(params, grad, mu, nu, jnp.float32(step),
                               lr, b1, b2, eps)
    return losses, first, params
