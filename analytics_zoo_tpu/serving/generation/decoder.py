"""A decoder described by configuration (ROADMAP D5) for the generation
engine: one block vocabulary, each layer's kinds read from lists.

  * norm: RMSNorm, before each sub-layer (pre-norm residual order) and
    once more before the head;
  * position: rotary (rotate-half over the whole head) on the layers
    whose attention kind says so, none elsewhere;
  * attention kind per layer, from `layer_types`: `sliding_attention`
    (a window of `sliding_window` positions, rotary) or
    `full_attention` (every earlier position, no rotary); grouped
    queries (`n_head` query heads over `n_kv_head` KV heads of
    `head_dim`, so heads * head_dim need not be the hidden size) with an
    RMSNorm over each head of q and k; or, in every layer of a model,
    `latent_attention` (below);
  * FFN kind per layer, from `mlp_layer_types`: `dense` (gated SiLU at
    `intermediate_size`) or `sparse` (`ExpertLayer`: a router over
    `num_experts`, the `experts_held` slice of them computed here, and
    a shared expert).

The call contract is `CausalLM`'s, so `steps.build_steps` drives it
unchanged: `input_ids, positions, token_mask | ctx_k/ctx_v/ctx_len |
kv_pool/block_tables/ctx_len` -> `logits, new_k, new_v`, the new keys
and values `[layers, batch, t, KV heads, head_dim]` as the pool stores
them (normalised and rotated).  Attention goes through `ops.attention`
in every mode.  What the engine has to know of the shapes it asks:
`kv_geometry()` (layers, KV heads, head dim of a pool row).  The
expert layers' counts are sown under `MOE_COUNTS` when the caller
makes that collection mutable, and `ExpertCounters` is the one reader
of their layout.

Latent attention (`kv_lora_rank` > 0; DeepSeek-V2's, as `sarvam_mla`
configures it).  A token caches ONE row a layer, `[c | k_r]`: a latent
`c` of `kv_lora_rank` columns (RMSNorm'd) that every head's key and
value are projections of, and one rotary key `k_r` of
`qk_rope_head_dim` columns (rotated) that all heads share.  Query head
h is `[q_nope | q_rope]` (`qk_nope_head_dim | qk_rope_head_dim`,
RMSNorm over the whole head, then the rotation of `q_rope`); with
`W_kvb,h = [W_UK,h ; W_UV,h]` the up-projection of head h,

    k_h,j = [W_UK,h c_j | k_r,j]     v_h,j = W_UV,h c_j
    s_h,ij = scale * q_h,i . k_h,j   o_h,i = sum_j softmax(s)_ij v_h,j

Two forms of that one mathematics, chosen by the call's shape:
EXPANDED where many new tokens meet the cache (a prompt, a chunk or a
verify window over cached rows, the concat oracle): every row in sight
is up-projected and `ops.attention.dot_product_attention` runs over
keys 192 wide beside values 128 wide, in blocks of query rows;
ABSORBED where one token a lane meets the pool (`decode`):
`q'_h = W_UK,h^T q_nope_h`, so `s_h,j = scale * [q'_h | q_rope_h] .
[c_j | k_r,j]` is one product with the cached row, `o'_h = sum_j p_j
c_j`, `o_h = W_UV,h o'_h` — `ops.attention.latent_decode_attention`
reads each cached row once for all heads and never expands it.  The
rotation's frequencies and the factor on `scale` are `deepseek_yarn`'s
(`yarn_frequencies`, `yarn_mscale`).  Such a model's `new_k` is the
cached rows `[layers, batch, t, 1, row]`, its `new_v` None, and a
gathered context comes back the same way (kv_cache.py's latent form).

The looped form (`total_ut_steps` T > 1; Ouro's LoopLM, as `ouro`
configures it).  The whole stack of L layers runs T times a token over
ONE set of weights, the residual stream carried from step to step and
the final norm closing every step (the next one starts from its
output, the head reads the last):

    for t in 0..T-1:  for l in 0..L-1:  x = layer_l(x, slot t * L + l)
                      x = RMSNorm_final(x)
    logits = x W_head

A layer met again at another step has keys and values of its own:
application (t, l) reads and writes pool slot `t * L + l`, so the pool
holds T * L slots (`kv_geometry()`), and every mode — a whole prompt,
a gathered context, the paged pool, a verify window — numbers them so.
The layers' weights are stacked, one leaf a projection with a leading
axis of L (`loop_q` `[L, hidden, heads * head_dim]`, ...), and the
forward is a `lax.scan` over layers inside a `lax.scan` over steps: a
compiled program holds ONE layer body whatever L and T, and the slot
is a traced int32 scalar, which `ops.attention` carries into the paged
kernel by scalar prefetch (no per-layer slice of the pool is ever an
operand).  Such a stack is of one kind — one attention kind, dense
FFNs — and its new keys and values come back in the dtype they were
computed in (bfloat16 as served: the pool's), not widened to float32.
Three switches of any layer, each off unless a configuration says
otherwise: rotary on `full_attention` layers too (`rotary_full`), no
RMSNorm over the heads of q and k (`qk_norm` False), and an RMSNorm on
each sub-layer's output before it joins the residual stream
(`sandwich_norm`: `x += RMSNorm(attn(RMSNorm(x)))`, the same round the
FFN).

Weights are held in `param_dtype` (bfloat16 as served: at 6144 wide a
float32 tree cast every step would be twice the chip) under leaves
named `kernel` / `embedding` / `scale` / `bias` only; a layer's held
experts are ONE stacked `kernel` `[held, in, out]` a projection, a
looped stack's layers one stacked `kernel` or `scale` a projection or
norm.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import grouped
from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    latent_decode_attention,
    latent_paged_context,
    paged_decode_attention,
    paged_verify_attention,
)
from analytics_zoo_tpu.ops.normalization import RMSNorm, rms_norm

SLIDING, FULL = "sliding_attention", "full_attention"
LATENT = "latent_attention"
DENSE, SPARSE = "dense", "sparse"
#: the flax collection an `ExpertLayer` sows its counts into
MOE_COUNTS = "moe_counts"


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """`deepseek_yarn`'s attention factor 0.1 * mscale * ln(factor) + 1
    (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(d: int, theta: float, scaling: Dict) -> np.ndarray:
    """The d/2 rotary frequencies of `deepseek_yarn` (`scaling`: the
    config's `rope_scaling`).  With f_i = theta ** (-2i / d): a
    dimension that turns more than `beta_fast` times over the original
    context keeps f_i, one that turns less than `beta_slow` times
    takes f_i / factor, and the ramp between the two dimensions
    (`yarn_correction_range`) mixes them."""
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    low, high = yarn_correction_range(d, theta, scaling)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f / scaling["factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def yarn_correction_range(d: int, theta: float, scaling: Dict
                          ) -> Tuple[int, int]:
    """(low, high): the dimensions between which `yarn_frequencies`
    ramps — where a dimension turns `beta_fast` and `beta_slow` times
    over `original_max_position_embeddings` positions."""
    def dim(turns):
        return d * math.log(scaling["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(dim(scaling["beta_fast"])), 0),
            min(math.ceil(dim(scaling["beta_slow"])), d - 1))


def rotary(x, positions, theta: float, inv_freq=None):
    """Rotate-half rotary embedding over the whole head: x [b, t, heads,
    d], positions [b, t].  Pair (i, i + d/2) turns by position *
    theta ** (-2i / d), or by position * `inv_freq`[i] where the
    frequencies are given (`yarn_frequencies`); float32 inside, x's
    dtype out."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * inv   # [b, t, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + turned * sin).astype(x.dtype)


def attend(q, k, v, *, layer: int, window=None, mask=None, ctx_k=None,
           ctx_v=None, ctx_len=None, kv_pool=None, kv_scale=None,
           block_tables=None, impl: str = "auto", compute_dtype=None):
    """Attention of q [b, t, heads, d] over the new k, v [b, t, KV
    heads, d] and whatever the call's mode says lies before them —
    `CausalLM`'s modes: the paged pool (t = 1: the decode kernel; t > 1:
    the verify form), a gathered context (`ctx_k`/`ctx_v` stacked over
    layers), or nothing (a whole prompt, causal under `mask`).
    `layer` is the pool's (and the gathered context's) row of this
    layer, `window` a sliding layer's reach."""
    if kv_pool is not None and q.shape[1] == 1:
        return paged_decode_attention(
            q[:, 0], k[:, 0], v[:, 0], kv_pool, block_tables, ctx_len,
            layer=layer, kv_scale=kv_scale, impl=impl,
            compute_dtype=compute_dtype, window=window)[:, None]
    if kv_pool is not None:
        return paged_verify_attention(
            q, k, v, kv_pool, block_tables, ctx_len, layer=layer,
            kv_scale=kv_scale, impl=impl, compute_dtype=compute_dtype,
            window=window)
    if ctx_k is not None:
        return dot_product_attention(
            q, k, v, compute_dtype=compute_dtype, ctx_k=ctx_k[layer],
            ctx_v=ctx_v[layer], ctx_len=ctx_len, window=window)
    return dot_product_attention(
        q, k, v, mask=mask, causal=True, compute_dtype=compute_dtype,
        window=window)


class GatedMLP(nn.Module):
    """down(silu(gate x) * up x), no biases."""
    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)
        h = nn.silu(proj(self.width, "gate")(x)) \
            * proj(self.width, "up")(x)
        return proj(x.shape[-1], "down")(h)


class SquaredReluMLP(nn.Module):
    """down(relu(up x) ** 2): not gated, no biases."""
    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)
        return proj(x.shape[-1], "down")(
            jnp.square(nn.relu(proj(self.width, "up")(x))))


class Kernel(nn.Module):
    """A bare weight under the leaf name every other one has."""
    shape: Tuple[int, ...]
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(0.02),
                          self.shape, self.param_dtype)


class Scales(nn.Module):
    """Norm scales of a looped stack's layers, stacked: [layers, width],
    ones, under the leaf name every norm's scale has."""
    shape: Tuple[int, ...]
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones_init(),
                          self.shape, self.param_dtype)


class ExpertLayer(nn.Module):
    """Routed experts, this chip's share of them, and the shared expert.

    The router scores ALL `num_experts` (sigmoid, float32), picks
    `top_k` by score + correction bias, and weighs the picked by their
    own scores, normalised and scaled.  Of the `top_k * tokens`
    assignments those that name one of the `experts_held` = (first id,
    count) experts are computed here — grouped by expert with a stable
    sort and multiplied through `ops.grouped.grouped_matmul` (the
    Pallas grouped kernel on a TPU, `jax.lax.ragged_dot` elsewhere),
    every one of them, at 1 token or 1,024: the row buffer holds all
    `top_k * tokens` assignments, so none can be dropped — and the
    others are left to the chips that hold them: nothing here stands
    in for those chips or their traffic.  The shared expert is whole.

    Two forms of expert.  `gated` (the default): down(silu(gate x) *
    up x) at the hidden size, the shared expert alike.  Not gated:
    down(relu(up x) ** 2).  With `latent` > 0 the routed experts live
    in a latent space of that width, entered (`latent_in`) and left
    (`latent_out`) by two projections all experts share: the picked
    experts' weighted sum is taken in the latent space and projected
    back once; the shared expert stays at the hidden size,
    `shared_width` wide (0: `width * num_shared_experts`).

    Returns (y [b, t, d], counts int32 [held + 2]): tokens computed by
    each held expert, then the assignments the router gave to held
    experts, then all it gave (real tokens * top_k).  The first `held`
    add up to the next number, or an assignment was dropped."""
    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    width: int
    scale: float = 1.0
    norm_topk_prob: bool = True
    num_shared_experts: int = 1
    gated: bool = True
    latent: int = 0
    shared_width: int = 0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, token_mask=None):
        b, t, d = x.shape
        first, held = self.experts_held
        n, k = b * t, self.top_k
        flat = x.reshape(n, d)
        real = (jnp.ones((n,), bool) if token_mask is None
                else token_mask.reshape(n).astype(bool))

        with jax.named_scope("moe.router"):
            w_r = Kernel((d, self.num_experts), self.param_dtype,
                         name="router")()
            bias = self.param("bias", nn.initializers.zeros_init(),
                              (self.num_experts,), self.param_dtype)
            score = jax.nn.sigmoid(jnp.matmul(
                flat.astype(jnp.float32), w_r.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))      # [n, E]
            _, picked = jax.lax.top_k(score + bias.astype(jnp.float32), k)
            weight = jnp.take_along_axis(score, picked, axis=-1)
            if self.norm_topk_prob:
                weight = weight / weight.sum(-1, keepdims=True)
            weight = weight * self.scale                    # [n, k]

        inner, source = d, flat
        if self.latent:
            with jax.named_scope("moe.latent_in"):
                inner = self.latent
                source = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  name="latent_in")(flat.astype(self.dtype))

        with jax.named_scope("moe.experts"):
            local = picked - first
            mine = (local >= 0) & (local < held) & real[:, None]
            # group the assignments by held expert: a stable sort by
            # local id, everything this chip does not compute last
            key = jnp.where(mine, local, held).reshape(n * k)
            order = jnp.argsort(key, stable=True)
            back = jnp.argsort(order)
            sizes = jnp.bincount(key, length=held + 1)[:held] \
                .astype(jnp.int32)
            rows = source[order // k].astype(self.dtype)  # [n*k, inner]

            def stacked(name, *shape):
                return Kernel((held,) + shape, self.param_dtype,
                              name=name)()
            if self.gated:
                h = nn.silu(grouped.grouped_matmul(
                    rows, stacked("experts_gate", inner, self.width),
                    sizes)) * grouped.grouped_matmul(
                        rows, stacked("experts_up", inner, self.width),
                        sizes)
            else:
                h = jnp.square(nn.relu(grouped.grouped_matmul(
                    rows, stacked("experts_up", inner, self.width),
                    sizes)))
            out = grouped.grouped_matmul(
                h.astype(self.dtype),
                stacked("experts_down", self.width, inner), sizes)
            # back to [token, pick]; rows past the groups hold nothing
            # a sum may see (the kernel leaves them unwritten)
            out = out[back].reshape(n, k, inner).astype(jnp.float32)
            routed = jnp.where(mine[..., None],
                               out * weight[..., None], 0.0).sum(1)

        if self.latent:
            with jax.named_scope("moe.latent_out"):
                routed = nn.Dense(d, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  name="latent_out")(
                    routed.astype(self.dtype)).astype(jnp.float32)

        with jax.named_scope("moe.shared"):
            shared = (GatedMLP if self.gated else SquaredReluMLP)(
                self.shared_width or self.width * self.num_shared_experts,
                dtype=self.dtype, param_dtype=self.param_dtype,
                name="shared")(flat.astype(self.dtype))
        counts = jnp.concatenate([
            sizes, jnp.stack([mine.sum(), real.sum() * k]
                             ).astype(jnp.int32)])
        y = (routed + shared.astype(jnp.float32)).reshape(b, t, d)
        return y, counts


class DecoderLM(nn.Module):
    """input_ids/positions [batch, t] -> (logits [batch, t, vocab],
    new_k, new_v [layers, batch, t, KV heads, head_dim]); the modes and
    their arguments are `CausalLM`'s (model.py).  `token_mask` also
    tells the expert layers which tokens are real (padding and dead
    lanes are routed nowhere and counted nowhere)."""

    vocab: int
    hidden_size: int
    n_head: int
    n_kv_head: int
    head_dim: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    intermediate_size: int
    moe_intermediate_size: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    num_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    sliding_window: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_position_len: int = 262144
    #: latent attention (module docstring): the cached latent's width
    #: (0: the model has none), a query head's two parts, a value head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: the config's `rope_scaling` (`deepseek_yarn`) as sorted items,
    #: or None: plain frequencies
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    #: the looped form (module docstring): the stack runs this many
    #: times a token over one set of weights
    total_ut_steps: int = 1
    #: rotary on `full_attention` layers as on window layers (EXAONE
    #: rotates its window layers only)
    rotary_full: bool = False
    #: RMSNorm over each head of q and k
    qk_norm: bool = True
    #: RMSNorm on each sub-layer's output before the residual add
    sandwich_norm: bool = False
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    paged_attention_impl: Optional[str] = None

    def __post_init__(self):
        # lists out of a JSON file become tuples: a module hashes
        for name in ("layer_types", "mlp_layer_types", "experts_held"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        super().__post_init__()
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        unknown = (set(self.layer_types) - {SLIDING, FULL, LATENT}) \
            | (set(self.mlp_layer_types) - {DENSE, SPARSE})
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if LATENT in self.layer_types:
            if set(self.layer_types) != {LATENT}:
                raise ValueError(
                    "latent_attention caches one row a token and the "
                    "other kinds two: one pool holds one form, so a "
                    "model has it in every layer or in none")
            if not (self.kv_lora_rank and self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError(
                    "latent_attention needs kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} KV heads")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps} < 1")
        if self.total_ut_steps > 1 and (
                len(set(self.layer_types)) != 1
                or set(self.mlp_layer_types) != {DENSE}
                or self.latent):
            raise ValueError(
                "a looped stack runs its layers as ONE body over stacked "
                "weights: one attention kind (window or full) and dense "
                f"FFNs, not {sorted(set(self.layer_types))} over "
                f"{sorted(set(self.mlp_layer_types))}")

    @classmethod
    def from_config(cls, config, **kw) -> "DecoderLM":
        """The module a Hugging-Face-style `config.json` mapping
        describes, by `exaone_moe`'s keys (`layer_types`,
        `mlp_layer_types`, `num_key_value_heads`, `num_experts`, ...)
        or, where it has a `kv_lora_rank`, by `sarvam_mla`'s
        (`first_k_dense_replace`, `qk_nope_head_dim`, `rope_scaling`,
        ...; its `head_dim` is the cached row's width), or, where it
        has a `total_ut_steps`, by `ouro`'s (dense layers looped that
        many times, rotary on every layer, sandwich norms, no q/k norm,
        a top-level `rope_theta`; an `early_exit_threshold` under 1
        asks for per-token exits, which are not served);
        `experts_held` = [first id, count] is this chip's share of the
        experts (all of them when absent).  `kw`: the fields no
        `config.json` has (dtypes, the paged kernel's impl)."""
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        held = config.get("experts_held")
        if "kv_lora_rank" in config:
            # `sarvam_mla`'s keys: latent attention in every layer, the
            # first `first_k_dense_replace` FFNs dense, `rope_theta`
            # and `rope_scaling` at the top level
            n, dense = config["num_hidden_layers"], \
                config.get("first_k_dense_replace", 0)
            kw = dict(
                layer_types=(LATENT,) * n,
                mlp_layer_types=(DENSE,) * dense + (SPARSE,) * (n - dense),
                kv_lora_rank=config["kv_lora_rank"],
                qk_nope_head_dim=config["qk_nope_head_dim"],
                qk_rope_head_dim=config["qk_rope_head_dim"],
                v_head_dim=config["v_head_dim"],
                rope_scaling=config.get("rope_scaling"),
                rope_theta=float(config["rope_theta"]), **kw)
        elif "total_ut_steps" in config:
            if config.get("early_exit_threshold", 1.0) < 1.0:
                raise ValueError(
                    "an early_exit_threshold under 1 lets a token leave "
                    "the loop early, and its later steps' pool slots "
                    "would have to be filled for the tokens after it: "
                    "not served")
            kw = dict(
                layer_types=tuple(config["layer_types"]),
                mlp_layer_types=(DENSE,) * config["num_hidden_layers"],
                total_ut_steps=int(config["total_ut_steps"]),
                rotary_full=True, qk_norm=False, sandwich_norm=True,
                rope_theta=float(config["rope_theta"]), **kw)
        else:
            kw = dict(
                layer_types=tuple(config["layer_types"]),
                mlp_layer_types=tuple(config["mlp_layer_types"]),
                rope_theta=float(config["rope_parameters"]["rope_theta"]),
                **kw)
        return cls(
            vocab=config["vocab_size"], hidden_size=hidden, n_head=heads,
            n_kv_head=config.get("num_key_value_heads") or heads,
            head_dim=config.get("head_dim") or hidden // heads,
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config.get("moe_intermediate_size", 0),
            num_experts=config.get("num_experts", 0),
            num_experts_per_tok=config.get("num_experts_per_tok", 0),
            experts_held=tuple(held) if held is not None else None,
            num_shared_experts=config.get("num_shared_experts", 1),
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            norm_topk_prob=config.get("norm_topk_prob", True),
            sliding_window=config.get("sliding_window") or 0,
            rms_norm_eps=config["rms_norm_eps"],
            max_position_len=config["max_position_embeddings"], **kw)

    # -- what the engine asks of a model -------------------------------

    @property
    def n_block(self) -> int:
        return len(self.layer_types)

    @property
    def latent(self) -> bool:
        return LATENT in self.layer_types

    def kv_geometry(self) -> Tuple[int, ...]:
        """(pool slots, KV heads, head dim): a pool row is KV heads *
        head dim wide, and a token holds two; a slot a layer, or
        `total_ut_steps` a layer in the looped form (slot t * layers +
        l).  With latent attention (layers, 1, the cached row's width,
        1): ONE row a token."""
        if self.latent:
            return (self.n_block, 1,
                    self.kv_lora_rank + self.qk_rope_head_dim, 1)
        return (self.n_block * self.total_ut_steps, self.n_kv_head,
                self.head_dim)

    def latent_constants(self):
        """(the rotary frequencies or None for the plain ones, the
        factor cos and sin carry, the softmax scale) of latent
        attention: `deepseek_yarn`'s where the config scales its
        rotary — mscale over mscale_all_dim's factor on cos and sin,
        the latter's square on (nope + rope) ** -0.5."""
        scaling = dict(self.rope_scaling or ())
        d = self.qk_nope_head_dim + self.qk_rope_head_dim
        if not scaling:
            return None, 1.0, d ** -0.5
        factor = scaling["factor"]
        all_dim = yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0))
        return (yarn_frequencies(self.qk_rope_head_dim, self.rope_theta,
                                 scaling),
                yarn_mscale(factor, scaling.get("mscale", 1.0)) / all_dim,
                d ** -0.5 * all_dim ** 2)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def moe_counts_shape(self) -> Optional[Tuple[int, int]]:
        """[expert layers, held + 2] of what `ExpertLayer` counts, or
        None for a model without one."""
        n = sum(kind == SPARSE for kind in self.mlp_layer_types)
        return (n, self.held[1] + 2) if n else None

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """Indices of the layers whose FFN is an `ExpertLayer`, in the
        order of the counts' rows."""
        return tuple(i for i, kind in enumerate(self.mlp_layer_types)
                     if kind == SPARSE)

    def unsupported_features(self) -> Dict[str, str]:
        """Engine features this model refuses, each with its reason
        (the engine raises at construction when one is asked for)."""
        return {
            "tensor_parallel": "the tensor-parallel placement shards "
                               "`CausalLM`'s fused qkv by name and has "
                               "no rule for grouped heads, stacked "
                               "experts or a latent row all heads read",
            "kv_quantization": "neither the grouped-query paged kernel "
                               "nor the latent one reads an int8 pool"}

    # -- the forward pass ----------------------------------------------

    @nn.compact
    def __call__(self, input_ids, positions, token_mask=None,
                 ctx_k=None, ctx_v=None, ctx_len=None,
                 kv_pool=None, kv_scale=None, block_tables=None):
        b, t = input_ids.shape
        h, g, hd = self.n_head, self.n_kv_head, self.head_dim
        cd, pd = self.compute_dtype, self.param_dtype
        impl = self.paged_attention_impl or "auto"

        def norm(name):
            return RMSNorm(epsilon=self.rms_norm_eps, dtype=cd,
                           param_dtype=pd, name=name)

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=cd, param_dtype=pd,
                            name=name)

        # the residual stream stays float32: eight layers of bf16 adds
        # would cost more digits than every matmul
        x = nn.Embed(self.vocab, self.hidden_size, param_dtype=pd,
                     name="token_embed")(input_ids.astype(jnp.int32)
                                         ).astype(jnp.float32)
        additive_mask = None
        if token_mask is not None:
            additive_mask = (1.0 - token_mask[:, None, None, :]
                             .astype(jnp.float32)) * -1e9

        def latent_attention(i, blk, a_in):
            # one layer's latent attention over `a_in` (normalised):
            # (its output before the o projection [b, t, h * dv], the
            # rows it caches [b, t, row]) — module docstring
            r, dn = self.kv_lora_rank, self.qk_nope_head_dim
            dr, dv = self.qk_rope_head_dim, self.v_head_dim
            inv_freq, turn, scale = self.latent_constants()
            q = dense(h * (dn + dr), f"{blk}_q")(a_in) \
                .reshape(b, t, h, dn + dr)
            q = norm(f"{blk}_q_norm")(q)

            def turned(x):
                x = rotary(x, positions, self.rope_theta, inv_freq)
                return x if turn == 1.0 else (x * turn).astype(x.dtype)
            q_rope = turned(q[..., dn:])
            row = dense(r + dr, f"{blk}_kv_a")(a_in)
            c = norm(f"{blk}_kv_a_norm")(row[..., :r])
            k_r = turned(row[..., None, r:])[:, :, 0]
            row = jnp.concatenate([c, k_r], axis=-1)      # [b, t, r + dr]
            w_b = Kernel((r, h * (dn + dv)), pd, name=f"{blk}_kv_b")() \
                .astype(cd).reshape(r, h, dn + dv)
            if kv_pool is not None and t == 1:
                # absorbed: the key up-projection into the query, the
                # value up-projection after the sum over the rows
                q_abs = jnp.concatenate([
                    jnp.einsum("bhd,rhd->bhr", q[:, 0, :, :dn],
                               w_b[..., :dn]).astype(cd),
                    q_rope[:, 0]], axis=-1)
                o = latent_decode_attention(
                    q_abs, row[:, 0], kv_pool, block_tables, ctx_len,
                    layer=i, value_width=r, scale=scale, impl=impl)
                a = jnp.einsum("bhr,rhd->bhd", o.astype(cd),
                               w_b[..., dn:])[:, None]
                return a, row

            def expand(rows):
                # cached rows [b, n, r + dr] -> every head's key
                # [b, n, h, dn + dr] and value [b, n, h, dv]
                kv = jnp.einsum("bnr,rhd->bnhd", rows[..., :r].astype(cd),
                                w_b)
                shared = jnp.broadcast_to(
                    rows[:, :, None, r:].astype(cd),
                    kv.shape[:3] + (dr,))
                return (jnp.concatenate([kv[..., :dn], shared], -1),
                        kv[..., dn:])
            q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
            ctx = {}
            if kv_pool is not None:
                cached = latent_paged_context(kv_pool, block_tables,
                                              layer=i, width=r + dr)
            elif ctx_k is not None:
                cached = ctx_k[i][:, :, 0]
            else:
                cached = None
                ctx = dict(mask=additive_mask, causal=True)
            if cached is not None:
                ctx_keys, ctx_vals = expand(cached)
                ctx = dict(ctx_k=ctx_keys, ctx_v=ctx_vals, ctx_len=ctx_len)
            a = dot_product_attention(q, *expand(row), compute_dtype=cd,
                                      scale=scale, **ctx)
            return a, row

        if self.total_ut_steps > 1:
            x, new_k, new_v = self._looped(
                x, positions, additive_mask, dict(
                    ctx_k=ctx_k, ctx_v=ctx_v, ctx_len=ctx_len,
                    kv_pool=kv_pool, kv_scale=kv_scale,
                    block_tables=block_tables, impl=impl))
            logits = dense(self.vocab, "lm_head")(x.astype(cd))
            return logits.astype(jnp.float32), new_k, new_v

        new_k, new_v, counts = [], [], []
        for i, (attn_kind, ffn_kind) in enumerate(
                zip(self.layer_types, self.mlp_layer_types)):
            blk = f"block_{i}"
            window = self.sliding_window if attn_kind == SLIDING else None
            with jax.named_scope(
                    "attn.latent" if attn_kind == LATENT else
                    "attn.window" if window else "attn.full"):
                a_in = norm(f"{blk}_attn_norm")(x)
                if attn_kind == LATENT:
                    a, row = latent_attention(i, blk, a_in)
                    new_k.append(row[:, :, None].astype(jnp.float32))
                else:
                    q = dense(h * hd, f"{blk}_q")(a_in) \
                        .reshape(b, t, h, hd)
                    k = dense(g * hd, f"{blk}_k")(a_in) \
                        .reshape(b, t, g, hd)
                    v = dense(g * hd, f"{blk}_v")(a_in) \
                        .reshape(b, t, g, hd)
                    if self.qk_norm:
                        q = norm(f"{blk}_q_norm")(q)
                        k = norm(f"{blk}_k_norm")(k)
                    if window or self.rotary_full:
                        q = rotary(q, positions, self.rope_theta)
                        k = rotary(k, positions, self.rope_theta)
                    new_k.append(k.astype(jnp.float32))
                    new_v.append(v.astype(jnp.float32))
                    a = attend(q, k, v, layer=i, window=window,
                               mask=additive_mask, ctx_k=ctx_k,
                               ctx_v=ctx_v, ctx_len=ctx_len,
                               kv_pool=kv_pool, kv_scale=kv_scale,
                               block_tables=block_tables, impl=impl,
                               compute_dtype=cd)
                a = dense(self.hidden_size, f"{blk}_o")(
                    a.reshape(b, t, -1).astype(cd))
                if self.sandwich_norm:
                    a = norm(f"{blk}_attn_post_norm")(a)
            x = x + a.astype(jnp.float32)
            f_in = norm(f"{blk}_ffn_norm")(x)
            if ffn_kind == DENSE:
                f = GatedMLP(self.intermediate_size, dtype=cd,
                             param_dtype=pd, name=f"{blk}_mlp")(f_in)
            else:
                f, n_tokens = ExpertLayer(
                    num_experts=self.num_experts, experts_held=self.held,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size,
                    scale=self.routed_scaling_factor,
                    norm_topk_prob=self.norm_topk_prob,
                    num_shared_experts=self.num_shared_experts,
                    dtype=cd, param_dtype=pd, name=f"{blk}_moe")(
                        f_in, token_mask)
                counts.append(n_tokens)
            if self.sandwich_norm:
                f = norm(f"{blk}_ffn_post_norm")(f)
            x = x + f.astype(jnp.float32)

        if counts:
            self.sow(MOE_COUNTS, "tokens", jnp.stack(counts),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        logits = dense(self.vocab, "lm_head")(norm("final_norm")(x))
        return (logits.astype(jnp.float32), jnp.stack(new_k),
                jnp.stack(new_v) if new_v else None)

    def _looped(self, x, positions, additive_mask, cache):
        """The looped form's stack (module docstring): x [b, t, hidden]
        float32 -> (x after the last step's final norm, new_k, new_v
        [T * L slots, b, t, KV heads, head_dim] in the compute dtype).
        `cache`: the call's mode, as `attend` takes it.  Called from the
        compact `__call__`, so the stacked leaves are its own."""
        b, t = x.shape[:2]
        h, g, hd = self.n_head, self.n_kv_head, self.head_dim
        d, ff = self.hidden_size, self.intermediate_size
        n_layers, cd, pd = self.n_block, self.compute_dtype, \
            self.param_dtype
        eps = self.rms_norm_eps
        window = (self.sliding_window
                  if self.layer_types[0] == SLIDING else None)
        turned = bool(window) or self.rotary_full

        def stacked(name, *shape):
            return Kernel((n_layers,) + shape, pd, name=f"loop_{name}")()
        w = {name: stacked(name, *shape) for name, shape in (
            ("q", (d, h * hd)), ("k", (d, g * hd)), ("v", (d, g * hd)),
            ("o", (h * hd, d)), ("gate", (d, ff)), ("up", (d, ff)),
            ("down", (ff, d)))}
        norms = ["attn_norm", "ffn_norm"] + (
            ["attn_post_norm", "ffn_post_norm"] if self.sandwich_norm
            else [])
        w.update((name, Scales((n_layers, d), pd, name=f"loop_{name}")())
                 for name in norms)
        if self.qk_norm:
            w.update((name, Scales((n_layers, hd), pd,
                                   name=f"loop_{name}")())
                     for name in ("q_norm", "k_norm"))
        final = Scales((d,), pd, name="final_norm")()

        def mm(a, kernel):
            return jnp.dot(a.astype(cd), kernel.astype(cd))

        def layer(x, w, slot):
            # one application of layer l at step t: slot t * L + l
            with jax.named_scope("attn.loop"):
                a_in = rms_norm(x, w["attn_norm"], eps=eps, out_dtype=cd)
                q = mm(a_in, w["q"]).reshape(b, t, h, hd)
                k = mm(a_in, w["k"]).reshape(b, t, g, hd)
                v = mm(a_in, w["v"]).reshape(b, t, g, hd)
                if self.qk_norm:
                    q = rms_norm(q, w["q_norm"], eps=eps, out_dtype=cd)
                    k = rms_norm(k, w["k_norm"], eps=eps, out_dtype=cd)
                if turned:
                    q = rotary(q, positions, self.rope_theta)
                    k = rotary(k, positions, self.rope_theta)
                a = attend(q, k, v, layer=slot, window=window,
                           mask=additive_mask, compute_dtype=cd, **cache)
                a = mm(a.reshape(b, t, -1), w["o"])
                if self.sandwich_norm:
                    a = rms_norm(a, w["attn_post_norm"], eps=eps,
                                 out_dtype=cd)
            x = x + a.astype(jnp.float32)
            with jax.named_scope("ffn.loop"):
                f_in = rms_norm(x, w["ffn_norm"], eps=eps, out_dtype=cd)
                f = mm(nn.silu(mm(f_in, w["gate"])) * mm(f_in, w["up"]),
                       w["down"])
                if self.sandwich_norm:
                    f = rms_norm(f, w["ffn_post_norm"], eps=eps,
                                 out_dtype=cd)
            return x + f.astype(jnp.float32), (k.astype(cd), v.astype(cd))

        def step(x, t_step):
            def one(x, layer_and_index):
                weights, index = layer_and_index
                return layer(x, weights, t_step * n_layers + index)
            x, kv = jax.lax.scan(
                one, x, (w, jnp.arange(n_layers, dtype=jnp.int32)))
            return rms_norm(x, final, eps=eps, out_dtype=jnp.float32), kv

        x, (ks, vs) = jax.lax.scan(
            step, x, jnp.arange(self.total_ut_steps, dtype=jnp.int32))
        slots = (self.total_ut_steps * n_layers,)
        return (x, ks.reshape(slots + ks.shape[2:]),
                vs.reshape(slots + vs.shape[2:]))


class ExpertCounters:
    """The expert layers' counts in a registry: tokens each held expert
    computed, by layer and global expert id (the registry has no
    labels: they ride in the name), where the router's assignments
    went, and the ones lost on the way.  The one reader of the
    `[expert layers, held + 2]` array a `DecoderLM` sows
    (`ExpertLayer`'s counts, stacked).  Beside them, the grouped
    products built since the engine was (`ops.grouped.BUILT`: a
    program's are built when it is first traced), by the path each
    took and the row tile the kernel chose."""

    @classmethod
    def of(cls, model, registry) -> Optional["ExpertCounters"]:
        """None for a model that hands back no counts."""
        if getattr(model, "moe_counts_shape", None) is None:
            return None
        return cls(model, registry)

    def __init__(self, model: DecoderLM, reg):
        first, held = model.held
        self._reg = reg
        self._built = grouped.built()
        self._tokens = [
            [reg.counter(
                f"generation_moe_expert_tokens_total_layer{layer}"
                f"_expert{first + e}",
                help="tokens this expert computed")
             for e in range(held)] for layer in model.moe_layers]
        self._held = reg.counter(
            "generation_moe_assignments_total_held",
            help="router assignments to experts held here")
        self._elsewhere = reg.counter(
            "generation_moe_assignments_total_elsewhere",
            help="router assignments to experts other chips hold")
        self._dropped = reg.counter(
            "generation_moe_dropped_total",
            help="assignments to held experts that no expert "
                 "computed (must read 0)")
        self._loads = {
            program: reg.counter(
                f"generation_moe_expert_loads_total_{program}",
                help="(layer, held expert) pairs a dispatch had a "
                     "token for: expert weights it had to read")
            for program in ("prefill", "decode")}

    def add(self, counts, program: str) -> None:
        """Add one dispatch's fetched counts.  `program`: "prefill"
        (chunks too) or "decode" (verify rounds too)."""
        self._count_built()
        counts = np.asarray(counts)
        held = counts.shape[1] - 2
        self._loads[program].inc(int((counts[:, :held] > 0).sum()))
        for row, counters in zip(counts, self._tokens):
            for n, counter in zip(row[:held], counters):
                if n:
                    counter.inc(int(n))
        to_held = int(counts[:, held].sum())
        self._held.inc(to_held)
        self._elsewhere.inc(int(counts[:, held + 1].sum()) - to_held)
        self._dropped.inc(to_held - int(counts[:, :held].sum()))

    def _count_built(self) -> None:
        built = grouped.built()
        for (path, tile), n in (built - self._built).items():
            self._reg.counter(
                f"generation_grouped_products_total_{path}",
                help="grouped products in the programs built, by "
                     "the path the dispatcher took").inc(n)
            if path == grouped.KERNEL:
                self._reg.counter(
                    f"generation_grouped_kernel_tile_rows_total_{tile}",
                    help="of the kernel's, by the row tile").inc(n)
        self._built = built
