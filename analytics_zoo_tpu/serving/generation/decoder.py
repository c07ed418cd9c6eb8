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
    RMSNorm over each head of q and k;
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

Weights are held in `param_dtype` (bfloat16 as served: at 6144 wide a
float32 tree cast every step would be twice the chip) under leaves
named `kernel` / `embedding` / `scale` / `bias` only; a layer's held
experts are ONE stacked `kernel` `[held, in, out]` a projection.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import grouped
from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    paged_decode_attention,
    paged_verify_attention,
)
from analytics_zoo_tpu.ops.normalization import RMSNorm

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
#: the flax collection an `ExpertLayer` sows its counts into
MOE_COUNTS = "moe_counts"


def rotary(x, positions, theta: float):
    """Rotate-half rotary embedding over the whole head: x [b, t, heads,
    d], positions [b, t].  Pair (i, i + d/2) turns by position *
    theta ** (-2i / d); float32 inside, x's dtype out."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
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
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    paged_attention_impl: Optional[str] = None

    def __post_init__(self):
        # lists out of a JSON file become tuples: a module hashes
        for name in ("layer_types", "mlp_layer_types", "experts_held"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))
        super().__post_init__()
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        unknown = (set(self.layer_types) - {SLIDING, FULL}) \
            | (set(self.mlp_layer_types) - {DENSE, SPARSE})
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} KV heads")

    @classmethod
    def from_config(cls, config, **kw) -> "DecoderLM":
        """The module a Hugging-Face-style `config.json` mapping
        describes, by `exaone_moe`'s keys (`layer_types`,
        `mlp_layer_types`, `num_key_value_heads`, `num_experts`, ...);
        `experts_held` = [first id, count] is this chip's share of the
        experts (all of them when absent).  `kw`: the fields no
        `config.json` has (dtypes, the paged kernel's impl)."""
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        held = config.get("experts_held")
        return cls(
            vocab=config["vocab_size"], hidden_size=hidden, n_head=heads,
            n_kv_head=config.get("num_key_value_heads", heads),
            head_dim=config.get("head_dim") or hidden // heads,
            layer_types=tuple(config["layer_types"]),
            mlp_layer_types=tuple(config["mlp_layer_types"]),
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config.get("moe_intermediate_size", 0),
            num_experts=config.get("num_experts", 0),
            num_experts_per_tok=config.get("num_experts_per_tok", 0),
            experts_held=tuple(held) if held is not None else None,
            num_shared_experts=config.get("num_shared_experts", 1),
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            norm_topk_prob=config.get("norm_topk_prob", True),
            sliding_window=config.get("sliding_window", 0),
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            rms_norm_eps=config["rms_norm_eps"],
            max_position_len=config["max_position_embeddings"], **kw)

    # -- what the engine asks of a model -------------------------------

    @property
    def n_block(self) -> int:
        return len(self.layer_types)

    def kv_geometry(self) -> Tuple[int, int, int]:
        """(layers, KV heads, head dim): a pool row is KV heads *
        head dim wide."""
        return self.n_block, self.n_kv_head, self.head_dim

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

    def unsupported_features(self) -> Tuple[str, ...]:
        """Engine features this model refuses (the engine raises at
        construction when one is asked for): the tensor-parallel
        placement shards `CausalLM`'s fused qkv by name and knows no
        grouped heads or stacked experts, and the grouped-query paged
        kernel reads no int8 pool."""
        return ("tensor_parallel", "kv_quantization")

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

        new_k, new_v, counts = [], [], []
        for i, (attn_kind, ffn_kind) in enumerate(
                zip(self.layer_types, self.mlp_layer_types)):
            blk = f"block_{i}"
            window = self.sliding_window if attn_kind == SLIDING else None
            with jax.named_scope(
                    "attn.window" if window else "attn.full"):
                a_in = norm(f"{blk}_attn_norm")(x)
                q = dense(h * hd, f"{blk}_q")(a_in).reshape(b, t, h, hd)
                k = dense(g * hd, f"{blk}_k")(a_in).reshape(b, t, g, hd)
                v = dense(g * hd, f"{blk}_v")(a_in).reshape(b, t, g, hd)
                q = norm(f"{blk}_q_norm")(q)
                k = norm(f"{blk}_k_norm")(k)
                if window:
                    q = rotary(q, positions, self.rope_theta)
                    k = rotary(k, positions, self.rope_theta)
                new_k.append(k.astype(jnp.float32))
                new_v.append(v.astype(jnp.float32))
                a = attend(q, k, v, layer=i, window=window,
                           mask=additive_mask, ctx_k=ctx_k, ctx_v=ctx_v,
                           ctx_len=ctx_len, kv_pool=kv_pool,
                           kv_scale=kv_scale, block_tables=block_tables,
                           impl=impl, compute_dtype=cd)
                a = dense(self.hidden_size, f"{blk}_o")(
                    a.reshape(b, t, h * hd).astype(cd))
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
            x = x + f.astype(jnp.float32)

        if counts:
            self.sow(MOE_COUNTS, "tokens", jnp.stack(counts),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        logits = dense(self.vocab, "lm_head")(norm("final_norm")(x))
        return (logits.astype(jnp.float32),
                jnp.stack(new_k), jnp.stack(new_v))


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
