"""A hybrid decoder for the generation engine: ONE pre-norm residual
sub-layer a pattern letter, its kind read from a pattern string
(`hybrid_override_pattern`, the `nemotron_h` family's key; built from
`layer_types` for `lfm2_moe`, two letters a layer):

  * `M` — a Mamba-2 state-space mixer (`ops.ssm`): `in_proj` to
    `[z | xBC | dt]`, a causal depthwise convolution over `xBC`, the
    selective scan over `[x | B | C]` with a per-head decay, the output
    gated by `silu(z)` BEFORE a group-wise RMSNorm, `out_proj`;
  * `C` — LFM2's gated short convolution: `[B | C | x] = in_proj(u)`,
    `v = B * x`, a causal depthwise convolution of `conv_kernel` taps
    over `v` (no bias), `out_proj(C * conv(v))`;
  * `*` — causal grouped-query attention through `ops.attention` in
    every mode: without positions and without q/k norms (`nemotron_h`),
    or with an RMSNorm over each head of q and k and then rotary
    positions over the whole head (`qk_norm`, `rope_theta`: `lfm2_moe`);
  * `D` — a dense gated SiLU FFN at `intermediate_size`;
  * `E` — `decoder.ExpertLayer`: a sigmoid router over all experts, in
    its latent, un-gated form with a squared-ReLU shared expert at the
    hidden size (`nemotron_h`), or gated at the hidden size with no
    shared expert (`moe_gated`, `num_shared_experts` 0: `lfm2_moe`).

Every letter is `x <- x + Sublayer(RMSNorm(x))`; the residual stream
stays float32; the head is untied.  An `lfm2_moe` layer is two letters,
its operator (`C` or `*`) and its FFN (`D` on the leading
`num_dense_layers` layers, `E` after them).

Two kinds of state.  The `*` layers' keys and values live in the paged
pool, a row a token, found through a block table: `kv_geometry()`
counts those layers only.  Each state layer keeps a FIXED-SIZE state a
lane which no block table finds: it lives in the engine's recurrent
pool, addressed by the lane's slot (`state_geometry()`;
kv_cache.RecurrentStatePool).  An `M` layer's is the scan's `H`
[heads, head_dim, state] in float32 and the last `conv_kernel - 1`
rows of the pre-convolution `xBC`; a `C` layer's is the last
`conv_kernel - 1` rows of `v` alone, in the compute dtype.  A pattern
holds one kind of state layer or none.

The call contract is `CausalLM`'s with one more argument and one more
result: `recurrent` = {"ssm": one [batch, heads, head_dim, state]
float32 array an `M` layer (none for `C` layers), "conv": one
[conv_kernel - 1, batch, channels] array a state layer} is the state
BEFORE `input_ids` (None: a sequence's start), and the fourth result
the state after them, in the same form.  With t = 1 (a decode round,
batch = every lane) a row whose `token_mask` is false hands its state
back untouched; with t > 1 (a prefill) the state is the one after each
row's `token_mask` real tokens — a bucket's padding does not advance
it.  A pattern without state layers takes no `recurrent` and hands
back three results, as `DecoderLM`.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import ssm
from analytics_zoo_tpu.ops.normalization import RMSNorm, rms_norm
from analytics_zoo_tpu.serving.generation.decoder import (
    MOE_COUNTS,
    ExpertLayer,
    GatedMLP,
    attend,
    rotary,
)

MAMBA, CONV, ATTENTION, DENSE, EXPERTS = "M", "C", "*", "D", "E"
#: why a model with state layers refuses an engine feature
_NO_SNAPSHOT = ("it would need a snapshot of the recurrent state at a "
                "position inside a sequence, which the state pool does "
                "not keep (one state a lane, the newest)")


class MambaMixer(nn.Module):
    """One Mamba-2 mixer over u [b, t, hidden] (already normalised).
    `state` = (H [b, heads, head_dim, S] float32, tail [k-1, b,
    channels]) or None; returns (out [b, t, hidden], new state)."""
    n_head: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk: int
    eps: float
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, state, real):
        b, t, d = u.shape
        H, P, G, S = (self.n_head, self.head_dim, self.n_groups,
                      self.state_size)
        inner, k = H * P, self.conv_kernel
        channels = inner + 2 * G * S
        cd, pd = self.dtype, self.param_dtype

        def vector(name, n, init):
            return self.param(name, init, (n,), pd)

        with jax.named_scope("ssm.in_proj"):
            # operands in the compute dtype, the sums handed on in
            # float32: what feeds a state that a lane carries for its
            # whole life is not rounded on the way
            zxd = nn.Dense(
                inner + channels + H, use_bias=False, dtype=cd,
                param_dtype=pd, name="in_proj",
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32)
            )(u.astype(cd))
            z, xbc, dt = jnp.split(zxd, [inner, inner + channels], axis=-1)
        with jax.named_scope("ssm.conv"):
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (k, channels), pd)
            tail = None if state is None else state[1]
            conv = nn.silu(ssm.causal_conv(
                xbc, kernel,
                vector("conv_bias", channels, nn.initializers.zeros_init()),
                tail))
            x, B, C = jnp.split(conv, [inner, inner + G * S], axis=-1)
            dt = jax.nn.softplus(
                dt.astype(jnp.float32)
                + vector("dt_bias", H, nn.initializers.zeros_init()
                         ).astype(jnp.float32))
            A = -jnp.exp(vector("A_log", H, nn.initializers.zeros_init()
                                ).astype(jnp.float32))
            D = vector("D", H, nn.initializers.ones_init())
        if t == 1:
            with jax.named_scope("ssm.step"):
                h = state[0]
                y, stepped = ssm.ssm_step(
                    h, x[:, 0].reshape(b, H, P), dt[:, 0], A,
                    B[:, 0].reshape(b, G, S), C[:, 0].reshape(b, G, S), D)
                live = real[:, 0]
                new_h = jnp.where(live[:, None, None, None], stepped, h)
                shifted = jnp.concatenate(
                    [tail[1:], xbc[None, :, 0].astype(tail.dtype)], axis=0)
                new_tail = jnp.where(live[None, :, None], shifted, tail)
                y = y[:, None]
        else:
            if state is not None:
                raise NotImplementedError(
                    "a prefill starts a sequence: carrying a state into "
                    "more than one token (a chunk) is not implemented")
            with jax.named_scope("ssm.scan"):
                y, new_h = ssm.ssm_scan(
                    x.reshape(b, t, H, P), dt * real[..., None], A,
                    B.reshape(b, t, G, S), C.reshape(b, t, G, S), D,
                    chunk=self.chunk)
                new_tail = ssm.conv_tail(
                    xbc, real.sum(-1).astype(jnp.int32), k)
        with jax.named_scope("ssm.out_proj"):
            # the gate goes in before the norm, which runs over each of
            # the G groups of inner / G columns
            gated = y.reshape(b, t, inner) \
                * nn.silu(z.astype(jnp.float32))
            scale = vector("norm_scale", inner, nn.initializers.ones_init())
            normed = rms_norm(
                gated.reshape(b, t, G, inner // G),
                scale.reshape(G, inner // G), eps=self.eps, out_dtype=cd)
            out = nn.Dense(d, use_bias=False, dtype=cd, param_dtype=pd,
                           name="out_proj")(normed.reshape(b, t, inner))
        return out, (new_h, new_tail)


class ShortConv(nn.Module):
    """LFM2's gated short convolution over u [b, t, hidden] (already
    normalised).  `tail` [k-1, b, hidden] = the k-1 rows of `v` before
    u (None: a sequence's start); returns (out [b, t, hidden], the k-1
    rows of `v` before the next position)."""
    kernel_size: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, tail, real):
        b, t, d = u.shape
        k, cd, pd = self.kernel_size, self.dtype, self.param_dtype
        with jax.named_scope("conv.short"):
            bcx = nn.Dense(
                3 * d, use_bias=False, dtype=cd, param_dtype=pd,
                name="in_proj",
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32)
            )(u.astype(cd))
            B, C, x = jnp.split(bcx, 3, axis=-1)
            # rounded once to the dtype the tail is kept in, so that a
            # prompt's positions and a decoded one's see the same rows
            v = (B * x).astype(cd)
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (k, d), pd)
            z = ssm.causal_conv(v, kernel, None, tail)
            if t == 1:
                shifted = jnp.concatenate([tail[1:], v[None, :, 0]], axis=0)
                new_tail = jnp.where(real[None, :, 0, None], shifted, tail)
            else:
                if tail is not None:
                    raise NotImplementedError(
                        "a prefill starts a sequence: carrying a tail "
                        "into more than one token (a chunk) is not "
                        "implemented")
                new_tail = ssm.conv_tail(
                    v, real.sum(-1).astype(jnp.int32), k)
            out = nn.Dense(d, use_bias=False, dtype=cd, param_dtype=pd,
                           name="out_proj")((C * z).astype(cd))
        return out, new_tail


class HybridLM(nn.Module):
    """input_ids/positions [batch, t] -> (logits [batch, t, vocab],
    new_k, new_v [attention layers, batch, t, KV heads, head_dim],
    the recurrent state after the tokens); modes and arguments are
    `CausalLM`'s plus `recurrent` (module docstring)."""

    vocab: int
    hidden_size: int
    pattern: str
    n_head: int
    n_kv_head: int
    head_dim: int
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 1
    ssm_state_size: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    intermediate_size: int = 0
    rope_theta: Optional[float] = None
    qk_norm: bool = False
    moe_intermediate_size: int = 0
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    moe_gated: bool = False
    num_shared_experts: int = 1
    num_experts: int = 0
    num_experts_per_tok: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_position_len: int = 262144
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    paged_attention_impl: Optional[str] = None

    def __post_init__(self):
        if isinstance(self.experts_held, list):
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))
        super().__post_init__()
        unknown = set(self.pattern) - {MAMBA, CONV, ATTENTION, DENSE,
                                       EXPERTS}
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if MAMBA in self.pattern and CONV in self.pattern:
            raise ValueError("one kind of state layer a model: the "
                             "recurrent pool holds one tail shape")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} KV heads")
        if MAMBA in self.pattern and self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} state-space heads "
                             f"over {self.n_groups} groups")

    @classmethod
    def from_config(cls, config, **kw) -> "HybridLM":
        """The module a Hugging-Face-style `config.json` mapping
        describes, by `nemotron_h`'s keys or, where `model_type` says
        so, `lfm2_moe`'s; `experts_held` = [first id, count] is this
        chip's share of the routed experts (all of them when absent).
        `kw`: the fields no `config.json` has."""
        if config.get("model_type") == "lfm2_moe":
            return cls._from_lfm2(config, **kw)
        held = config.get("experts_held")
        return cls(
            vocab=config["vocab_size"], hidden_size=config["hidden_size"],
            pattern=config["hybrid_override_pattern"],
            n_head=config["num_attention_heads"],
            n_kv_head=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            n_groups=config["n_groups"],
            ssm_state_size=config["ssm_state_size"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            moe_intermediate_size=config.get("moe_intermediate_size", 0),
            moe_latent_size=config.get("moe_latent_size", 0),
            moe_shared_expert_intermediate_size=config.get(
                "moe_shared_expert_intermediate_size", 0),
            num_experts=config.get("n_routed_experts", 0),
            num_experts_per_tok=config.get("num_experts_per_tok", 0),
            experts_held=tuple(held) if held is not None else None,
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            norm_topk_prob=config.get("norm_topk_prob", True),
            norm_eps=config["norm_eps"],
            max_position_len=config["max_position_embeddings"], **kw)

    @classmethod
    def _from_lfm2(cls, config, **kw) -> "HybridLM":
        """`lfm2_moe`: layer i is its operator (`conv` -> `C`,
        `full_attention` -> `*`) then its FFN (`D` below
        `num_dense_layers`, `E` from there on)."""
        unknown = set(config["layer_types"]) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown lfm2_moe layer types "
                             f"{sorted(unknown)}")
        if config.get("conv_bias"):
            raise NotImplementedError(
                "lfm2_moe with a convolution bias is not implemented")
        rope = config.get("rope_parameters") or {
            "rope_theta": config["rope_theta"]}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_type {rope['rope_type']!r} is not implemented")
        dense = config["num_dense_layers"]
        pattern = "".join(
            (CONV if kind == "conv" else ATTENTION)
            + (DENSE if i < dense else EXPERTS)
            for i, kind in enumerate(config["layer_types"]))
        heads, held = config["num_attention_heads"], config.get(
            "experts_held")
        return cls(
            vocab=config["vocab_size"], hidden_size=config["hidden_size"],
            pattern=pattern, n_head=heads,
            n_kv_head=config["num_key_value_heads"],
            head_dim=(config.get("head_dim")
                      or config["hidden_size"] // heads),
            conv_kernel=config["conv_L_cache"],
            intermediate_size=config["intermediate_size"],
            rope_theta=float(rope["rope_theta"]), qk_norm=True,
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_gated=True, num_shared_experts=0,
            num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            experts_held=tuple(held) if held is not None else None,
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            norm_topk_prob=config.get("norm_topk_prob", True),
            norm_eps=config["norm_eps"],
            max_position_len=config["max_position_embeddings"], **kw)

    # -- what the engine asks of a model -------------------------------

    def _layers(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, c in enumerate(self.pattern) if c == kind)

    @property
    def n_block(self) -> int:
        return len(self.pattern)

    def kv_geometry(self) -> Tuple[int, int, int]:
        """(attention layers, KV heads, head dim): the paged pool holds
        rows for the `*` layers alone."""
        return (len(self._layers(ATTENTION)), self.n_kv_head,
                self.head_dim)

    def state_geometry(self):
        """(state layers, per-lane shape and dtype of the scan's state
        — None where the state layers are `C`, which have none — and of
        the convolution's tail): what the recurrent pool holds a lane,
        or None for a pattern without state layers."""
        if CONV in self.pattern:
            return (len(self._layers(CONV)), None,
                    ((self.conv_kernel - 1, self.hidden_size),
                     self.compute_dtype))
        n = len(self._layers(MAMBA))
        if not n:
            return None
        channels = self.mamba_num_heads * self.mamba_head_dim \
            + 2 * self.n_groups * self.ssm_state_size
        return (n,
                ((self.mamba_num_heads, self.mamba_head_dim,
                  self.ssm_state_size), jnp.float32),
                ((self.conv_kernel - 1, channels), self.compute_dtype))

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return self._layers(EXPERTS)

    @property
    def moe_counts_shape(self) -> Optional[Tuple[int, int]]:
        n = len(self.moe_layers)
        return (n, self.held[1] + 2) if n else None

    def unsupported_features(self) -> Dict[str, str]:
        """Engine features this model refuses, each with its reason
        (the engine raises at construction when one is asked for)."""
        refused = {
            "tensor_parallel": "the tensor-parallel placement has no "
                               "rule for grouped heads, stacked experts "
                               "or state-space projections",
            "kv_quantization": "the grouped-query paged kernel reads no "
                               "int8 pool"}
        if self.state_geometry() is not None:
            refused.update(dict.fromkeys(
                ("prefix_caching", "chunked_prefill",
                 "speculative_decoding", "kv_host_tier"), _NO_SNAPSHOT))
        return refused

    # -- the forward pass ----------------------------------------------

    @nn.compact
    def __call__(self, input_ids, positions, token_mask=None,
                 ctx_k=None, ctx_v=None, ctx_len=None,
                 kv_pool=None, kv_scale=None, block_tables=None,
                 recurrent=None):
        b, t = input_ids.shape
        h, g, hd = self.n_head, self.n_kv_head, self.head_dim
        cd, pd = self.compute_dtype, self.param_dtype
        impl = self.paged_attention_impl or "auto"
        real = (jnp.ones((b, t), bool) if token_mask is None
                else token_mask.astype(bool))

        def norm(name):
            return RMSNorm(epsilon=self.norm_eps, dtype=cd,
                           param_dtype=pd, name=name)

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=cd, param_dtype=pd,
                            name=name)

        x = nn.Embed(self.vocab, self.hidden_size, param_dtype=pd,
                     name="token_embed")(input_ids.astype(jnp.int32)
                                         ).astype(jnp.float32)
        additive_mask = None
        if token_mask is not None:
            additive_mask = (1.0 - token_mask[:, None, None, :]
                             .astype(jnp.float32)) * -1e9

        new_k, new_v, counts, new_h, new_tail = [], [], [], [], []
        for i, kind in enumerate(self.pattern):
            blk = f"block_{i}"
            u = norm(f"{blk}_norm")(x)
            if kind == MAMBA:
                j = len(new_h)
                state = (None if recurrent is None else
                         (recurrent["ssm"][j], recurrent["conv"][j]))
                out, (h_j, tail_j) = MambaMixer(
                    n_head=self.mamba_num_heads,
                    head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                    state_size=self.ssm_state_size,
                    conv_kernel=self.conv_kernel, chunk=self.chunk_size,
                    eps=self.norm_eps, dtype=cd, param_dtype=pd,
                    name=f"{blk}_mixer")(u, state, real)
                new_h.append(h_j)
                new_tail.append(tail_j)
            elif kind == CONV:
                tail = (None if recurrent is None
                        else recurrent["conv"][len(new_tail)])
                out, tail_j = ShortConv(
                    kernel_size=self.conv_kernel, dtype=cd, param_dtype=pd,
                    name=f"{blk}_conv")(u, tail, real)
                new_tail.append(tail_j)
            elif kind == ATTENTION:
                j = len(new_k)
                with jax.named_scope("attn.full"):
                    q = dense(h * hd, f"{blk}_q")(u).reshape(b, t, h, hd)
                    k = dense(g * hd, f"{blk}_k")(u).reshape(b, t, g, hd)
                    v = dense(g * hd, f"{blk}_v")(u).reshape(b, t, g, hd)
                    if self.qk_norm:
                        q = norm(f"{blk}_q_norm")(q)
                        k = norm(f"{blk}_k_norm")(k)
                    if self.rope_theta is not None:
                        q = rotary(q, positions, self.rope_theta)
                        k = rotary(k, positions, self.rope_theta)
                    new_k.append(k.astype(jnp.float32))
                    new_v.append(v.astype(jnp.float32))
                    a = attend(q, k, v, layer=j, mask=additive_mask,
                               ctx_k=ctx_k, ctx_v=ctx_v, ctx_len=ctx_len,
                               kv_pool=kv_pool, kv_scale=kv_scale,
                               block_tables=block_tables, impl=impl,
                               compute_dtype=cd)
                    out = dense(self.hidden_size, f"{blk}_o")(
                        a.reshape(b, t, h * hd).astype(cd))
            elif kind == DENSE:
                with jax.named_scope("ffn.dense"):
                    out = GatedMLP(self.intermediate_size, dtype=cd,
                                   param_dtype=pd, name=f"{blk}_ffn")(u)
            else:
                out, n_tokens = ExpertLayer(
                    num_experts=self.num_experts, experts_held=self.held,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size,
                    scale=self.routed_scaling_factor,
                    norm_topk_prob=self.norm_topk_prob,
                    num_shared_experts=self.num_shared_experts,
                    gated=self.moe_gated, latent=self.moe_latent_size,
                    shared_width=self.moe_shared_expert_intermediate_size,
                    dtype=cd, param_dtype=pd, name=f"{blk}_moe")(
                        u, token_mask)
                counts.append(n_tokens)
            x = x + out.astype(jnp.float32)

        if counts:
            self.sow(MOE_COUNTS, "tokens", jnp.stack(counts),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        logits = dense(self.vocab, "lm_head")(norm("final_norm")(x))
        kv_shape = (0, b, t, g, hd)
        out = (logits.astype(jnp.float32),
               jnp.stack(new_k) if new_k else jnp.zeros(kv_shape),
               jnp.stack(new_v) if new_v else jnp.zeros(kv_shape))
        if not new_tail:    # no state layer: `DecoderLM`'s contract
            return out
        return out + ({"ssm": tuple(new_h), "conv": tuple(new_tail)},)
