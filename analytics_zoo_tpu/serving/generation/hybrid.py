"""A hybrid decoder for the generation engine: ONE sub-layer a layer,
its kind read from a pattern string (`hybrid_override_pattern`, the
`nemotron_h` family's key):

  * `M` — a Mamba-2 state-space mixer (`ops.ssm`): `in_proj` to
    `[z | xBC | dt]`, a causal depthwise convolution over `xBC`, the
    selective scan over `[x | B | C]` with a per-head decay, the output
    gated by `silu(z)` BEFORE a group-wise RMSNorm, `out_proj`;
  * `*` — causal grouped-query attention without positions and without
    q/k norms, through `ops.attention` in every mode;
  * `E` — `decoder.ExpertLayer` in its latent, un-gated form: sigmoid
    router over all experts, squared-ReLU experts in a latent space,
    a squared-ReLU shared expert at the hidden size.

Every layer is `x <- x + Mixer(RMSNorm(x))`; the residual stream stays
float32; the head is untied.

Two kinds of state.  The `*` layers' keys and values live in the paged
pool, a row a token, found through a block table: `kv_geometry()`
counts those layers only.  Each `M` layer keeps a FIXED-SIZE state a
lane — the scan's `H` [heads, head_dim, state] in float32 and the last
`conv_kernel - 1` rows of the pre-convolution `xBC` — which no block
table finds: it lives in the engine's recurrent pool, addressed by the
lane's slot (`state_geometry()`; kv_cache.RecurrentStatePool).

The call contract is `CausalLM`'s with one more argument and one more
result: `recurrent` = {"ssm": one [batch, heads, head_dim, state]
float32 array a state layer, "conv": one [conv_kernel - 1, batch,
channels] array a state layer} is the state BEFORE `input_ids` (None: a
sequence's start), and the fourth result the state after them, in the
same form.  With t = 1 (a decode round, batch = every lane) a row whose
`token_mask` is false hands its state back untouched; with t > 1 (a
prefill) the state is the one after each row's `token_mask` real
tokens — a bucket's padding does not advance it.  A pattern without
`M` takes no `recurrent` and hands back three results, as `DecoderLM`.
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
    attend,
)

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
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
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 0
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
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
        unknown = set(self.pattern) - {MAMBA, ATTENTION, EXPERTS}
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} KV heads")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} state-space heads "
                             f"over {self.n_groups} groups")

    @classmethod
    def from_config(cls, config, **kw) -> "HybridLM":
        """The module a Hugging-Face-style `config.json` mapping
        describes, by `nemotron_h`'s keys; `experts_held` = [first id,
        count] is this chip's share of the routed experts (all of them
        when absent).  `kw`: the fields no `config.json` has."""
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
        """(state layers, per-lane shape and dtype of the scan's state,
        of the convolution's tail): what the recurrent pool holds a
        lane, or None for a pattern without `M`."""
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
            elif kind == ATTENTION:
                j = len(new_k)
                with jax.named_scope("attn.full"):
                    q = dense(h * hd, f"{blk}_q")(u).reshape(b, t, h, hd)
                    k = dense(g * hd, f"{blk}_k")(u).reshape(b, t, g, hd)
                    v = dense(g * hd, f"{blk}_v")(u).reshape(b, t, g, hd)
                    new_k.append(k.astype(jnp.float32))
                    new_v.append(v.astype(jnp.float32))
                    a = attend(q, k, v, layer=j, mask=additive_mask,
                               ctx_k=ctx_k, ctx_v=ctx_v, ctx_len=ctx_len,
                               kv_pool=kv_pool, kv_scale=kv_scale,
                               block_tables=block_tables, impl=impl,
                               compute_dtype=cd)
                    out = dense(self.hidden_size, f"{blk}_o")(
                        a.reshape(b, t, h * hd).astype(cd))
            else:
                out, n_tokens = ExpertLayer(
                    num_experts=self.num_experts, experts_held=self.held,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size,
                    scale=self.routed_scaling_factor,
                    norm_topk_prob=self.norm_topk_prob, gated=False,
                    latent=self.moe_latent_size,
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
        if not new_h:       # no state layer: `DecoderLM`'s contract
            return out
        return out + ({"ssm": tuple(new_h), "conv": tuple(new_tail)},)
