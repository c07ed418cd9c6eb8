"""Causal decoder LM for the generation engine.

A small GPT-style stack (token+position embeds, post-LN blocks like
`keras.layers.self_attention.TransformerBlock`, tied-free Dense head)
whose attention routes through `ops.attention` in EVERY mode: full
causal self-attention for prefill, `paged_decode_attention` (the
Pallas paged kernel / its bit-matching XLA fallback) for decode over
the block pool, and the legacy concat read path (`ctx_k/ctx_v`) kept
as the parity oracle.  Every call also RETURNS the new tokens'
per-layer keys/values — the model never WRITES the paged pool; the
engine quantizes (int8 mode) and scatters them into block slots
outside (model.py stays pure, paging stays in engine.py).

compute_dtype defaults to float32 so KV-cached decode matches the
full-sequence recompute to tight fp tolerance (tested); serve bf16 on a
real TPU by passing compute_dtype=jnp.bfloat16.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    paged_decode_attention,
    paged_verify_attention,
)
from analytics_zoo_tpu.ops.normalization import LayerNorm


class CausalLM(nn.Module):
    """input_ids/positions [batch, t] -> (logits [batch, t, vocab],
    new_k, new_v [n_block, batch, t, heads, head_dim]).

    Prefill: pass `token_mask` [batch, t] (1 = real token) and no ctx —
    full causal attention over the (bucket-padded) prompt.
    Paged decode (t == 1): pass `kv_pool` [n_block, 2, num_blocks,
    block_size, heads * head_dim] (the engine's pool in the form it is
    stored in, heads merged into each token's row — the block view of
    kv_cache.py, a bitcast), `block_tables` [batch, max_blocks],
    `ctx_len` [batch] — and `kv_scale` [n_block, 2, num_blocks,
    block_size] when the pool is int8 — each new token attends over
    [its block table ; itself] through
    `ops.attention.paged_decode_attention`, which is handed the pool
    WHOLE and this block's index: slicing `kv_pool[i]` here would make
    XLA materialize a copy of every layer's K and V each step.
    Paged verify (t > 1, same args): speculative decoding's scoring
    pass — each lane's pending token plus its drafted tokens attend
    causally over [its block table ; themselves] through
    `ops.attention.paged_verify_attention` (the chunk-step read
    semantics over the pool).  The t == 1 branch is untouched, so the
    compiled decode program is identical with speculation armed.
    Concat decode (parity oracle) AND chunked/prefix-cached prefill:
    pass `ctx_k`/`ctx_v` [n_block, batch, ctx, heads, head_dim]
    (gathered from the pool) and `ctx_len` [batch].  The ctx read path
    is causal over [cached context ; new tokens], so it serves both
    t == 1 decode and t > 1 prefill chunks whose prefix KV is already
    in the pool (the engine's chunk step — engine.py)
    with identical semantics.

    `paged_attention_impl` pins the paged dispatch ("pallas"/"xla";
    None = auto: Pallas on TPU) — tests use "pallas" to drive the real
    kernel through the CPU interpreter."""

    vocab: int
    hidden_size: int = 64
    n_head: int = 4
    n_block: int = 2
    intermediate_size: int = 256
    max_position_len: int = 2048
    compute_dtype: jnp.dtype = jnp.float32
    paged_attention_impl: Optional[str] = None

    @nn.compact
    def __call__(self, input_ids, positions, token_mask=None,
                 ctx_k=None, ctx_v=None, ctx_len=None,
                 kv_pool=None, kv_scale=None, block_tables=None):
        b, t = input_ids.shape
        h = self.n_head
        hd = self.hidden_size // h
        x = nn.Embed(self.vocab, self.hidden_size,
                     name="token_embed")(input_ids.astype(jnp.int32))
        x = x + nn.Embed(self.max_position_len, self.hidden_size,
                         name="position_embed"
                         )(positions.astype(jnp.int32))
        x = LayerNorm(name="embed_ln")(x)

        additive_mask = None
        if token_mask is not None:
            additive_mask = (1.0 - token_mask[:, None, None, :]
                             .astype(jnp.float32)) * -1e9

        new_k, new_v = [], []
        for i in range(self.n_block):
            blk = f"block_{i}"
            qkv = nn.Dense(3 * self.hidden_size, dtype=self.compute_dtype,
                           name=f"{blk}_qkv")(x)
            q, k, v = (a.reshape(b, t, h, hd)
                       for a in jnp.split(qkv, 3, axis=-1))
            # the pool holds f32 (or the cache dtype): hand back the
            # raw per-token keys/values before attention consumes them
            new_k.append(k.astype(jnp.float32))
            new_v.append(v.astype(jnp.float32))
            if kv_pool is not None and t == 1:
                a = paged_decode_attention(
                    q[:, 0], k[:, 0], v[:, 0], kv_pool, block_tables,
                    ctx_len, layer=i, kv_scale=kv_scale,
                    impl=self.paged_attention_impl or "auto",
                    compute_dtype=self.compute_dtype)[:, None]
            elif kv_pool is not None:
                a = paged_verify_attention(
                    q, k, v, kv_pool, block_tables, ctx_len, layer=i,
                    kv_scale=kv_scale,
                    impl=self.paged_attention_impl or "auto",
                    compute_dtype=self.compute_dtype)
            elif ctx_k is not None:
                a = dot_product_attention(
                    q, k, v, compute_dtype=self.compute_dtype,
                    ctx_k=ctx_k[i], ctx_v=ctx_v[i], ctx_len=ctx_len)
            else:
                a = dot_product_attention(
                    q, k, v, mask=additive_mask, causal=True,
                    compute_dtype=self.compute_dtype)
            a = nn.Dense(self.hidden_size, dtype=self.compute_dtype,
                         name=f"{blk}_proj")(
                             a.reshape(b, t, self.hidden_size))
            x = LayerNorm(name=f"{blk}_ln1")(x + a.astype(x.dtype))
            f = nn.Dense(self.intermediate_size,
                         dtype=self.compute_dtype,
                         name=f"{blk}_fc1")(x)
            f = nn.gelu(f)
            f = nn.Dense(self.hidden_size, dtype=self.compute_dtype,
                         name=f"{blk}_fc2")(f)
            x = LayerNorm(name=f"{blk}_ln2")(x + f.astype(x.dtype))

        logits = nn.Dense(self.vocab, name="lm_head")(x)
        return (logits.astype(jnp.float32),
                jnp.stack(new_k), jnp.stack(new_v))
