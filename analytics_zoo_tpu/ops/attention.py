"""Attention ops.

`dot_product_attention` is the reference implementation every attention
consumer in the framework calls; it computes the [b, h, q, k] score matrix
with bfloat16 einsums (MXU-friendly) and float32 softmax accumulation.
A pallas flash-attention kernel (tiled online-softmax, no materialized
score matrix) can replace it for long sequences — same signature — via
`use_flash=True` once `analytics_zoo_tpu.ops.pallas.flash_attention` lands.

`paged_decode_attention` is the serving decode path (q_len=1 per lane
against a paged KV block pool): the ONE dispatch point the generation
engine routes through (enforced by scripts/check_kernel_dispatch.py),
picking the Pallas paged kernel (block-table gather inside the kernel,
ops/pallas/paged_attention.py) on TPU and an XLA fallback that
bit-matches the gather+concat-attend path everywhere else.

`paged_verify_attention` is its q_len>1 sibling for speculative
decoding's verify step (serving/generation/speculation.py): each lane's
pending token plus its k drafted tokens attend causally over the lane's
paged context in one call.  It reuses the decode path's XLA fallback
(block-table gather + the `dot_product_attention` ctx read path) on
every backend today — the dedicated q_len>1 Pallas kernel is future
TPU-round work, and the gather path is what the CPU parity tests pin.

`latent_decode_attention` is the decode path of a model that caches ONE
row a token (latent attention in its absorbed form,
serving/generation/decoder.py): each lane's heads all read the same
cached rows, as key over the whole row and as value over its leading
columns.  The same kind of dispatch point: the Pallas latent kernel on
a TPU, the gather by table and the same mathematics in XLA elsewhere.
`latent_paged_context` is the gather alone, for the forms that expand
the rows (verify).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def dot_product_attention(q, k, v, mask=None, causal: bool = False,
                          dropout_rate: float = 0.0, dropout_rng=None,
                          compute_dtype=jnp.bfloat16,
                          ctx_k=None, ctx_v=None, ctx_len=None,
                          window: Optional[int] = None,
                          scale: Optional[float] = None):
    """q, k, v: [batch, time, heads, head_dim] (BTHD).  `mask` is an
    additive float mask broadcastable to [batch, heads, q_time, k_time].
    Returns [batch, time, heads, head_dim].

    KV-cache read path (autoregressive decoding): `ctx_k`/`ctx_v`
    [batch, ctx, heads, head_dim] hold the cached keys/values of the
    tokens PRECEDING q — gathered from a paged pool and padded with
    garbage beyond `ctx_len` [batch] (int32 valid lengths; cached
    position j lives at column j).  q/k/v then carry only the NEW
    tokens, whose absolute positions are ctx_len..ctx_len+time-1, and
    attention runs causally over [ctx ; new] with the padding columns
    masked out: decoding with time=1 is O(ctx) instead of the O(ctx^2)
    full recompute.  `mask`/`causal` are ignored on this path (causal
    semantics are implied); dropout is unsupported (decode is
    inference-only).

    Grouped queries and windows: k/v (and ctx_k/ctx_v) may carry FEWER
    heads than q — query head i then reads KV head i // (q heads / KV
    heads) — and `window` w hides every key more than w - 1 positions
    behind its query (key j is visible to query p iff j <= p and
    p - j < w).  Either one takes `_grouped_attention`, the same
    mathematics with the KV heads kept as an axis of their own; plain
    multi-head attention without a window runs the code below as it
    always has.

    `scale` multiplies the scores in place of head_dim ** -0.5 (a
    rotary scaling's factor rides on it).  A key wider than the value
    (latent attention expanded: a rotary part behind the key alone)
    takes `_blocked_attention`: the same mathematics in blocks of
    query rows, each over the keys it can see, so that no [t, t] array
    of scores exists at any prompt length."""
    b, t, h, d = q.shape
    if v.shape[-1] != d:
        if dropout_rate > 0.0 or window is not None or k.shape[2] != h:
            raise ValueError("a key wider than the value comes with "
                             "neither dropout, a window nor grouped "
                             "heads")
        return _blocked_attention(
            q, k, v, mask=mask, causal=causal,
            compute_dtype=compute_dtype, ctx_k=ctx_k, ctx_v=ctx_v,
            ctx_len=ctx_len, scale=scale)
    if k.shape[2] != h or window is not None:
        if dropout_rate > 0.0:
            raise ValueError("dropout is not supported with grouped "
                             "query heads or a window")
        return _grouped_attention(
            q, k, v, mask=mask, causal=causal,
            compute_dtype=compute_dtype, ctx_k=ctx_k, ctx_v=ctx_v,
            ctx_len=ctx_len, window=window, scale=scale)
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q = q.astype(compute_dtype)
    k = k.astype(compute_dtype)
    v = v.astype(compute_dtype)

    if ctx_k is not None:
        if dropout_rate > 0.0:
            raise ValueError("dropout is not supported on the KV-cache "
                             "read path (decode is inference-only)")
        c = ctx_k.shape[1]
        ctx_len = jnp.asarray(ctx_len, jnp.int32)
        keys = jnp.concatenate([ctx_k.astype(compute_dtype), k], axis=1)
        vals = jnp.concatenate([ctx_v.astype(compute_dtype), v], axis=1)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, keys)
                  .astype(jnp.float32) * scale)          # [b, h, t, c+t]
        col = jnp.arange(c + t)[None, :]                 # [1, c+t]
        # absolute key positions: cached col j sits at position j; new
        # col c+j2 is the token at ctx_len+j2
        k_pos = jnp.where(col < c, col, ctx_len[:, None] + (col - c))
        q_pos = ctx_len[:, None] + jnp.arange(t)[None]   # [b, t]
        valid = ((k_pos[:, None, :] <= q_pos[:, :, None])
                 & ((col >= c) | (col < ctx_len[:, None]))[:, None, :])
        scores = jnp.where(valid[:, None], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd",
                         probs.astype(compute_dtype), vals)
        return out.astype(jnp.float32)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal_mask[None, None], scores, -1e9)
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(compute_dtype), v)
    return out.astype(jnp.float32)


def _grouped_attention(q, k, v, *, mask, causal, compute_dtype, ctx_k,
                       ctx_v, ctx_len, window, scale=None):
    """`dot_product_attention` for g KV heads under h = g * r query
    heads and/or a window: q [b, t, h, d], k/v [b, t, g, d] (and
    ctx_k/ctx_v [b, c, g, d]).  Scores are [b, g, r, q, k] — a K or V
    head is read once for its r queries, never repeated in memory."""
    b, t, h, d = q.shape
    g = k.shape[2]
    if h % g:
        raise ValueError(f"{h} query heads do not divide over {g} KV "
                         f"heads")
    r = h // g
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q = q.astype(compute_dtype).reshape(b, t, g, r, d)
    keys, vals = k.astype(compute_dtype), v.astype(compute_dtype)
    if ctx_k is not None:
        c = ctx_k.shape[1]
        ctx_len = jnp.asarray(ctx_len, jnp.int32)
        keys = jnp.concatenate([ctx_k.astype(compute_dtype), keys], 1)
        vals = jnp.concatenate([ctx_v.astype(compute_dtype), vals], 1)
        col = jnp.arange(c + t)[None, :]
        k_pos = jnp.where(col < c, col, ctx_len[:, None] + (col - c))
        q_pos = ctx_len[:, None] + jnp.arange(t)[None]
        valid = ((k_pos[:, None, :] <= q_pos[:, :, None])
                 & ((col >= c) | (col < ctx_len[:, None]))[:, None, :])
    else:
        k_pos = q_pos = jnp.arange(t)[None]
        valid = (k_pos[:, None, :] <= q_pos[:, :, None]) if causal \
            else jnp.ones((1, t, t), bool)
    if window is not None:
        valid = valid & (q_pos[:, :, None] - k_pos[:, None, :]
                         < int(window))
    scores = (jnp.einsum("bqgrd,bkgd->bgrqk", q, keys)
              .astype(jnp.float32) * scale)
    scores = jnp.where(valid[:, None, None], scores, -1e9)
    if mask is not None and ctx_k is None:
        mask = jnp.asarray(mask)
        scores = scores + (mask[:, :, None] if mask.shape[1] == 1 else
                           mask.reshape(mask.shape[0], g, r,
                                        *mask.shape[2:]))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(compute_dtype),
                     vals)
    return out.reshape(b, t, h, d).astype(jnp.float32)


#: query rows a block of `_blocked_attention` holds: at 64 heads and
#: 4,096 keys its float32 scores are 0.54 GB
QUERY_BLOCK = 512


def _blocked_attention(q, k, v, *, mask, causal, compute_dtype, ctx_k,
                       ctx_v, ctx_len, scale, q_block: int = QUERY_BLOCK):
    """`dot_product_attention` for keys [b, t, h, dk] beside values
    [b, t, h, dv], dk != dv, in blocks of `q_block` query rows.  Without
    a context a causal block reads the keys up to its own last row and
    no further (half the products of the whole square); with one
    (`ctx_k` [b, c, h, dk], `ctx_v` [b, c, h, dv], `ctx_len`) every
    block reads the cached columns, masked past `ctx_len`, and the new
    ones up to its last row.  Scores and softmax in float32."""
    b, t, h, dk = q.shape
    if scale is None:
        scale = dk ** -0.5
    q = q.astype(compute_dtype)
    keys, vals = k.astype(compute_dtype), v.astype(compute_dtype)
    c = 0
    if ctx_k is not None:
        c = ctx_k.shape[1]
        ctx_len = jnp.asarray(ctx_len, jnp.int32)
        keys = jnp.concatenate([ctx_k.astype(compute_dtype), keys], 1)
        vals = jnp.concatenate([ctx_v.astype(compute_dtype), vals], 1)
        causal, mask = True, None
    out = []
    for start in range(0, t, q_block):
        stop = min(start + q_block, t)
        seen = c + stop if causal else c + t
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, start:stop], keys[:, :seen],
            preferred_element_type=jnp.float32) * scale  # [b, h, rows, seen]
        col = jnp.arange(seen)[None, :]
        rows = jnp.arange(start, stop)[None, :]
        if ctx_k is not None:
            # cached column j is position j; new column c + j is the
            # token at ctx_len + j
            valid = (((col < c) & (col < ctx_len[:, None]))[:, None, :]
                     | ((col >= c)[:, None, :]
                        & (col - c <= rows[..., None])))
        elif causal:
            valid = col[:, None, :] <= rows[..., None]
        else:
            valid = jnp.ones((1, stop - start, seen), bool)
        scores = jnp.where(valid[:, None], scores, -1e9)
        if mask is not None:
            scores = scores + jnp.asarray(mask)[..., :seen]
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              probs.astype(compute_dtype), vals[:, :seen]))
    out = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
    return out.astype(jnp.float32)


def _paged_context(kv_pool, kv_scale, layer, which, block_tables, h):
    """Every lane's table blocks of `layer`'s K (which=0) or V (1),
    gathered out of the pool's block view as a [S, C, h, d] context and
    dequantized when scales ride along.  Heads are split out of what
    was gathered, never of the pool; a traced `layer` is one more index
    of the same gather."""
    blocks = kv_pool[layer, which, block_tables]     # [S, MB, bs, h*d]
    s, mb, bs, hd = blocks.shape
    ctx = blocks.reshape(s, mb * bs, h, hd // h)
    if kv_scale is not None:
        scale = kv_scale[layer, which, block_tables].astype(jnp.float32)
        ctx = (ctx.astype(jnp.float32)
               * scale.reshape(s, mb * bs)[:, :, None, None])
    return ctx


def _resolved(impl: str) -> str:
    """`impl` with "auto" settled: the Pallas kernel on a TPU, the XLA
    form elsewhere."""
    if impl != "auto":
        return impl
    try:
        platform = jax.default_backend()
    except Exception:
        platform = "cpu"
    return "pallas" if platform == "tpu" else "xla"


def paged_decode_attention(q, new_k, new_v, kv_pool, block_tables,
                           ctx_len, *, layer: int, kv_scale=None,
                           impl: str = "auto",
                           block_gather: Optional[int] = None,
                           compute_dtype=jnp.float32,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None):
    """Decode-step attention of one new token per lane over its paged
    KV cache — the generation engine's hot path (docs/kernels.md,
    docs/generation.md).

    q / new_k / new_v: [S, heads, head_dim] — lane S's pending token
    (it attends to itself in addition to the cache).
    kv_pool: [n_layers, 2, num_blocks, block_size, heads * head_dim] —
    the WHOLE paged pool in the form it is stored in (the block view of
    `PagedKVCache.kv`; block 0 reserved as the null block), `layer` the
    layer to read — a Python int, or a traced int32 scalar (a looped
    decoder's pool slot, serving/generation/decoder.py), which the
    kernel takes by scalar prefetch and the XLA form as one more index
    of its gather: no per-layer slice of the pool is ever an operand.  int8 pools pass `kv_scale` [n_layers, 2, num_blocks,
    block_size] f32 per-token-slot dequant scales
    (serving/generation/kv_cache.py's quantized mode).
    block_tables: [S, max_blocks] int32; ctx_len: [S] int32 — cached
    position p of lane s lives at block_tables[s, p // bs], slot
    p % bs; entries past ctx_len are masked (garbage-safe, so
    null-table padding and mid-preemption lanes cost nothing).
    Returns [S, heads, head_dim] float32.

    Grouped queries: new_k / new_v (and the pool's rows) may carry g KV
    heads under q's h = g * q_per_kv query heads; query head i reads
    KV head i // q_per_kv, and one staged K/V block serves all the
    q_per_kv queries of a KV head.  `window` w (a window layer): the
    token at position ctx_len sees itself and the w - 1 cached
    positions before it; the kernel walks only the table's blocks that
    reach into them, so what a window layer READS stops growing with
    the context (the blocks behind the window stay allocated).

    impl: "auto" (Pallas on TPU, XLA elsewhere) | "pallas" | "xla".
    The XLA fallback gathers the context and runs the exact
    `dot_product_attention` KV-cache read path (concat-attend) — the
    pre-paged-kernel decode path, bit for bit, which is what the
    parity tests pin the kernel against.  `block_gather=None` asks the
    autotuner (ops/tuning, key family "paged_decode") for the Pallas
    kernel's gather width; `interpret=True` runs the kernel on the CPU
    interpreter (tests)."""
    s, h, d = q.shape
    g = new_k.shape[1]                 # KV heads (= h without grouping)
    bs = kv_pool.shape[3]
    impl = _resolved(impl)
    if impl == "xla":
        out = dot_product_attention(
            q[:, None], new_k[:, None], new_v[:, None],
            compute_dtype=compute_dtype,
            ctx_k=_paged_context(kv_pool, kv_scale, layer, 0,
                                 block_tables, g),
            ctx_v=_paged_context(kv_pool, kv_scale, layer, 1,
                                 block_tables, g),
            ctx_len=ctx_len, window=window)
        return out[:, 0]
    if impl != "pallas":
        raise ValueError(f"unknown paged_decode_attention impl "
                         f"{impl!r}; use 'auto', 'pallas' or 'xla'")
    from analytics_zoo_tpu.ops.pallas.paged_attention import (
        paged_decode_pallas,
        tuned_paged_block_gather,
    )
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if block_gather is None:
        block_gather = tuned_paged_block_gather(
            bs, s, g, d, kv_pool.dtype, mb=block_tables.shape[1])

    def kernel(q, new_k, new_v, kv_pool, block_tables, ctx_len,
               kv_scale=None):
        return paged_decode_pallas(
            q, new_k, new_v, kv_pool, block_tables, ctx_len,
            layer=layer, head_dim=d, kv_scale=kv_scale,
            block_gather=block_gather, interpret=interpret,
            q_per_kv=h // g, window=window)

    # the kernel's rows are the pool's: heads merged into h*d columns
    args = (q.reshape(s, h * d), new_k.reshape(s, g * d),
            new_v.reshape(s, g * d), kv_pool, block_tables, ctx_len)
    if kv_scale is not None:
        args += (kv_scale,)
    from analytics_zoo_tpu.parallel.sharding import (
        mesh_axis_size, shard_map_compat, traced_mesh)
    mesh = traced_mesh()
    if mesh is not None:
        # a program over several devices (the tp engine) carries the
        # kernel in a shard_map: attention is head-local and a head
        # shard is a contiguous slice of the merged axis, so each
        # device runs it over its own columns of the pool
        # (serving/distributed/tp.py) with the tables, lengths and
        # per-token scales whole
        n_tp = mesh_axis_size("tp", mesh)
        tp = "tp" if n_tp > 1 and g % n_tp == 0 else None
        lane, pool = P(None, tp), P(None, None, None, None, tp)
        kernel = shard_map_compat(
            kernel, mesh=mesh,
            in_specs=(lane,) * 3 + (pool,) + (P(),) * (len(args) - 4),
            out_specs=lane)
    return kernel(*args).reshape(s, h, d)


def paged_verify_attention(q, new_k, new_v, kv_pool, block_tables,
                           ctx_len, *, layer: int, kv_scale=None,
                           impl: str = "auto",
                           compute_dtype=jnp.float32,
                           window: Optional[int] = None):
    """Verify-step attention of q_len>1 new tokens per lane over its
    paged KV cache — speculative decoding's scoring pass
    (serving/generation/speculation.py; docs/generation.md).

    q / new_k / new_v: [S, T, heads, head_dim] — lane s's pending token
    followed by its T-1 drafted tokens at absolute positions
    ctx_len[s]..ctx_len[s]+T-1; they attend causally over
    [cached context ; themselves], exactly the chunk-prefill read
    semantics (`dot_product_attention`'s ctx path).
    kv_pool / layer / block_tables / ctx_len / kv_scale: as in
    `paged_decode_attention`, grouped KV heads and `window` too.
    Returns [S, T, heads, head_dim] float32.

    impl: "auto" | "pallas" | "xla" — all three currently run the XLA
    gather path (the decode fallback generalized to T queries); a
    dedicated q_len>1 Pallas verify kernel is future TPU-round work,
    so engines pinned to `paged_attention_impl="pallas"` verify
    through the same fallback their CPU parity tests exercise."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown paged_verify_attention impl "
                         f"{impl!r}; use 'auto', 'pallas' or 'xla'")
    g = new_k.shape[2]
    return dot_product_attention(
        q, new_k, new_v, compute_dtype=compute_dtype,
        ctx_k=_paged_context(kv_pool, kv_scale, layer, 0, block_tables,
                             g),
        ctx_v=_paged_context(kv_pool, kv_scale, layer, 1, block_tables,
                             g),
        ctx_len=ctx_len, window=window)


def latent_paged_context(kv_pool, block_tables, *, layer: int, width: int):
    """Every lane's table blocks of `layer` of a latent pool (its block
    view, [n_layers, 1, num_blocks, block_size, columns as stored])
    gathered as cached rows [S, C, width]: the read of the forms that
    expand the rows (verify).  The stored padding is cut off what was
    gathered, never of the pool."""
    blocks = kv_pool[layer, 0, block_tables]          # [S, MB, bs, W]
    s, mb, bs, _ = blocks.shape
    return blocks.reshape(s, mb * bs, -1)[..., :width]


def latent_decode_attention(q_abs, new_row, kv_pool, block_tables, ctx_len,
                            *, layer: int, value_width: int, scale: float,
                            impl: str = "auto",
                            block_gather: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Decode-step attention of one new token a lane over a pool of ONE
    row a token — latent attention in its absorbed form
    (serving/generation/decoder.py, docs/generation.md).

    q_abs: [S, heads, width] — each head's query in the cached row's
    space (the key up-projection absorbed into the no-position part,
    the rotated part behind it).  new_row: [S, width], the pending
    token's own row (it attends to itself).  kv_pool: the latent pool's
    block view [n_layers, 1, num_blocks, block_size, columns as stored
    >= width], `layer` (static) the layer read; block_tables / ctx_len
    as `paged_decode_attention`'s.  For head h and cached row c_j:

        s_j = scale * q_abs[h] . c_j
        out[h] = sum_j softmax(s)_j c_j[:value_width]

    — one row read once serves as key (all of it) and as value (its
    first `value_width` columns) for every head.  Returns [S, heads,
    value_width] float32; the caller projects it up to the value heads.

    impl: "auto" (the Pallas latent kernel on a TPU, XLA elsewhere) |
    "pallas" | "xla".  The XLA form gathers each lane's rows by table
    and computes the same sums with a float32 softmax; it is what the
    kernel is held to in the interpreter (`interpret=True`).
    `block_gather`: pool blocks a grid step of the kernel reads (None:
    the kernel's default)."""
    s, h, width = q_abs.shape
    stored = kv_pool.shape[-1]
    impl = _resolved(impl)
    if impl == "xla":
        cd = q_abs.dtype
        ctx = latent_paged_context(kv_pool, block_tables, layer=layer,
                                   width=width).astype(cd)   # [S, C, W]
        rows = jnp.concatenate([ctx, new_row[:, None].astype(cd)], 1)
        scores = jnp.einsum("shw,scw->shc", q_abs, rows,
                            preferred_element_type=jnp.float32) * scale
        c = ctx.shape[1]
        col = jnp.arange(c + 1)[None, :]
        valid = (col < jnp.asarray(ctx_len, jnp.int32)[:, None]) \
            | (col == c)
        probs = jax.nn.softmax(
            jnp.where(valid[:, None], scores, -1e9), axis=-1)
        out = jnp.einsum("shc,scv->shv", probs.astype(cd),
                         rows[..., :value_width])
        return out.astype(jnp.float32)
    if impl != "pallas":
        raise ValueError(f"unknown latent_decode_attention impl "
                         f"{impl!r}; use 'auto', 'pallas' or 'xla'")
    from analytics_zoo_tpu.ops.pallas.paged_attention import (
        latent_decode_pallas)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    # the kernel's rows are the pool's: padded to the stored columns
    # (the pool's own padding is zeros: a query's meets nothing)
    pad = ((0, 0),) * 2 + ((0, stored - width),)
    return latent_decode_pallas(
        jnp.pad(q_abs, pad), jnp.pad(new_row, pad[1:]), kv_pool,
        block_tables, ctx_len, layer=layer, value_width=value_width,
        scale=scale, block_gather=block_gather, interpret=interpret)
