"""The generation engine's compiled step programs, built once.

Nothing here knows the engine, the scheduler or a request: a program's
arguments are the device state (params, pool, scales, lane state:
lane_state.py) and one upload, its results the same state advanced.
The engine's loop (engine.py) owns when each one runs and what the
host does with what it fetches.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.observability import profiling
from analytics_zoo_tpu.serving.generation import lane_state
from analytics_zoo_tpu.serving.generation.decoder import MOE_COUNTS
from analytics_zoo_tpu.serving.generation.kv_cache import (
    NULL_BLOCK,
    admit_state,
    block_view,
    gather_kv,
    pool_geometry,
    write_kv,
    write_kv_blocks,
)
from analytics_zoo_tpu.serving.generation.sampling import sample_tokens


def build_steps(model, *, block_size: int, n_head: int, quantized: bool,
                paged: bool, width: int, counted: bool,
                stateful: bool = False, tp=None, prefill_variants: int,
                verify_variants: Optional[int] = None) -> tuple:
    """The six program families the engine dispatches, jitted and
    registered with the dispatch ledger: (`prefill`, `chunk_prefill`,
    `decode`, `spec_verify`, `copy_block`, `restore_block`), the last
    None under tensor parallelism (the host tier is off there).

    `block_size`, `n_head`: the pool's block length and KV heads (the
    rows a token holds and their width follow the pool and the model:
    a latent model hands back one row a token as its `new_k`, None as
    its `new_v`, and is handed a gathered context the same way —
    kv_cache.py);
    `quantized`: an int8 pool with scales beside it; `paged`: decode
    and verify read the pool through the paged kernel (else the
    gather+concat oracle); `width`: a lane row's width
    (`LaneState.width`); `counted`: the model sows expert counts, which
    ride back as one more result (decoder.py); `stateful`: the model
    has state layers, whose recurrent state rides in the lane state as
    "recurrent" — `decode` hands the model every lane's and takes them
    back advanced, `prefill` starts from none and leaves the row's in
    its slot (hybrid.py, kv_cache.RecurrentStatePool); `tp`: the
    `TensorParallelPlacement`, or None on one device.
    `prefill_variants` / `verify_variants` are the compile budgets of
    the bucketed families (None: speculation is off)."""
    bs = block_size
    max_pos = model.max_position_len
    head_dim = pool_geometry(model)[2]
    # buffer donation lets XLA update the KV pool (and its scale
    # vectors) in place; the CPU backend ignores donation and
    # warns, so only donate off-CPU
    donate = ((1, 2) if jax.devices()[0].platform != "cpu" else ())
    # ... and the lane state with them, where a step takes it
    donate_lanes = donate and donate + (3,)
    # the block programs take the pool first
    donate_pool = (0, 1) if donate else ()

    def apply(params, *args, token_mask=None, **kw):
        # model.apply and, beside its outputs, the expert layers'
        # counts where the model has any (decoder.py sows them;
        # `token_mask` tells it which tokens are real).  A model
        # without them is called exactly as it always was.
        if counted or stateful:
            kw["token_mask"] = token_mask
        if not counted:
            return model.apply({"params": params}, *args, **kw), ()
        out, state = model.apply(
            {"params": params}, *args, mutable=[MOE_COUNTS], **kw)
        return out, (state[MOE_COUNTS]["tokens"],)

    def paged_apply(params, kv, kv_scale, tokens, pos, block_tables,
                    ctx_len, real=None, **kw):
        # the pool goes to the model whole, as its block view (a
        # bitcast — kv_cache.block_view), with each lane's block
        # table: the attention op gathers pool blocks by table
        # index itself (ops/pallas/paged_attention.py), so neither
        # a [S, C, h, d] context nor a per-layer slice of the pool
        # is ever materialized
        return apply(
            params, tokens, pos, token_mask=real,
            kv_pool=block_view(kv, bs),
            kv_scale=block_view(kv_scale, bs) if quantized else None,
            block_tables=block_tables, ctx_len=ctx_len, **kw)

    def concat_apply(params, kv, kv_scale, tokens, pos, tok_idx,
                     ctx_len, real=None, **kw):
        # the context gathered out of the pool by token slot
        # (kv_cache.gather_kv) and attended by the concat read
        # path: the parity oracle, and the chunk step's read
        ctx_k, ctx_v = gather_kv(kv, kv_scale, tok_idx, n_head, head_dim)
        return apply(params, tokens, pos, token_mask=real,
                     ctx_k=ctx_k, ctx_v=ctx_v, ctx_len=ctx_len, **kw)

    def each(cut, *new):
        # `cut` of each kind of row a model call made (`new_v` is None
        # where a token holds one row)
        return tuple(None if x is None else cut(x) for x in new)

    def prefill(params, kv, kv_scale, lanes, request):
        # request = [slot | the lane's row | tokens, bucket-padded]
        # (lane_state.split_request): writes KV for the row's
        # `length` real tokens, samples the first new token from
        # the last real position with the state's key, and leaves
        # the row — that token pending, the lane stopped if it was
        # its only one or its `eos` — in the lane's place
        slot, row, tokens = lane_state.split_request(request, width)
        _, length, _, temperature, top_k, block_table = \
            lane_state.fields(row)
        B = tokens.shape[1]
        pos = jnp.minimum(jnp.arange(B), max_pos - 1)
        token_mask = (jnp.arange(B) < length)[None]
        (logits, new_k, new_v, *fresh), counts = apply(
            params, tokens, pos[None], token_mask=token_mask)
        # a prompt starts its first block, so its rows go in by whole
        # blocks (kv_cache.write_kv_blocks); those wholly past
        # `length` name the null block, as padding rows do elsewhere
        # (an index past a short table is clamped, then masked)
        nth = jnp.arange(-(-B // bs))
        blocks = jnp.where(nth * bs < length, block_table[nth],
                           NULL_BLOCK)
        kv, kv_scale = write_kv_blocks(
            kv, kv_scale, blocks,
            *each(lambda x: x[:, 0], new_k, new_v), bs)
        last = logits[0, length - 1]
        rng, sub = jax.random.split(lanes["rng"])
        nxt = sample_tokens(last[None], sub, temperature[None],
                            top_k[None])[0]
        out = {"rows": lane_state.admitted(lanes["rows"], slot, row, nxt),
               "rng": rng}
        if stateful:
            # the state after the row's `length` real tokens, in the
            # lane's slot: what the slot held is gone
            out["recurrent"] = admit_state(lanes["recurrent"], slot,
                                           fresh[0])
        return (kv, kv_scale, nxt, last, out) + counts

    def decode(params, kv, kv_scale, lanes, patch):
        # ONE static-shape step for all lanes, over the resident
        # rows once `patch` (the rows the host changed, or none)
        # is applied: tokens [S] (each lane's pending token),
        # ctx_len [S] (= its position), block_tables [S,
        # max_blocks], active [S] lane mask.  Hands the rows back
        # advanced — a lane that sampled its last token or its `eos`
        # inactive, so the next round may be enqueued before this
        # one's tokens are fetched — and the key split
        rows = lane_state.patched(lanes["rows"], patch)
        tokens, ctx_len, active, temperature, top_k, block_tables \
            = lane_state.fields(rows)
        S, MB = block_tables.shape
        pos = jnp.minimum(ctx_len, max_pos - 1)
        # every lane's recurrent state goes in and comes back: a live
        # lane's advanced by its token, a dead lane's as it was
        carried = {"recurrent": lanes["recurrent"]} if stateful else {}
        if paged:
            (logits, new_k, new_v, *stepped), counts = paged_apply(
                params, kv, kv_scale, tokens[:, None], pos[:, None],
                block_tables, ctx_len, active[:, None], **carried)
        else:
            tok_idx = (block_tables[:, :, None] * bs
                       + jnp.arange(bs)[None, None, :]
                       ).reshape(S, -1)
            (logits, new_k, new_v, *stepped), counts = concat_apply(
                params, kv, kv_scale, tokens[:, None], pos[:, None],
                tok_idx, ctx_len, active[:, None], **carried)
        dest = block_tables[jnp.arange(S), ctx_len // bs] * bs \
            + ctx_len % bs
        dest = jnp.where(active, dest, 0)   # dead lanes → null block
        kv, kv_scale = write_kv(
            kv, kv_scale, dest,
            *each(lambda x: x[:, :, 0], new_k, new_v))
        last = jnp.where(active[:, None], logits[:, 0], 0.0)
        rng, sub = jax.random.split(lanes["rng"])
        nxt = sample_tokens(last, sub, temperature, top_k, active)
        out = {"rows": lane_state.advanced(rows, nxt), "rng": rng}
        if stateful:
            out["recurrent"] = stepped[0]
        return (kv, kv_scale, nxt, last, out) + counts

    def chunk_prefill(params, kv, kv_scale, tokens, start, length,
                      block_table, temperature, top_k, rng):
        # one chunk of a (possibly prefix-matched, possibly
        # chunked) prefill: tokens [1, B] (bucket-padded), start
        # scalar = context tokens whose KV is already written
        # (cached prefix + earlier chunks), length scalar = real
        # tokens in this chunk.  The chunk attends over the
        # already-written context (gathered from the pool by block
        # table — the concat read path, causal semantics implied by
        # ops.attention's ctx path) plus itself causally, writes
        # its KV into block slots, and samples from its last real
        # position — only the FINAL chunk's sample is consumed by
        # the host.  `rng` is the lane state's key: split here as
        # the other steps split it, its successor handed back.
        B = tokens.shape[1]
        rel = jnp.arange(B)
        pos = jnp.minimum(start + rel, max_pos - 1)
        tok_idx = (block_table[:, None] * bs
                   + jnp.arange(bs)[None, :]).reshape(1, -1)
        (logits, new_k, new_v), counts = concat_apply(
            params, kv, kv_scale, tokens, pos[None], tok_idx,
            jnp.reshape(start, (1,)).astype(jnp.int32),
            (rel < length)[None])
        dest = block_table[(start + rel) // bs] * bs \
            + (start + rel) % bs
        dest = jnp.where(rel < length, dest, 0)
        kv, kv_scale = write_kv(kv, kv_scale, dest,
                                *each(lambda x: x[:, 0], new_k, new_v))
        last = logits[0, length - 1]
        rng, sub = jax.random.split(rng)
        nxt = sample_tokens(last[None], sub, temperature, top_k)[0]
        return (kv, kv_scale, nxt, last, rng) + counts

    def spec_verify(params, kv, kv_scale, tokens, block_tables,
                    start, length, active):
        # speculative verify over the whole slot grid: tokens
        # [S, W] = each drafted lane's [pending token ; draft ;
        # pad], start [S] = context tokens whose KV is already
        # written (= context_len - 1), length [S] = 1 + real draft
        # tokens, active [S].  Every position attends over the
        # lane's pool context plus the preceding new tokens (the
        # chunk step's ctx-read semantics, batched over lanes —
        # ops.attention.paged_verify_attention), writes its KV
        # into the lane's (pre-grown) block slots, and the host
        # accepts the longest draft prefix matching the returned
        # per-position greedy argmax.  Speculation is greedy-only,
        # so no rng/temperature ride in.
        S, W = tokens.shape
        rel = jnp.arange(W)
        pos = jnp.minimum(start[:, None] + rel[None], max_pos - 1)
        real = (rel[None] < length[:, None]) & active[:, None]
        if paged:
            (logits, new_k, new_v), counts = paged_apply(
                params, kv, kv_scale, tokens, pos, block_tables,
                start, real)
        else:
            tok_idx = (block_tables[:, :, None] * bs
                       + jnp.arange(bs)[None, None, :]
                       ).reshape(S, -1)
            (logits, new_k, new_v), counts = concat_apply(
                params, kv, kv_scale, tokens, pos, tok_idx, start,
                real)
        abs_pos = start[:, None] + rel[None]        # [S, W]
        dest = block_tables[jnp.arange(S)[:, None],
                            abs_pos // bs] * bs + abs_pos % bs
        dest = jnp.where((rel[None] < length[:, None])
                         & active[:, None], dest, 0).reshape(-1)
        kv, kv_scale = write_kv(
            kv, kv_scale, dest,
            *each(lambda x: x.reshape(x.shape[0], S * W, *x.shape[-2:]),
                  new_k, new_v))
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (kv, kv_scale, greedy) + counts

    def copy_block(kv, kv_scale, src, dst):
        # copy-on-write: duplicate one pool block's token slots
        # (and their dequant scales) so a shared block becomes
        # exclusively owned before it is written
        rows = jax.lax.dynamic_slice_in_dim(kv, src * bs, bs,
                                            axis=2)
        kv = jax.lax.dynamic_update_slice_in_dim(kv, rows,
                                                 dst * bs, axis=2)
        if quantized:
            srows = jax.lax.dynamic_slice_in_dim(
                kv_scale, src * bs, bs, axis=2)
            kv_scale = jax.lax.dynamic_update_slice_in_dim(
                kv_scale, srows, dst * bs, axis=2)
        return kv, kv_scale

    def restore_block(kv, kv_scale, dst, rows, srows):
        # host-tier restore: land one host slab's token slots
        # (rows [L, 2, bs, h*d] in pool dtype, srows [L, 2, bs]
        # scales — a 1-element placeholder unquantized) into pool
        # block `dst`.  A separate single-shape program, warmed in
        # warmup(), never touching the decode step.
        kv = jax.lax.dynamic_update_slice_in_dim(
            kv, rows.astype(kv.dtype), dst * bs, axis=2)
        if quantized:
            kv_scale = jax.lax.dynamic_update_slice_in_dim(
                kv_scale, srows.astype(kv_scale.dtype),
                dst * bs, axis=2)
        return kv, kv_scale

    def ledgered(family, fn, donated, n_out, argnames, expected):
        # ONE wrap a program: jitted where the steps run — under
        # tensor parallelism the placement pins out_shardings (pool
        # head-sharded, scales/tokens/logits replicated) so every
        # step's outputs feed the next step in the same layout
        # (zero-recompile holds) — and registered with the dispatch
        # ledger (signature forensics + call counting; `_cache_size`
        # forwards so the compile-count pins keep reading the real
        # jit cache).  Argument names feed the compile-event differ so
        # a recompile post-mortem names the guilty leaf as e.g.
        # `tokens: int32[4] -> int32[5]`.  `expected`: the family's
        # compile budget, how many program variants its call-site
        # geometry implies — the ledger flags `over_budget` the moment
        # it compiles MORE (a recompile storm is then a budget breach
        # in /dispatch, not just a counter rate).
        jitted = (tp.jit_step(fn, donated, n_out) if tp is not None
                  else jax.jit(fn, donate_argnums=donated))
        if expected is not None:
            profiling.declare_expected(family, expected)
        return profiling.instrument(family, jitted, argnames=argnames)

    return (
        ledgered("prefill", prefill, donate_lanes, 5,
                 ("params", "kv", "kv_scale", "lanes", "request"),
                 prefill_variants),
        ledgered("chunk_prefill", chunk_prefill, donate, 5,
                 ("params", "kv", "kv_scale", "tokens", "start",
                  "length", "block_table", "temperature", "top_k",
                  "rng"), prefill_variants),
        ledgered("decode", decode, donate_lanes, 5,
                 ("params", "kv", "kv_scale", "lanes", "patch"), 1),
        ledgered("spec_verify", spec_verify, donate, 3,
                 ("params", "kv", "kv_scale", "tokens", "block_tables",
                  "start", "length", "active"), verify_variants),
        ledgered("copy_block", copy_block, donate_pool, 2,
                 ("kv", "kv_scale", "src", "dst"), 1),
        None if tp is not None else ledgered(
            "host_restore", restore_block, donate_pool, 2,
            ("kv", "kv_scale", "dst", "rows", "srows"), 1))
