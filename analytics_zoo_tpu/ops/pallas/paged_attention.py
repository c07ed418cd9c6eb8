"""Paged decode attention — a Pallas TPU kernel for q_len=1 serving.

The generation engine's decode step attends ONE new token per lane
against that lane's paged KV cache (serving/generation/kv_cache.py).
The pre-PR-6 path gathered every lane's blocks into a contiguous
[S, C, h, d] context with an XLA gather and ran the concat-attend
einsum of `ops.attention.dot_product_attention` — materializing
C = max_blocks * block_size tokens per lane in HBM traffic whether the
lane holds 3 tokens or 300.  This kernel is the vLLM-PagedAttention
answer, TPU-native: the BLOCK TABLE RIDES INTO THE KERNEL as a
scalar-prefetch operand, the grid walks (lane, block-group), and each
grid step's BlockSpec index map *reads the table* to aim the HBM->VMEM
DMA at the lane's next pool block — the gather happens in the DMA
engine, never as a materialized context tensor.

The operand is the WHOLE pool in the form it is stored in,
[n_layers, 2, num_blocks, block_size, heads * head_dim] (the block
view of `PagedKVCache.kv`, a bitcast), with the layer an index of the
K and V index maps `(layer, 0|1, table[lane, j], 0, 0)` — a constant
where the caller's layer is a Python int, read from one more
scalar-prefetch operand where it is traced (a looped decoder's pool
slot): no per-layer slice of the pool is ever an operand, so XLA
materializes none.  A staged tile is [n, h*d], n = block_gather *
block_size rows of 768 lanes for GPT-2's 12 x 64, lane-dense and
tile-exact, where a [bs, 12, 64] tile padded every (12, 64) plane to
(16, 128).

Heads never get an axis of their own in the pool, and the tile goes to
the MXU as it lies, in the pool's dtype.  The lane's query is laid out
block-diagonally once, at its first grid step: Q_bd [h, h*d], row k
holding head k's columns of q and zeros elsewhere (rows padded to a
whole bf16 tile).  Then, over the n rows of a grid step,

    scores [h, n]   = (Q_bd @ K.T) * d**-0.5     (positions along lanes)
    acc    [h, h*d] = acc * alpha + p @ V        (only row k's head-k
                                                  columns are kept)

with the usual online softmax between them (running max / denominator
in [h, 128], f32 VMEM scratch — the flash_attention bookkeeping at
q_len=1), masked by the lane's `ctx_len`.  The new token's
self-attention is folded into the initialization (a decode token always
attends to itself); finalize divides by the denominator and keeps each
row's own head.  The tile is never cast to f32: a bf16 K or V tile is
the MXU's operand as stored, and the f32 operand against it (Q_bd, p)
goes in as three bf16 pieces stacked along the rows, so each product is
ONE bf16 pass with the tile loaded once and still exact to f32
(`_split3`, `_dot_ready`) — left to itself Mosaic rounds an f32 operand
to bf16 (a gap of 2.4e-3 to the XLA form on N(0, 1) data against
1.3e-6 this way, on one TPU v5e).  An f32 copy of the tile would cost
more vector work than the rest of the step together, and buy nothing:
the products accumulate in f32 either way.  A tile of fewer than
`_BLOCK_DIAGONAL_ROWS` rows (one block of 16: a table without a
tuned gather) keeps the head-indicator body, which is faster there
(`_kernel_indicator`: with E the [h*d, h] 0/1 indicator of each merged
column's head, scores [n, h] = ((K * q) @ E) * d**-0.5 and out =
sum over rows of ((p @ E.T) * V), both products exact the same way).

Quantized pools (int8 KV, serving/generation/kv_cache.py): when
`kv_scale` [n_layers, 2, num_blocks, block_size] rides along, the int8
tile is read as bf16 (exact) and the kernel dequantizes ON READ by
folding each token's scale into its position of the scores /
probabilities (s *= k_scale; p *= v_scale, a [1, n] row) —
algebraically identical to scaling K/V rows, but never materializing a
dequantized block.

The tunable is `block_gather` (G): how many pool blocks one grid step
processes.  G > 1 passes the pool G times with G table-indexed
BlockSpecs, so one grid step streams G blocks and amortizes the
per-step softmax bookkeeping and both products over a G*block_size-row
tile — the decode analog of flash's block_k.  Registered with
`ops/tuning` under the fwd-only key family

    paged_decode|<platform>|<pool dtype>|bs=<block_size>,d=<head_dim>,
    lanes=<max_slots>

(pow2-bucketed like every tuner key; see docs/kernels.md).  Decode is
inference-only — there is no backward kernel and no custom_vjp.

Dispatch lives in `ops.attention.paged_decode_attention` (the one
entry point the generation engine is allowed to call —
scripts/check_kernel_dispatch.py): Pallas on TPU, an XLA fallback that
bit-matches the pre-PR-6 gather+concat path everywhere else, and
`interpret=True` to run this kernel on the CPU interpreter in tests.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: builtin-fallback block gather width (one pool block per grid step —
#: always legal; the tuner widens it where VMEM and the table allow)
DEFAULT_BLOCK_GATHER = 1
#: candidate VMEM ceiling (same headroom discipline as flash_attention)
_VMEM_BUDGET = 12 * 1024 * 1024
#: lanes the running max / denominator of a kernel with query rows are
#: kept over (every lane holds the same number: a [rows, 1] scratch is
#: no whole tile)
_STAT_LANES = 128


#: the multi-head kernel's block-diagonal queries have a row a head,
#: padded up to whole bf16 tiles of 16 rows so that their three pieces
#: stack on tile boundaries (the rows past the heads stay zero)
_ROW_TILE = 16
#: staged tile rows (block_gather * block_size) from which the
#: multi-head form takes the block-diagonal body (`_kernel`); a smaller
#: tile takes the head-indicator body (`_kernel_indicator`).  Measured
#: on one TPU v5e at GPT-2 small's decode shapes (32 lanes, 12 heads x
#: 64, tables of 64 blocks of 16), the indicator body against the
#: block-diagonal one, us a call: every grid step live, 16 rows, 1,015
#: against 1,150; contexts 64-672, 32 rows, 347 / 367 against 329 /
#: 350 (two seeds); 64 rows, 312 / 329 against 267 / 276
_BLOCK_DIAGONAL_ROWS = 32


def _head_rows(h: int) -> int:
    return -(-h // _ROW_TILE) * _ROW_TILE


def paged_decode_candidates(bs: int, mb: int, h: int, d: int,
                            itemsize: int = 4
                            ) -> List[Dict[str, int]]:
    """The autotuner's candidate grid: block-gather widths that fit the
    VMEM budget and don't exceed the per-lane table.  Per grid step the
    kernel holds the k+v tiles [n = g*bs, h*d] in the pool's dtype
    (double buffered by the pipeline), the q/new-token/output rows (8
    sublanes each, double buffered) and the scale rows, and what its
    body works in.  The block-diagonal body (n >= _BLOCK_DIAGONAL_ROWS):
    both tiles as the product reads them (concatenated; bf16 where the
    pool is int8), the lane's block-diagonal queries [3hp, h*d] bf16
    (hp: the heads padded to whole row tiles), the scores' and the
    values' f32 products [3hp, n] and [3hp, h*d], the [hp, h*d]
    accumulator and the m / l statistics.  The head-indicator body:
    some six f32 copies of the tile, both indicators (bf16, padded to
    128 lanes / 16 sublanes) and its [1, h*d] scratch."""
    hd = h * d
    hp = _head_rows(h)
    out = []
    for g in (1, 2, 4, 8):
        if g > max(1, mb):
            continue
        n = g * bs
        if n >= _BLOCK_DIAGONAL_ROWS:
            work = (2 * n * hd * max(itemsize, 2)   # the tiles as read
                    + 3 * hp * hd * 2               # queries, 3 pieces
                    + 3 * hp * (n + hd) * 4         # the two products
                    + hp * hd * 4                   # accumulator
                    + 2 * hp * _STAT_LANES * 4)     # m, l
        else:
            work = (6 * n * hd * 4                  # f32 working set
                    + 2 * hd * 128 * 2 + 2 * 16 * hd * 2   # E, E.T
                    + hd * 4 + 2 * 8 * 128 * 4)     # o/m/l scratch
        vmem = (2 * 2 * n * hd * itemsize           # k+v tiles, 2 buffers
                + work
                + 4 * 2 * 8 * hd * 4                # q, new_k, new_v, o
                + 2 * 2 * g * 8 * 128 * 4)          # scale rows
        if vmem <= _VMEM_BUDGET:
            out.append({"block_gather": g})
    return out or [{"block_gather": DEFAULT_BLOCK_GATHER}]


def _rows(xs):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)


def _first_block(cl, window: Optional[int], bs: int):
    """The first table entry a lane at context `cl` reads: 0 without a
    window, else the block that holds position cl - window + 1 (the
    oldest cached position the pending token at position cl sees)."""
    if window is None:
        return 0
    return jnp.maximum(cl - (window - 1), 0) // bs


def _layer_operand(layer):
    """How the pool's `layer` reaches the index maps: (the scalar-prefetch
    operands it adds, the maps' reading of it from the refs they are
    handed after the table and the lengths).  A Python int stays a
    constant of the maps — the lowering every static caller has always
    had; a traced int32 scalar (the slot of a looped decoder's layer
    application, decoder.py) rides in as one more scalar-prefetch
    operand [1] that the maps read from SMEM, so the DMA is aimed at
    that layer's blocks and no per-layer slice of the pool is ever an
    operand."""
    if isinstance(layer, (int, np.integer)):
        return (), lambda *_: int(layer)
    return ((jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),),
            lambda lyr: lyr[0])


def _past_layer_ref(kernel):
    """`kernel` behind the traced layer's prefetch ref: the index maps
    read it, the body never does."""
    def body(tbl_ref, cl_ref, _layer_ref, *refs):
        return kernel(tbl_ref, cl_ref, *refs)
    return body


def _split3(x):
    """x f32 [r, k] as its three bf16 pieces stacked along the rows."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _dot_ready(x, w, contract_w: int):
    """x times a K or V tile `w` as it lies in the pool, contracting w's
    axis `contract_w` (1: x @ w.T, 0: x @ w), to f32 [r, n].  Where w
    is bf16, x is an f32 operand's three bf16 pieces [3r, k]
    (`_split3`): ONE bf16 MXU pass, the tile loaded once, exact to f32
    — left to itself Mosaic rounds an f32 operand to bf16 (a gap of
    2.4e-3 to the XLA form on N(0, 1) data against 1.3e-6 this way, on
    one TPU v5e).  Any other tile (the f32 pools of the CPU tests)
    meets x [r, k] in f32 at the highest precision."""
    dims = (((1,), (contract_w,)), ((), ()))
    if w.dtype == jnp.bfloat16:
        r = x.shape[0] // 3
        y = jax.lax.dot_general(x, w, dims,
                                preferred_element_type=jnp.float32)
        return y[:r] + y[r:2 * r] + y[2 * r:]
    return jax.lax.dot_general(x, w.astype(jnp.float32), dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot_f32(x, w, contract_w: int):
    """x [r, k] f32 times a pool tile `w` (`_dot_ready`), split here."""
    return _dot_ready(_split3(x) if w.dtype == jnp.bfloat16 else x, w,
                      contract_w)


def _kernel(tbl_ref, cl_ref, q_ref, nk_ref, nv_ref, *rest, g: int, bs: int,
            num_j: int, d: int, quantized: bool, scale: float,
            window: Optional[int] = None):
    # scalar prefetch: tbl_ref [S, MB] block tables, cl_ref [S] ctx
    # lengths.  q/nk/nv_ref: [1, h*d] lane rows.  rest: g gathered K
    # blocks [bs, h*d], g V blocks, (g k-scale + g v-scale [1, bs] rows
    # when quantized), then o_ref [1, h*d] and the VMEM scratch carried
    # across the block axis: qd, the lane's queries block-diagonal —
    # row k holds head k's columns of q, zeros elsewhere, the h rows
    # padded to hp — as the staged tile's product takes them (three
    # bf16 pieces [3hp, h*d], or f32 [hp, h*d] beside an f32 tile); the
    # o [hp, h*d] accumulator, of which only row k's head-k columns
    # count; the running max / denominator m, l [hp, 128].
    rest = list(rest)
    ks = [rest.pop(0) for _ in range(g)]
    vs = [rest.pop(0) for _ in range(g)]
    kscl = [rest.pop(0) for _ in range(g)] if quantized else None
    vscl = [rest.pop(0) for _ in range(g)] if quantized else None
    o_ref, qd_scr, o_scr, m_scr, l_scr = rest
    hp, hd = o_scr.shape
    pieces = qd_scr.dtype == jnp.bfloat16
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    n = g * bs

    def own_head():      # [hp, hd]: row k's columns of head k
        row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
        return (col >= row * d) & (col < row * d + d)

    def ready(x):        # an f32 operand as the tile's product takes it
        return _split3(x) if pieces else x

    def tile(refs):      # [n, hd] as staged; int8 read as bf16 (exact)
        return _rows([r[...].astype(jnp.bfloat16)
                      if r.dtype == jnp.int8 else r[...] for r in refs])

    def lanes(refs):     # g [1, bs] scale rows as one [1, n] row
        xs = [r[...] for r in refs]
        return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)

    @pl.when(j == 0)
    def _init():
        # the new token always attends to itself: seed the online
        # softmax with its own score (p_self = exp(0) = 1, l = 1,
        # o = new_v) instead of a NEG_INF/0 init — no empty-context
        # special case, no 0/0 at finalize
        qd = jnp.where(own_head(), q_ref[...].astype(jnp.float32), 0.0)
        qd_scr[...] = ready(qd)
        s_self = (qd * nk_ref[...].astype(jnp.float32)).sum(
            axis=1, keepdims=True) * scale               # [hp, 1]
        m_scr[...] = jnp.broadcast_to(s_self, m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
        o_scr[...] = jnp.broadcast_to(nv_ref[...].astype(jnp.float32),
                                      o_scr.shape)

    cl = cl_ref[s_idx]
    # the rows of this grid step start at position `base`: group j of
    # the table, counted from the first block the window reaches into
    base = _first_block(cl, window, bs) * bs + j * n

    # block groups entirely past the lane's context are all-masked:
    # skip their compute (the DMAs still stream by, cheaply — the
    # shapes stay static, which is the zero-recompile contract)
    @pl.when(base < cl)
    def _compute():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        valid = pos < cl                                 # [1, n]
        if window is not None:
            valid = valid & (pos > cl - window)
        # every head's scores in ONE product with the tile as it lies
        s = _dot_ready(qd_scr[...], tile(ks), 1) * scale  # [hp, n]
        if quantized:
            s = s * lanes(kscl)          # dequant-on-read, by position
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:, 0:1]                           # [hp, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0:1] * alpha + p.sum(axis=1, keepdims=True)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        if quantized:
            p = p * lanes(vscl)
        o_scr[...] = o_scr[...] * alpha + _dot_ready(ready(p), tile(vs), 0)

    @pl.when(j == num_j - 1)
    def _finalize():
        o = o_scr[...] / l_scr[:, 0:1]
        o_ref[...] = jnp.where(own_head(), o, 0.0).sum(
            axis=0, keepdims=True).astype(o_ref.dtype)


def _kernel_indicator(tbl_ref, cl_ref, q_ref, nk_ref, nv_ref, e_ref, et_ref,
                      *rest, g: int, bs: int, num_j: int, quantized: bool,
                      scale: float, window: Optional[int] = None):
    # the multi-head form on a staged tile of fewer than
    # `_BLOCK_DIAGONAL_ROWS` rows: positions along the sublanes, heads
    # found through e_ref [h*d, h] and et_ref [h, h*d], the 0/1 head
    # indicators E and E.T (fetched once: their block never moves) —
    # scores [n, h] = ((K * q) @ E) * scale, out = sum over rows of
    # ((p @ E.T) * V) — with the o [1, h*d] / m, l [1, h] scratch.
    # Refs otherwise as `_kernel`'s.
    rest = list(rest)
    ks = [rest.pop(0) for _ in range(g)]
    vs = [rest.pop(0) for _ in range(g)]
    kscl = [rest.pop(0) for _ in range(g)] if quantized else None
    vscl = [rest.pop(0) for _ in range(g)] if quantized else None
    o_ref, o_scr, m_scr, l_scr = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    n = g * bs
    qv = q_ref[...].astype(jnp.float32)                  # [1, hd]

    def heads(x):        # [r, hd] -> [r, h]: each head's sum
        return _dot_f32(x, e_ref[...], 0)

    def spread(x):       # [r, h] -> [r, hd]: each head's value, d times
        return _dot_f32(x, et_ref[...], 0)

    def up8(x):          # a [1, w] row as 8 sublanes: a whole MXU tile
        return jnp.broadcast_to(x, (8, x.shape[1]))

    @pl.when(j == 0)
    def _init():
        # the new token attends to itself: see `_kernel`
        s_self = heads(up8(qv * nk_ref[...].astype(jnp.float32)))
        m_scr[...] = s_self[0:1] * scale                 # [1, h]
        l_scr[...] = jnp.ones_like(l_scr)
        o_scr[...] = nv_ref[...].astype(jnp.float32)

    cl = cl_ref[s_idx]
    base = j * n if window is None \
        else _first_block(cl, window, bs) * bs + j * n

    @pl.when(base < cl)
    def _compute():
        kt = _rows([k[...].astype(jnp.float32) for k in ks])  # [n, hd]
        vt = _rows([v[...].astype(jnp.float32) for v in vs])
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        valid = pos < cl                                 # [n, 1]
        if window is not None:
            valid = valid & (pos > cl - window)
        s = heads(kt * qv) * scale                       # [n, h]
        if quantized:
            # the scales arrive as [1, bs] rows and scale ROWS here:
            # turn each into a [bs, 1] column through the diagonal
            eye = (jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1))

            def col(refs):
                return _rows([jnp.where(eye, r[...], 0.0).sum(
                    axis=1, keepdims=True) for r in refs])
            s = s * col(kscl)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)                  # [1, h]
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=0, keepdims=True)
        m_scr[...] = m_new
        if quantized:
            p = p * col(vscl)
        # p and the rescale factor share one trip through E.T
        pa = spread(jnp.concatenate([p, up8(alpha)], axis=0))
        o_scr[...] = (o_scr[...] * pa[n:n + 1]
                      + (pa[:n] * vt).sum(axis=0, keepdims=True))

    @pl.when(j == num_j - 1)
    def _finalize():
        o_ref[...] = (o_scr[...] / spread(up8(l_scr[...]))[0:1]
                      ).astype(o_ref.dtype)


def _kernel_gqa(tbl_ref, cl_ref, q_ref, nk_ref, nv_ref, *rest, g: int,
                bs: int, num_j: int, kv_heads: int, r: int, d: int,
                window: Optional[int], scale: float):
    # grouped queries: q_ref [h, d], the h = kv_heads * r query heads of
    # one lane as rows (KV head c's queries are rows c*r .. c*r + r - 1);
    # nk_ref / nv_ref [h, d] the new token's key / value of each query
    # head's KV head.  rest: g gathered K blocks [bs, kv_heads * d], g V
    # blocks, then o_ref [h, d] and the o [h, d] / m / l [h, 128] VMEM
    # scratch carried across the block axis.  A KV head's columns are a
    # static slice of the staged tile — whole lanes at d = 128 — and its
    # r queries meet them in one MXU product: one read of a K/V block
    # serves r queries.
    rest = list(rest)
    ks = [rest.pop(0) for _ in range(g)]
    vs = [rest.pop(0) for _ in range(g)]
    o_ref, o_scr, m_scr, l_scr = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    n = g * bs
    cl = cl_ref[s_idx]
    base = _first_block(cl, window, bs) * bs + j * n

    @pl.when(j == 0)
    def _init():
        # the new token attends to itself: see `_kernel`
        qv = q_ref[...].astype(jnp.float32)
        s_self = (qv * nk_ref[...].astype(jnp.float32)).sum(
            axis=1, keepdims=True) * scale               # [h, 1]
        m_scr[...] = jnp.broadcast_to(s_self, m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
        o_scr[...] = nv_ref[...].astype(jnp.float32)

    @pl.when(base < cl)
    def _compute():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        valid = pos < cl                                 # [1, n]
        if window is not None:
            valid = valid & (pos > cl - window)
        for c in range(kv_heads):
            rows = slice(c * r, (c + 1) * r)
            cols = slice(c * d, (c + 1) * d)
            kc = _rows([k[:, cols] for k in ks])         # [n, d]
            vc = _rows([v[:, cols] for v in vs])
            s = _dot_f32(q_ref[rows, :].astype(jnp.float32), kc, 1) \
                * scale                                  # [r, n]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[rows, 0:1]                    # [r, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[rows, 0:1] * alpha + p.sum(axis=1,
                                                     keepdims=True)
            l_scr[rows, :] = jnp.broadcast_to(l_new, (r, _STAT_LANES))
            m_scr[rows, :] = jnp.broadcast_to(m_new, (r, _STAT_LANES))
            o_scr[rows, :] = o_scr[rows, :] * alpha + _dot_f32(p, vc, 0)

    @pl.when(j == num_j - 1)
    def _finalize():
        o_ref[...] = (o_scr[...] / l_scr[:, 0:1]).astype(o_ref.dtype)


def paged_decode_pallas(q, new_k, new_v, kv_pool, block_tables, ctx_len,
                        *, layer: int, head_dim: int, kv_scale=None,
                        block_gather: int = DEFAULT_BLOCK_GATHER,
                        interpret: bool = False, q_per_kv: int = 1,
                        window: Optional[int] = None):
    """The raw kernel call (dispatch through
    `ops.attention.paged_decode_attention`, which picks impl and asks
    the tuner for `block_gather`).

    q, new_k, new_v: [S, h*d] — lane S's pending token's query and its
    key/value (it attends to itself), heads merged like the pool's rows.
    kv_pool: [n_layers, 2, num_blocks, block_size, h*d] — the whole
    paged pool (block 0 = the null block; any float dtype, or int8 with
    scales); `layer` picks the layer in the index maps: a Python int
    (static) or a traced int32 scalar, which rides in by scalar
    prefetch (`_layer_operand`).
    kv_scale: [n_layers, 2, num_blocks, block_size] f32 per-token-slot
    dequant scales (required iff the pool is quantized).
    block_tables: [S, max_blocks] int32; ctx_len: [S] int32 valid
    lengths (cached position p lives at table[p // bs], slot p % bs).
    Returns [S, h*d] float32.

    q_per_kv > 1 (grouped queries): q is [S, h*d] over h = kv_heads *
    q_per_kv query heads while new_k / new_v and the pool's rows are
    [.., kv_heads * d]; query head i reads KV head i // q_per_kv
    (`_kernel_gqa`: one staged K/V tile, q_per_kv query rows against
    each KV head's columns).  `window` w: the pending token sees itself
    and the w - 1 cached positions before it; the grid walks
    ceil((w - 1) / bs) + 1 table entries from the block of position
    ctx_len - w + 1 on, whatever the context's length, and masks the
    rest inside.  Without either, the kernel is the one it was.
    """
    s, hd = q.shape
    bs = kv_pool.shape[3]
    h = hd // head_dim
    mb = block_tables.shape[1]
    g = max(1, int(block_gather))
    quantized = kv_scale is not None
    reach = None
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        # the w - 1 cached positions in sight touch at most `reach`
        # blocks: gather no more than that at a time
        reach = -(-(window - 1) // bs) + 1
        g = min(g, reach)
    # pad the table up to a multiple of g with null blocks — their
    # positions sit past every ctx_len, so the mask kills them
    if mb % g:
        pad = g - mb % g
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        mb += pad
    num_j = mb // g
    if reach is not None:
        num_j = min(num_j, -(-reach // g))
    block_tables = block_tables.astype(jnp.int32)
    ctx_len = jnp.asarray(ctx_len, jnp.int32)
    by_layer, at_layer = _layer_operand(layer)
    prefetch = (block_tables, ctx_len) + by_layer

    def body(kernel):
        return _past_layer_ref(kernel) if by_layer else kernel

    def _entry(si, j, i, tbl, cl):
        # the table entry grid step j's i-th block reads: counted from
        # the window's first block, held inside the table (an entry
        # past the context is masked whatever it names)
        if window is None:
            return tbl[si, j * g + i]
        first = _first_block(cl[si], window, bs)
        return tbl[si, jnp.minimum(first + j * g + i, mb - 1)]

    if q_per_kv > 1:
        if quantized:
            raise NotImplementedError(
                "the grouped-query paged kernel reads no int8 pool yet")
        if h % q_per_kv:
            raise ValueError(f"{h} query heads do not divide by "
                             f"q_per_kv {q_per_kv}")
        kv_heads, d = h // q_per_kv, head_dim
        heads = pl.BlockSpec((None, h, d),
                             lambda si, j, tbl, cl, *_: (si, 0, 0))

        def per_query_head(x):     # [S, kv_heads*d] -> [S, h, d]
            return jnp.repeat(x.reshape(s, kv_heads, d), q_per_kv, axis=1)

        pool = [pl.BlockSpec(
            (None, None, None, bs, kv_heads * d),
            lambda si, j, tbl, cl, *lyr, w=w, i=i: (
                at_layer(*lyr), w, _entry(si, j, i, tbl, cl), 0, 0))
            for w in (0, 1) for i in range(g)]
        out = pl.pallas_call(
            body(partial(_kernel_gqa, g=g, bs=bs, num_j=num_j,
                         kv_heads=kv_heads, r=q_per_kv, d=d,
                         window=window, scale=1.0 / (head_dim ** 0.5))),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=(s, num_j),
                in_specs=[heads, heads, heads] + pool, out_specs=heads,
                scratch_shapes=[
                    pltpu.VMEM((h, d), jnp.float32),
                    pltpu.VMEM((h, _STAT_LANES), jnp.float32),
                    pltpu.VMEM((h, _STAT_LANES), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((s, h, d), jnp.float32),
            interpret=interpret,
        )(*prefetch, q.reshape(s, h, d),
          per_query_head(new_k), per_query_head(new_v),
          *[kv_pool] * (2 * g))
        return out.reshape(s, hd)

    # a [1, hd] block of an [S, hd] array breaks Mosaic's (8, 128)
    # block rule; of the [S, 1, hd] view it is the last two dims whole
    lane = pl.BlockSpec((None, 1, hd),
                        lambda si, j, tbl, cl, *_: (si, 0, 0))

    def _pool_spec(which, i, rows):
        # K (which=0) or V (1) block table[lane, j*g + i] of `layer`;
        # `rows` is the block's own shape: (bs, hd) of the pool,
        # (1, bs) of the scales' [..., num_blocks, 1, bs] view
        return pl.BlockSpec(
            (None, None, None) + rows,
            lambda si, j, tbl, cl, *lyr: (
                at_layer(*lyr), which, _entry(si, j, i, tbl, cl), 0, 0))

    def _fixed(shape):
        return pl.BlockSpec(shape, lambda si, j, tbl, cl, *_: (0, 0))

    indicator = g * bs < _BLOCK_DIAGONAL_ROWS
    in_specs = [lane, lane, lane]
    args = [q[:, None], new_k[:, None], new_v[:, None]]
    if indicator:
        e = (jnp.arange(hd)[:, None] // head_dim
             == jnp.arange(h)[None, :]).astype(jnp.bfloat16)
        in_specs += [_fixed((hd, h)), _fixed((h, hd))]
        args += [e, e.T]
    in_specs += [_pool_spec(w, i, (bs, hd))
                 for w in (0, 1) for i in range(g)]
    args += [kv_pool] * (2 * g)
    if quantized:
        in_specs += [_pool_spec(w, i, (1, bs))
                     for w in (0, 1) for i in range(g)]
        args += [kv_scale.astype(jnp.float32).reshape(
            *kv_scale.shape[:3], 1, bs)] * (2 * g)
    scale = 1.0 / (head_dim ** 0.5)
    if indicator:
        kernel = partial(_kernel_indicator, g=g, bs=bs, num_j=num_j,
                         quantized=quantized, scale=scale, window=window)
        scratch = [pltpu.VMEM((1, hd), jnp.float32),
                   pltpu.VMEM((1, h), jnp.float32),
                   pltpu.VMEM((1, h), jnp.float32)]
    else:
        kernel = partial(_kernel, g=g, bs=bs, num_j=num_j, d=head_dim,
                         quantized=quantized, scale=scale, window=window)
        hp = _head_rows(h)
        # the block-diagonal queries as the staged tile's product takes
        # them: three bf16 pieces beside a bf16 (or int8) tile, else f32
        scratch = [(pltpu.VMEM((3 * hp, hd), jnp.bfloat16)
                    if kv_pool.dtype in (jnp.bfloat16, jnp.int8)
                    else pltpu.VMEM((hp, hd), jnp.float32)),
                   pltpu.VMEM((hp, hd), jnp.float32),
                   pltpu.VMEM((hp, _STAT_LANES), jnp.float32),
                   pltpu.VMEM((hp, _STAT_LANES), jnp.float32)]
    return pl.pallas_call(
        body(kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(s, num_j),
            in_specs=in_specs, out_specs=lane, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((s, 1, hd), jnp.float32),
        interpret=interpret,
    )(*prefetch, *args)[:, 0]


#: pool blocks a grid step of the latent kernel reads where the caller
#: names none.  Measured at `sarvam_105b_ep8_serve`'s decode shapes (128
#: lanes, contexts about 2,900, tables of 320 blocks of 16 rows; my chip
#: run, PR 37): 5 layers take 24.5 / 16.5 / 14.0 / 12.3 ms at 4 / 8 / 16
#: / 32 — a grid step costs some 0.3 us and each 20 KB block DMA some
#: 0.05 us whatever the width, so fewer, wider steps win until the tile
#: (32 blocks: 512 rows, 0.66 MB a buffer) crowds VMEM
LATENT_BLOCK_GATHER = 32


def _dot_pool(x, w, contract_w: int):
    """x [r, k] (float32 or the pool's dtype) times a pool tile `w`,
    contracting w's axis `contract_w`, to float32: ONE MXU pass in the
    pool's dtype where that is bfloat16 (x rounded to it, as the XLA
    form rounds its probabilities), float32 at the highest precision
    for any other pool (the float32 pools of the CPU tests)."""
    dims = (((1,), (contract_w,)), ((), ()))
    if w.dtype == jnp.bfloat16:
        return jax.lax.dot_general(x.astype(jnp.bfloat16), w, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(x.astype(jnp.float32),
                               w.astype(jnp.float32), dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel_latent(tbl_ref, cl_ref, q_ref, new_ref, *rest, g: int, bs: int,
                   num_j: int, vw: int, scale: float):
    # one row a token: q_ref [h, w] the lane's heads in the cached
    # row's space, new_ref [1, w] the pending token's own row.  rest: g
    # gathered blocks [bs, w] of the ONE pool row kind, then o_ref
    # [h, vw] and the o [h, vw] / m / l [h, 128] VMEM scratch carried
    # across the block axis.  A staged tile is read once and used
    # twice: whole as the keys of all h heads, its first vw columns
    # (whole lanes) as their values.
    rest = list(rest)
    blocks = [rest.pop(0) for _ in range(g)]
    o_ref, o_scr, m_scr, l_scr = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    n = g * bs
    cl = cl_ref[s_idx]
    base = j * n

    @pl.when(j == 0)
    def _init():
        # the new token attends to itself: see `_kernel`
        own = new_ref[...].astype(jnp.float32)               # [1, w]
        s_self = (q_ref[...].astype(jnp.float32) * own).sum(
            axis=1, keepdims=True) * scale                   # [h, 1]
        m_scr[...] = jnp.broadcast_to(s_self, m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
        o_scr[...] = jnp.broadcast_to(own[:, :vw], o_scr.shape)

    # a dead lane's table names the null block and its context is 0:
    # every group is past it, and it hands back its own row
    @pl.when(base < cl)
    def _compute():
        rows = _rows([blk[...] for blk in blocks])           # [n, w]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        valid = pos < cl                                     # [1, n]
        s = _dot_pool(q_ref[...], rows, 1) * scale           # [h, n]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:, 0:1]                               # [h, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0:1] * alpha + p.sum(axis=1, keepdims=True)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        o_scr[...] = o_scr[...] * alpha + _dot_pool(p, rows[:, :vw], 0)

    @pl.when(j == num_j - 1)
    def _finalize():
        o_ref[...] = (o_scr[...] / l_scr[:, 0:1]).astype(o_ref.dtype)


def latent_decode_pallas(q, new_row, kv_pool, block_tables, ctx_len, *,
                         layer: int, value_width: int, scale: float,
                         block_gather: Optional[int] = None,
                         interpret: bool = False):
    """The raw latent kernel call (dispatch through
    `ops.attention.latent_decode_attention`).

    q: [S, h, w] — lane S's heads in the cached row's space, w the
    pool's columns as stored (padded with zeros past the row's own);
    new_row: [S, w], the pending token's row.  kv_pool: [n_layers, 1,
    num_blocks, block_size, w] — the whole latent pool (block 0 = the
    null block), `layer` (static) picked in the index map.
    block_tables [S, max_blocks] int32, ctx_len [S] int32 as
    `paged_decode_pallas`'s.  Returns [S, h, value_width] float32: per
    head, the softmax over scale * q . row of the cached rows and the
    token's own, times the rows' first `value_width` columns."""
    s, h, w = q.shape
    bs = kv_pool.shape[3]
    mb = block_tables.shape[1]
    g = max(1, min(int(block_gather or LATENT_BLOCK_GATHER), mb))
    if mb % g:
        pad = g - mb % g
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        mb += pad
    num_j = mb // g
    heads = pl.BlockSpec((None, h, w), lambda si, j, tbl, cl: (si, 0, 0))
    own = pl.BlockSpec((None, 1, w), lambda si, j, tbl, cl: (si, 0, 0))
    pool = [pl.BlockSpec(
        (None, None, None, bs, w),
        lambda si, j, tbl, cl, i=i: (layer, 0, tbl[si, j * g + i], 0, 0))
        for i in range(g)]
    return pl.pallas_call(
        partial(_kernel_latent, g=g, bs=bs, num_j=num_j, vw=value_width,
                scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s, num_j),
            in_specs=[heads, own] + pool,
            out_specs=pl.BlockSpec((None, h, value_width),
                                   lambda si, j, tbl, cl: (si, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, value_width), jnp.float32),
                pltpu.VMEM((h, _STAT_LANES), jnp.float32),
                pltpu.VMEM((h, _STAT_LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, value_width), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), jnp.asarray(ctx_len, jnp.int32),
      q, new_row[:, None], *[kv_pool] * g)


# ----------------------------------------------------------------------
# autotuning (fwd-only key family "paged_decode")
# ----------------------------------------------------------------------

def _bench_paged_decode(bs, lanes, h, d, dtype, cfg, iters: int = 8):
    """Autotuner benchmark: decode-step wall time with a synthetic
    near-full pool, iterations chained output->query inside one
    compiled scan (the flash bench technique, so dispatch latency never
    masquerades as kernel time)."""
    import numpy as np

    from analytics_zoo_tpu.observability import now
    mb = max(4, 512 // bs)                 # a serving-shaped table
    nb = lanes * mb + 1
    rng = np.random.default_rng(0)
    shape = (1, 2, nb, bs, h * d)
    if jnp.dtype(dtype) == jnp.int8:
        kv_pool = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        kv_scale = jnp.asarray(rng.uniform(0.005, 0.02, shape[:4]),
                               jnp.float32)
    else:
        kv_pool = jnp.asarray(rng.normal(size=shape), dtype)
        kv_scale = None
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1)[:lanes * mb].reshape(lanes, mb),
        jnp.int32)
    ctx = jnp.full(lanes, mb * bs - 1, jnp.int32)
    q0 = jnp.asarray(rng.normal(size=(lanes, h * d)), jnp.float32)
    nk = jnp.asarray(rng.normal(size=(lanes, h * d)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(lanes, h * d)), jnp.float32)

    @jax.jit
    def many(q):
        def body(c, _):
            o = paged_decode_pallas(
                c, nk, nv, kv_pool, tables, ctx, layer=0, head_dim=d,
                kv_scale=kv_scale, block_gather=cfg["block_gather"])
            return o, None
        c, _ = jax.lax.scan(body, q, None, length=iters)
        return c[0, 0]

    float(many(q0))                        # compile + warm
    dt = float("inf")
    for _ in range(2):
        t0 = now()
        float(many(q0))                    # value fetch = device fence
        dt = min(dt, now() - t0)
    return dt / iters


def tuned_paged_block_gather(bs, lanes, h, d, dtype,
                             mb: Optional[int] = None,
                             allow_search=None) -> int:
    """The block-gather width for this decode geometry, from the
    autotuner (ops/tuning) under the fwd-only "paged_decode" key family
    — with tuning off (the default) a dict lookup against the persisted
    cache / checked-in tables, falling back to DEFAULT_BLOCK_GATHER;
    never a benchmark under a jax trace or on CPU."""
    from analytics_zoo_tpu.ops import tuning
    shape = {"bs": bs, "lanes": lanes, "d": d}
    cands = paged_decode_candidates(bs, mb if mb is not None else 8,
                                    h, d, jnp.dtype(dtype).itemsize)
    cfg = tuning.get_config(
        "paged_decode", shape, dtype,
        default={"block_gather": DEFAULT_BLOCK_GATHER},
        candidates=cands,
        bench=lambda c: _bench_paged_decode(bs, lanes, h, d, dtype, c),
        allow_search=allow_search)
    return int(cfg["block_gather"])


def tune_paged_decode(bs, lanes, h, d, dtype=jnp.float32,
                      mb: Optional[int] = None, force=False) -> int:
    """Search NOW (`ops.tuning.tune`, on a real TPU): benchmark
    the candidate gather widths, persist the winner to
    `OrcaContext.kernel_tuning_cache_dir`, return it."""
    from analytics_zoo_tpu.ops import tuning
    shape = {"bs": bs, "lanes": lanes, "d": d}
    cfg = tuning.tune(
        "paged_decode", shape, dtype,
        paged_decode_candidates(bs, mb if mb is not None else 8, h, d,
                                jnp.dtype(dtype).itemsize),
        lambda c: _bench_paged_decode(bs, lanes, h, d, dtype, c),
        force=force)
    return int(cfg["block_gather"])
