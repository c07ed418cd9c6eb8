"""Paged KV cache: fixed-size blocks in one preallocated device buffer.

vLLM's PagedAttention insight, TPU-native: instead of reserving a
max-context-length KV strip per sequence (most of it empty), the cache
is a pool of `num_blocks` fixed-size blocks and each sequence holds a
BLOCK TABLE — the list of block ids its tokens occupy, in order.
Fragmentation drops from per-sequence worst-case to one partial block
per sequence, so many more sequences fit in the same HBM.

The device side is ONE jax array per cache,
[n_layers, 2, num_blocks * block_size, heads * head_dim] (k=0/v=1 on
axis 1): one ROW per token slot, heads and head_dim merged.  This is
the one form every reader and writer shares, chosen so that no step
program ever relays the pool out.  The chip stores an array in
(sublane, 128-lane) tiles over its two minor dims — (16, 128) for
bf16 — and Mosaic wants its operands row-major in those tiles.  A
minor pair of (heads, head_dim) = (12, 64) fits neither, so the old
[..., heads, head_dim] pool was padded and transposed on the way into
every program and back out (three whole-pool copies a decode step);
a merged row of 768 is six full lanes and a block of 16 rows one
sublane tile, so `block_view` is a bitcast and a paged-kernel DMA
lands on whole tiles.  The pool has two writers, each ONE scatter
that XLA performs in place on the donated pool, indexed by (layer,
k/v, ...) so that no window spans the layer axis (one that did made
XLA move that axis inward around the write: two more whole-pool
copies).  `write_kv` moves single rows to token slots: what decode
(one row a lane), a chunk (a start that need not be a block's) and
verify have.  `write_kv_blocks` moves whole blocks of the block view
to block ids: a whole prompt's rows, which start a block and run on
from there (the `prefill` program).  The chip pays a scatter by the
window, not by its bytes — 0.11 us a row whatever its width — so a
1,024-token prompt's 24,576 rows cost 2.9 ms where its 1,536 blocks
cost 0.27 (PERF.md section 6, PR 40).  Which writer a program takes
follows from which program it is, and from nothing else.  A head
shard under tensor parallelism is a contiguous slice of the
merged axis (`KV_TP_SPEC`).  Block 0 is reserved as the NULL block:
inactive slots' table entries (and padding writes) all point at it,
so dead lanes scribble harmlessly instead of branching — that is what
keeps the decode step's shapes static.

Allocation is host-side (the free list is python state; the device
never sees it) — the allocator hands block ids to the scheduler, which
bakes them into the block-table arrays fed to the jitted step.

Quantized mode (`quantization="int8"`, the engine's
`kv_quantization="int8"`): the pool stores int8 with a
per-token-slot symmetric scale vector `kv_scale`
[n_layers, 2, num_blocks * block_size] f32 — the `serving/quantize.py`
amax/127 calibration idiom applied at token granularity, so appends
never touch already-written slots (no requantization drift; the
round-trip error is the textbook |x - deq| <= scale/2 bound, pinned by
test).  KV bytes per token drop from 2*L*h*d*itemsize to
2*L*(h*d + 4): ~1.9x block-pool residency vs f16 at equal pool bytes
for h*d >= 64.  Reads dequantize in the paged-attention kernel (or the
XLA fallback) — a dequantized pool never exists in HBM.
`logical_nbytes` vs `physical_nbytes` report both sides for the
`memory_kv_pool_*` gauges (docs/observability.md).

The latent form (`rows=1`): a model whose attention caches ONE row a
token a layer — a compressed latent every head reads, with the one
rotated key all heads share behind it (decoder.py,
`latent_attention`) — says so in its `kv_geometry()`, and the pool is
[n_layers, 1, num_blocks * block_size, width]: the same array with
one row where the K/V form has two, so every function here and every
block program (copy, spill, restore) follows it by its shape.  A
latent row's columns need fill no whole lane tiles (576 is four and a
half), so the pool stores them padded to the next multiple of
`LANES` — what the chip's tiling would store anyway, made explicit so
that a kernel's block is whole tiles; the padding is written as zeros,
`gather_kv` cuts it off, `logical_nbytes` leaves it out and
`physical_nbytes` counts it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

#: block id 0 is never allocated; see module docstring
NULL_BLOCK = 0
#: columns of a lane tile: a latent row is stored padded to a multiple
LANES = 128
#: the pool under tensor parallelism: a head shard is a contiguous
#: slice of the merged heads * head_dim axis
KV_TP_SPEC = P(None, None, None, "tp")


def quantize_kv_tokens(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-token int8 quantization of K or V slabs
    `x` [..., heads, head_dim]: one amax/127 scale per leading index
    (the serving/quantize.py idiom at token granularity).  Returns
    (int8 values, f32 scales [...]) — jit-traceable, so the engine's
    prefill/decode steps quantize on block write inside the one
    compiled program."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv_tokens(q, scale):
    """Inverse of `quantize_kv_tokens` (tests and the XLA read path)."""
    return q.astype(jnp.float32) * scale[..., None, None]


def pool_geometry(model) -> Tuple[int, int, int]:
    """(layers, KV heads, head dim) of the pool `model` needs: what
    the model says of itself (`kv_geometry()`: grouped heads, a head
    that is not hidden / heads, one latent "head" as wide as the
    cached row), else the plain multi-head reading of `CausalLM`'s
    fields.  With `pool_rows`, the one place the pool's geometry is
    read from a model."""
    geometry = getattr(model, "kv_geometry", None)
    if geometry is not None:
        return tuple(int(n) for n in geometry()[:3])
    return (int(model.n_block), int(model.n_head),
            int(model.hidden_size) // int(model.n_head))


def pool_rows(model) -> int:
    """Rows a cached token holds in a layer: 2 (a key and a value), or
    the fourth number of a `kv_geometry()` that has one (1: the latent
    form)."""
    geometry = getattr(model, "kv_geometry", lambda: ())()
    return int(geometry[3]) if len(geometry) > 3 else 2


def state_geometry(model):
    """What the recurrent pool holds a lane for `model`: (state
    layers, (shape, dtype) of a layer's scan state, (shape, dtype) of
    its convolution tail), as the model says of itself
    (`state_geometry()`: hybrid.py), or None for a model all of whose
    state is keys and values.  The one place it is read from a model."""
    geometry = getattr(model, "state_geometry", None)
    return geometry() if geometry is not None else None


def block_view(x, block_size: int):
    """The pool [L, rows, slots, h*d] (or its scale vectors [L, 2, slots])
    with the slot axis split into [num_blocks, block_size] — what the
    paged kernel's BlockSpecs index by block-table entry.  A bitcast
    wherever `block_size` rows fill whole tiles (16 for bf16, 8 for
    f32), so the decode program never holds a second pool."""
    return x.reshape(*x.shape[:2], -1, block_size, *x.shape[3:])


def _row_index(kv, slots):
    """(layer, k/v, slot) index arrays that broadcast to
    [L, rows, *slots.shape] of pool `kv` (rows: 2, or 1 in the latent
    form): a gather or scatter through them moves single
    ROWS of the pool — whole blocks of its block view, `slots` then
    being block ids — which XLA does on the pool as it lies (indexing
    `kv[:, :, slots]` instead makes the window span the layer and k/v
    axes, and XLA relays the whole pool out to bring them inward)."""
    n_layers, rows = kv.shape[:2]
    ones = (1,) * slots.ndim
    return (jnp.arange(n_layers).reshape(n_layers, 1, *ones),
            jnp.arange(rows).reshape(1, rows, *ones), slots[None, None])


def gather_kv(kv, kv_scale, tok_idx, n_head: int,
              head_dim: Optional[int] = None):
    """Token slots `tok_idx` [...] of every layer as (ctx_k, ctx_v)
    [L, ..., heads, head_dim] — the concat-oracle, chunk-prefill and
    verify read paths; of a latent pool (the cached rows, None):
    there is one row a token.  Heads are split out of what was
    GATHERED, never of the pool, and columns past heads * `head_dim`
    (a latent row's padding) are cut off it; an int8 pool dequantizes
    here by `kv_scale` [L, 2, slots]."""
    idx = _row_index(kv, tok_idx)
    rows = kv[idx]
    if head_dim is not None and n_head * head_dim != rows.shape[-1]:
        rows = rows[..., :n_head * head_dim]
    rows = rows.reshape(*rows.shape[:-1], n_head, -1)
    if kv.dtype == jnp.int8:
        rows = dequantize_kv_tokens(rows, kv_scale[idx])
    return rows[:, 0], (rows[:, 1] if kv.shape[1] == 2 else None)


def _stored_rows(kv, new_k, new_v):
    """new_k / new_v [L, n, heads, head_dim] as pool `kv` stores them:
    (rows [L, rows, n, columns] — a key and a value, or the one latent
    row padded with zeros to the pool's columns — in the pool's dtype,
    their scales [L, rows, n] or None).  An int8 pool quantizes here,
    a token at a time (`quantize_kv_tokens`), so a dequantized pool
    never exists and appends never touch already-written slots."""
    L, n = new_k.shape[:2]
    rows = jnp.stack([new_k] if new_v is None else [new_k, new_v],
                     axis=1)                       # [L, rows, n, h, d]
    scales = None
    if kv.dtype == jnp.int8:
        rows, scales = quantize_kv_tokens(rows)
    rows = rows.reshape(L, kv.shape[1], n, -1).astype(kv.dtype)
    if rows.shape[-1] != kv.shape[-1]:
        rows = jnp.pad(rows, ((0, 0),) * 3
                       + ((0, kv.shape[-1] - rows.shape[-1]),))
    return rows, scales


def write_kv(kv, kv_scale, dest, new_k, new_v):
    """Write new_k / new_v [L, n, heads, head_dim] into token slots
    `dest` [n] of every layer — the pool write of the programs whose
    rows fall where they fall: decode (one a lane), a chunk (a start
    that need not be a block's) and verify; a latent pool takes its
    one row a token as `new_k`, `new_v` None (`_stored_rows`).  ONE
    scatter of rows: index (layer, k/v, slot), window a single merged
    row, so XLA updates the donated pool in place whatever `n` is (a
    `kv.at[:, 0, dest]` window spans the layer axis and costs two
    whole-pool relayouts).  An int8 pool's scales are written beside
    its rows; otherwise `kv_scale` passes through.  Duplicate slots
    only ever name the null block (dead lanes, padding), where any
    winner is harmless."""
    rows, scales = _stored_rows(kv, new_k, new_v)
    idx = _row_index(kv, dest)
    if scales is not None:
        kv_scale = kv_scale.at[idx].set(scales)
    return kv.at[idx].set(rows), kv_scale


def write_kv_blocks(kv, kv_scale, blocks, new_k, new_v, block_size: int):
    """`write_kv` for rows that start a block and run on from there —
    a whole prompt's, positions 0..n-1 (the `prefill` program): new_k
    / new_v [L, n, heads, head_dim] go into pool blocks `blocks`
    [ceil(n / block_size)], in order.  ONE scatter into `block_view`
    whose index is (layer, k/v, block) and whose window is a whole
    block [block_size, columns] — the chip pays a scatter by the
    window, not by its bytes, and a block is block_size rows in one
    (whole tile rows of the pool, so still in place on the donated
    array; the reshape back is the bitcast `block_view` is).  Every
    block named is written WHOLE: the caller names the null block for
    those past the prompt's last row, and the rows of the last real
    block past that row take whatever `new_k` holds there (the padded
    positions' finite keys and values; zeros short of a whole block) —
    slots no reader looks at, every read being cut at the lane's
    context length, until a decode round has written them."""
    short = blocks.shape[0] * block_size - new_k.shape[1]
    if short:
        new_k, new_v = (
            x if x is None else jnp.pad(x, ((0, 0), (0, short), (0, 0),
                                            (0, 0)))
            for x in (new_k, new_v))
    rows, scales = _stored_rows(kv, new_k, new_v)
    idx = _row_index(kv, blocks)
    if scales is not None:
        kv_scale = block_view(kv_scale, block_size).at[idx].set(
            block_view(scales, block_size)).reshape(kv_scale.shape)
    kv = block_view(kv, block_size).at[idx].set(
        block_view(rows, block_size)).reshape(kv.shape)
    return kv, kv_scale


class RecurrentStatePool:
    """The second kind of state: what a state-space layer carries for a
    lane, of a FIXED size whatever the lane's context, so it is found
    by the lane's slot and not through a block table.  A state layer
    holds two arrays, both functional state like `PagedKVCache.kv`
    (the `decode` and `prefill` programs take them donated, inside the
    lane state, and hand them back):

      * "ssm": `[max_slots, *state shape]` (float32: the scan's state
        is summed into for a lane's whole life);
      * "conv": `[rows, max_slots, channels]`, the rows before the
        next position the convolution still needs — the slot axis
        second, so that the two minor axes fill whole tiles (3 rows
        innermost-but-one would be padded to 16).

    One array a layer, not one stacked over layers: a decode round
    rewrites every live lane's state of a layer whole, which XLA does
    in place on a donated array and would do through a slice of a
    stacked one.  Admission needs no reset of its own — a prefill
    starts from no state and leaves the state after the prompt in the
    lane's slot (`admit_state`) — release needs nothing, and a
    preempted lane's state is rebuilt by the prefill of its resume."""

    def __init__(self, geometry, max_slots: int):
        self.n_layers, (ssm, ssm_dtype), (conv, conv_dtype) = geometry
        self.max_slots = max_slots
        self.shapes = {
            "ssm": ((max_slots,) + tuple(ssm), jnp.dtype(ssm_dtype)),
            "conv": ((conv[0], max_slots) + tuple(conv[1:]),
                     jnp.dtype(conv_dtype))}

    def zeros(self):
        """The pool, empty, on the default device."""
        return {kind: tuple(jnp.zeros(shape, dtype)
                            for _ in range(self.n_layers))
                for kind, (shape, dtype) in self.shapes.items()}

    @property
    def nbytes(self) -> int:
        return self.n_layers * sum(
            math.prod(shape) * dtype.itemsize
            for shape, dtype in self.shapes.values())


def admit_state(pool, slot, fresh):
    """`pool` with `fresh` — the state a prefill of ONE row left, in
    the pool's form at a batch of 1 — in lane `slot`: whatever the
    slot held before is gone (inside the jitted prefill)."""
    zero = jnp.int32(0)
    return {
        "ssm": tuple(
            jax.lax.dynamic_update_slice(
                old, new.astype(old.dtype),
                (slot,) + (zero,) * (old.ndim - 1))
            for old, new in zip(pool["ssm"], fresh["ssm"])),
        "conv": tuple(
            jax.lax.dynamic_update_slice(
                old, new.astype(old.dtype),
                (zero, slot) + (zero,) * (old.ndim - 2))
            for old, new in zip(pool["conv"], fresh["conv"]))}


class BlockAllocator:
    """Free-list allocator over `num_blocks` KV blocks (block 0
    reserved as the null block), with per-block REFERENCE COUNTS so the
    prefix cache (serving/generation/prefix_cache.py) can share one
    committed block between many sequences (and the radix tree itself).
    `alloc` hands out blocks at refcount 1; `share` pins an extra
    reference; `free` drops one reference per listed id and only
    returns a block to the free list when its count reaches zero.
    LIFO reuse keeps recently-freed blocks hot.  Not thread-safe — the
    engine loop is the only caller."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the "
                             "reserved null block)")
        self.num_blocks = num_blocks
        # pop() takes from the tail: ascending init → low ids first
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        #: block id -> live reference count (allocated blocks only)
        self._refs: Dict[int, int] = {}

    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the null block)."""
        return self.num_blocks - 1

    def available(self) -> int:
        return len(self._free)

    def occupancy(self) -> float:
        """Fraction of allocatable blocks currently held — the
        cache-pressure gauge."""
        return len(self._refs) / self.capacity

    def ref_count(self, block: int) -> int:
        """Live references on `block` (0 = free / never allocated)."""
        return self._refs.get(block, 0)

    def n_shared(self) -> int:
        """Blocks held by more than one reference — the shared half of
        the pool's shared/exclusive residency split."""
        return sum(1 for c in self._refs.values() if c > 1)

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """n blocks, or None when the pool can't cover the request
        (the caller preempts or defers admission; partial allocations
        are never handed out)."""
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for blk in blocks:
            self._refs[blk] = 1
        return blocks

    def share(self, blocks: List[int]) -> None:
        """Pin one extra reference on each (already-allocated) block —
        the prefix cache's hit path and the radix tree's own hold."""
        for blk in blocks:
            if blk not in self._refs:
                raise ValueError(
                    f"cannot share unallocated block {blk}")
        for blk in blocks:
            self._refs[blk] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per listed id.  The guard validates the
        WHOLE request before mutating anything: freeing an id that is
        already on the free list, out of range, the null block — or
        listed more times than it has references (a duplicate id inside
        one call is a double free too) — raises instead of silently
        corrupting the pool."""
        need: Dict[int, int] = {}
        for blk in blocks:
            if blk == NULL_BLOCK:
                raise ValueError("cannot free the null block")
            if not 0 < blk < self.num_blocks:
                raise ValueError(f"block id {blk} out of range")
            need[blk] = need.get(blk, 0) + 1
        for blk, n in need.items():
            if n > self._refs.get(blk, 0):
                raise ValueError(f"double free of block {blk}")
        for blk in blocks:
            self._refs[blk] -= 1
            if self._refs[blk] == 0:
                del self._refs[blk]
                self._free.append(blk)


class PagedKVCache:
    """The device pool + its allocator.  `kv` is functional state: the
    jitted prefill/decode steps take it as a donated argument and
    return the updated array; the engine swaps its reference."""

    def __init__(self, n_layers: int, num_blocks: int, block_size: int,
                 n_head: int, head_dim: int, dtype=jnp.float32,
                 quantization: Optional[str] = None, rows: int = 2):
        if quantization not in (None, "int8"):
            raise ValueError(f"unsupported KV quantization "
                             f"{quantization!r}; use None or 'int8'")
        if rows not in (1, 2):
            raise ValueError(f"a cached token holds 1 or 2 rows a "
                             f"layer, not {rows}")
        if rows == 1 and quantization is not None:
            raise ValueError("a latent pool has no quantized form")
        self.n_layers = n_layers
        #: rows a token holds in a layer: a key and a value, or 1 (the
        #: latent form)
        self.rows = rows
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.n_head = n_head
        self.head_dim = head_dim
        self.quantization = quantization
        #: the dtype reads dequantize to (and the pool dtype itself
        #: when quantization is off)
        self.logical_dtype = jnp.dtype(dtype)
        store = jnp.int8 if quantization == "int8" else dtype
        #: columns of a row as stored: a latent row padded to whole
        #: lane tiles (module docstring)
        self.row_width = n_head * head_dim if rows == 2 \
            else -(-n_head * head_dim // LANES) * LANES
        self.kv = jnp.zeros(
            (n_layers, rows, num_blocks * block_size, self.row_width),
            store)
        #: per-token-slot dequant scales (int8 mode only) — functional
        #: state like `kv`: the jitted steps take and return it
        self.kv_scale = (
            jnp.ones((n_layers, 2, num_blocks * block_size),
                     jnp.float32)
            if quantization == "int8" else None)
        self.allocator = BlockAllocator(num_blocks)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold n_tokens."""
        return -(-n_tokens // self.block_size)

    @property
    def slab_shape(self) -> Tuple[int, int, int, int]:
        """One block's rows across every layer, [L, rows, block_size,
        columns as stored] — the unit the host tier spills and
        restores (its scales are `slab_shape[:3]`)."""
        return (self.n_layers, self.rows, self.block_size,
                self.row_width)

    def read_block(self, blk: int):
        """Block `blk`'s slab and its scales (None unquantized), still
        on the device — what the prefix cache spills."""
        rows = slice(blk * self.block_size, (blk + 1) * self.block_size)
        return (self.kv[:, :, rows],
                None if self.kv_scale is None
                else self.kv_scale[:, :, rows])

    @property
    def physical_nbytes(self) -> int:
        """Bytes the pool actually occupies in HBM (int8 values plus
        their scale vectors in quantized mode)."""
        total = self.kv.size * self.kv.dtype.itemsize
        if self.kv_scale is not None:
            total += self.kv_scale.size * self.kv_scale.dtype.itemsize
        return total

    @property
    def logical_nbytes(self) -> int:
        """Bytes the same pool would occupy unquantized at
        `logical_dtype`, a latent row's padding left out —
        physical/logical is the residency win the `memory_kv_pool_*`
        gauges report."""
        return (self.kv.size // self.row_width * self.n_head
                * self.head_dim * self.logical_dtype.itemsize)

    @property
    def token_nbytes(self) -> int:
        """Logical bytes one cached token holds over all layers."""
        return (self.n_layers * self.rows * self.n_head * self.head_dim
                * self.logical_dtype.itemsize)

    @property
    def nbytes(self) -> int:
        return self.physical_nbytes
