"""The grouped product — Pallas TPU kernel.

`rows [m, k]` lie sorted by group, `sizes[i]` of them to group i, and
group i's rows are multiplied by `kernels[i] [k, n]`.  The kernel is
megablox's `gmm` (Gale et al., "MegaBlocks", MLSys 2023; shipped with
JAX as `jax.experimental.pallas.ops.tpu.megablox`), held to this
repo's contract and tiling by the wrapper below.

Grid (n tiles, row-tile visits, k tiles), k innermost.  A row tile of
`tile_m` rows is visited once for every group that has a row in it —
the visits and their groups are computed from `sizes` on the device
and prefetched as scalars, so the weight block's index map reads
"this visit's group" — and a visit multiplies `[tile_m, tile_k]` rows
by `[tile_k, tile_n]` of that ONE group's kernel into a float32 VMEM
accumulator, storing under a mask the rows that are the group's.  So

  * a group with no row is never visited: its weights are not read;
  * a group with rows streams its `[k, n]` once a row tile it touches
    (once, where the group fits a tile), not once a row of the buffer;
  * the arithmetic is `tile_m` rows a visit, whatever `m` is: with
    `tile_m` about the size of a group it is the work that was routed.

`jax.lax.ragged_dot`'s own TPU lowering multiplies every row tile of
512 by every group that touches it and masks afterwards — the same
answers at thirty times the arithmetic when a group holds 4-10 rows
(docs/kernels.md, "The grouped product").

Rows past `sizes.sum()` belong to no group: no visit stores them, and
what the result holds there is whatever the buffer held.  A caller
that sums over the result masks them out (`ExpertLayer` does).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def grouped_matmul_pallas(rows, kernels, sizes, *, tile_m: int,
                          tile_k: int, tile_n: int,
                          interpret: Optional[bool] = None):
    """rows [m, k] x kernels [g, k, n] by sizes int32 [g] -> [m, n] in
    the operands' type (float32 accumulation), as `jax.lax.ragged_dot`
    gives it for the rows inside the groups.  `tile_m` need not divide
    `m` (the rows are padded up to it); `tile_k` and `tile_n` are
    multiples of 128 or the whole dimension."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    m = rows.shape[0]
    pad = -m % tile_m
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, kernels, sizes.astype(jnp.int32),
              jnp.result_type(rows.dtype, kernels.dtype),
              (tile_m, tile_k, tile_n), None, None, False, interpret)
    return out[:m] if pad else out
