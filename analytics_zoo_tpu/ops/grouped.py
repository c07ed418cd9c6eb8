"""The grouped product — the dispatch point for an expert layer's
stacked projections.

`grouped_matmul(rows, kernels, sizes)`: `rows [m, k]` sorted by group,
`kernels [g, k, n]`, `sizes int32 [g]` rows to each group in order;
rows past `sizes.sum()` belong to no group.  Returns `[m, n]` in the
operands' type, float32 accumulation.

impl="auto" picks the Pallas grouped kernel (ops/pallas/
grouped_matmul.py) on a TPU where `k` and `n` tile, and
`jax.lax.ragged_dot` everywhere else — the CPU, a width that is no
multiple of 128, a program GSPMD partitions over a mesh (Mosaic
refuses a kernel it would have to partition, and no engine places
stacked experts across chips yet).  The choice reads the platform and
the shape and nothing a user sets.  On the rows inside the groups
both paths give the same sums; past them `ragged_dot` gives zeros and
the kernel whatever its buffer held, so a caller masks those rows
before it sums over them.

The kernel's tiles come from the autotuner's table by shape
(`ops/tuning/default_tables.json`, kernel `grouped_matmul`, keyed by
m, g, k, n: the rows measured on the chip) and, for a shape the table
has not, from `default_tiling`: a row tile about the size of a group,
weight blocks as large as VMEM takes.  No search runs here.

Every product built is counted in `BUILT` by the path it took and the
row tile it chose (`built()` hands out a copy); `decoder.ExpertCounters`
shows the counts in its engine's registry (docs/observability.md).
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

KERNEL, RAGGED_DOT = "kernel", "ragged_dot"

#: (path, row tile; 0 on `ragged_dot`) -> grouped products built in
#: this process: one a call of `grouped_matmul`, so one a projection of
#: an expert layer a program traced
BUILT: "collections.Counter[Tuple[str, int]]" = collections.Counter()
_built_lock = threading.Lock()     # engines trace from their own threads

#: bytes the builtin tiling gives one `[tile_k, tile_n]` weight block:
#: the pipeline holds two, beside the rows, the result and the float32
#: accumulator, inside the 16 MiB of VMEM a kernel gets by default
WEIGHT_BLOCK_BYTES = 4 << 20


def _kernel_supported(rows, kernels) -> bool:
    from analytics_zoo_tpu.parallel.sharding import traced_mesh
    try:
        platform = jax.default_backend()
    except Exception:
        return False
    k, n = kernels.shape[1:]
    return (platform == "tpu" and traced_mesh() is None
            and rows.dtype == kernels.dtype
            and rows.dtype in (jnp.bfloat16, jnp.float32)
            and k % 128 == 0 and n % 128 == 0)


def _count(path: str, tile_m: int) -> None:
    with _built_lock:
        BUILT[path, tile_m] += 1


def built() -> "collections.Counter[Tuple[str, int]]":
    """`BUILT` as it stands, a copy of the reader's own."""
    with _built_lock:
        return collections.Counter(BUILT)


def _largest_tile(dim: int, limit: int) -> int:
    """The largest multiple of 128 that divides `dim` and is at most
    `limit` (128 where none is)."""
    best = 128
    for tile in range(128, dim + 1, 128):
        if dim % tile == 0 and tile <= limit:
            best = tile
    return best


def default_tiling(m: int, g: int, k: int, n: int,
                   itemsize: int) -> Dict[str, int]:
    """The tiles of a shape the table has no row for.  Rows: the power
    of two at or above the mean group, 16 to 128 — a tile a group, so
    a visit multiplies about the rows that were routed.  Weights: the
    whole `k` where a `[k, 128]` block fits (no second pass over the
    accumulator), then as much of `n` as `WEIGHT_BLOCK_BYTES` takes."""
    from analytics_zoo_tpu.ops.tuning import pow2_bucket
    tile_m = min(128, max(16, pow2_bucket(-(-m // max(g, 1)))))
    tile_k = _largest_tile(k, WEIGHT_BLOCK_BYTES // (128 * itemsize))
    tile_n = _largest_tile(n, WEIGHT_BLOCK_BYTES // (tile_k * itemsize))
    return {"tile_m": tile_m, "tile_k": tile_k, "tile_n": tile_n}


def _tiling(m: int, g: int, k: int, n: int, dtype) -> Dict[str, int]:
    from analytics_zoo_tpu.ops import tuning
    builtin = default_tiling(m, g, k, n, jnp.dtype(dtype).itemsize)
    cfg = tuning.get_config("grouped_matmul",
                            {"m": m, "g": g, "k": k, "n": n}, dtype,
                            default=builtin)
    # the table's keys are bucketed to powers of two: a neighbour's
    # row may hold tiles that do not divide this shape
    if k % cfg["tile_k"] or n % cfg["tile_n"]:
        return builtin
    return cfg


def grouped_matmul(rows, kernels, sizes, *, impl: str = "auto",
                   tile_m: Optional[int] = None,
                   tile_k: Optional[int] = None,
                   tile_n: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """rows [m, k] x kernels [g, k, n] by sizes [g] -> [m, n].  `impl`:
    "auto", "kernel" or "ragged_dot"; tiles default to the table's
    answer for the shape."""
    m, k = rows.shape
    g, _, n = kernels.shape
    if impl == "auto":
        impl = KERNEL if _kernel_supported(rows, kernels) else RAGGED_DOT
    if impl == RAGGED_DOT:
        _count(RAGGED_DOT, 0)
        return jax.lax.ragged_dot(rows, kernels, sizes)
    if impl != KERNEL:
        raise ValueError(f"unknown grouped_matmul impl {impl!r}; use "
                         "'auto', 'kernel' or 'ragged_dot'")
    from analytics_zoo_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul_pallas)
    if tile_m is None or tile_k is None or tile_n is None:
        cfg = _tiling(m, g, k, n, rows.dtype)
        tile_m = tile_m or cfg["tile_m"]
        tile_k = tile_k or cfg["tile_k"]
        tile_n = tile_n or cfg["tile_n"]
    _count(KERNEL, tile_m)
    return grouped_matmul_pallas(rows, kernels, sizes, tile_m=tile_m,
                                 tile_k=tile_k, tile_n=tile_n,
                                 interpret=interpret)
