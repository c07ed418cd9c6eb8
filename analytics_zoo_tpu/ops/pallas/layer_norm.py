"""Fused LayerNorm — Pallas TPU kernels (forward AND backward).

XLA compiles an unfused LayerNorm into several elementwise/reduce HLOs
that each round-trip the [rows, d] activation through HBM; this kernel
streams every row block through VMEM exactly once per pass.  The
forward emits the per-row mean and rstd (f32 [rows, 1]) so the
backward never recomputes the statistics; the backward emits dx plus
PER-BLOCK partial sums for dscale/dbias ([num_blocks, 1, d] f32,
reduced to [d] by one tiny XLA sum outside the kernel — emitting
partials keeps every grid step's output block disjoint, so the kernel
needs no cross-step accumulation state; the unit middle axis makes
each step's (1, d) block the array's own last two dims, which is what
Mosaic's (8, 128) block rule asks of a one-row block).

Numerics match `flax.linen.LayerNorm` defaults on purpose (same
formula, same order): stats in f32 with the fast-variance form
`var = max(0, E[x^2] - E[x]^2)`, `y = (x - mu) * (rsqrt(var + eps) *
scale) + bias`.  The dispatch layer (`ops.normalization.layer_norm`)
uses the plain-XLA mirror of the same math off-TPU, so CPU test runs
are bit-compatible with the pre-fusion flax layer.

`block_rows` is tunable (ops/tuning); rows must tile it and d rides
whole in each block (LayerNorm reduces over d, so splitting lanes
would need a second pass).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: measured-default row block; real hosts re-tune via ops/tuning
DEFAULT_BLOCK_ROWS = 512


def fit_block_rows(block_rows: int, rows: int) -> int:
    """Shrink to a divisor of `rows` (pow2 halving, floor 8)."""
    blk = min(int(block_rows), rows)
    while blk >= 8 and rows % blk:
        blk //= 2
    return blk


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref,
                   *, eps: float):
    # x_ref [br, d]; scale/bias [1, d]; y [br, d]; mean/rstd [br, 1]
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.maximum(0.0, jnp.mean(x * x, axis=1, keepdims=True)
                      - mu * mu)
    rstd = jax.lax.rsqrt(var + eps)
    mul = rstd * scale_ref[...].astype(jnp.float32)
    y = (x - mu) * mul + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mu
    rstd_ref[...] = rstd


def _ln_bwd_kernel(x_ref, scale_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dscale_ref, dbias_ref):
    # dx = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat))
    # with xhat = (x - mu) * rstd, dxhat = g * scale; dscale/dbias land
    # as per-row-block partials (reduced outside).
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    xhat = (x - mean_ref[...]) * rstd_ref[...]
    dxhat = g * scale_ref[...].astype(jnp.float32)
    c1 = jnp.mean(dxhat, axis=1, keepdims=True)
    c2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd_ref[...] * (dxhat - c1 - xhat * c2)
                   ).astype(dx_ref.dtype)
    dscale_ref[...] = jnp.sum(g * xhat, axis=0, keepdims=True)
    dbias_ref[...] = jnp.sum(g, axis=0, keepdims=True)


def _ln_fwd(x, scale, bias, *, eps: float, block_rows: int,
            out_dtype, interpret: bool):
    rows, d = x.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        partial(_ln_fwd_kernel, eps=eps),
        out_shape=[jax.ShapeDtypeStruct((rows, d), out_dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret,
    )(x, scale.reshape(1, d), bias.reshape(1, d))


def _ln_bwd(x, scale, mean, rstd, g, *, block_rows: int, interpret: bool):
    rows, d = x.shape
    nb = rows // block_rows
    dx, dscale_p, dbias_p = pl.pallas_call(
        _ln_bwd_kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, d), x.dtype),
                   jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 1, d), jnp.float32)],
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret,
    )(x, scale.reshape(1, d), mean, rstd, g)
    return dx, dscale_p.sum(axis=(0, 1)), dbias_p.sum(axis=(0, 1))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _layer_norm(x, scale, bias, eps, block_rows, out_dtype, interpret):
    y, _, _ = _ln_fwd(x, scale, bias, eps=eps, block_rows=block_rows,
                      out_dtype=out_dtype, interpret=interpret)
    return y


def _layer_norm_vjp_fwd(x, scale, bias, eps, block_rows, out_dtype,
                        interpret):
    y, mean, rstd = _ln_fwd(x, scale, bias, eps=eps,
                            block_rows=block_rows, out_dtype=out_dtype,
                            interpret=interpret)
    return y, (x, scale, bias, mean, rstd)


def _layer_norm_vjp_bwd(eps, block_rows, out_dtype, interpret, res, g):
    x, scale, bias, mean, rstd = res
    dx, dscale, dbias = _ln_bwd(x, scale, mean, rstd, g,
                                block_rows=block_rows,
                                interpret=interpret)
    return dx, dscale.astype(scale.dtype), dbias.astype(bias.dtype)


_layer_norm.defvjp(_layer_norm_vjp_fwd, _layer_norm_vjp_bwd)


def layer_norm_pallas(x, scale, bias, *, eps: float = 1e-6,
                      block_rows: int = None, out_dtype=None,
                      interpret: bool = None):
    """Fused LayerNorm over the LAST axis of `x` [..., d] (params
    `scale`/`bias` are [d]).  Raises ValueError when the shape cannot
    tile — callers go through `ops.normalization.layer_norm`, which
    falls back to the XLA mirror instead."""
    *lead, d = x.shape
    rows = 1
    for s in lead:
        rows *= s
    if out_dtype is None:
        out_dtype = jnp.result_type(x.dtype, scale.dtype, bias.dtype)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if block_rows is None:
        block_rows = DEFAULT_BLOCK_ROWS
    block_rows = fit_block_rows(block_rows, rows)
    if rows % block_rows or rows < 8:
        raise ValueError(
            f"layer_norm_pallas: rows {rows} does not tile block_rows "
            f"{block_rows}")
    x2 = x.reshape(rows, d)
    y = _layer_norm(x2, scale, bias, float(eps), int(block_rows),
                    jnp.dtype(out_dtype), bool(interpret))
    return y.reshape(*lead, d)
