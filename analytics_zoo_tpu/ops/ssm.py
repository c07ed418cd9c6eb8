"""Selective state-space (Mamba-2) operators: the causal depthwise
convolution in front of the scan (bias-free, also LFM2's short
convolution: serving/generation/hybrid.py), the chunked scan a prefill
runs, and the one-token update a decode round runs.

The recurrence, for head `h` of group `g` with state `H_h` [P, S]:

    H_h(t) = exp(dt_h(t) * A_h) * H_h(t-1) + dt_h(t) * x_h(t) (x) B_g(t)
    y_h(t) = H_h(t) . C_g(t) + D_h * x_h(t)

`ssm_scan` computes it over a whole sequence in the state-space-duality
form: inside a chunk of `chunk` positions a masked matrix product (the
decay between two positions of a chunk is a lower-triangular matrix),
across chunks the recurrence over the chunks' end states.  `ssm_step`
is one step of it for every row of a batch.  Both keep the state in
float32 and multiply at `highest` precision: the state is what a lane
carries for its whole life, and what the products cost is nothing
beside the projections around them.

A position that must not advance the state (a bucket's padding after
the prompt's `length` tokens) is given dt = 0: its decay is 1 and it
adds nothing, so the state after the sequence is the state after its
real tokens.

`impl`: "auto" and "xla" are the one `jax.numpy` form today; the
dispatcher is where a Pallas kernel would be chosen (`ops/attention.py`
and `ops/dense.py` have the pattern).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _check_impl(impl: str) -> None:
    if impl not in ("auto", "xla"):
        raise ValueError(f"unknown ssm impl {impl!r}; 'auto' or 'xla'")


def causal_conv(x, kernel, bias, tail=None):
    """Causal depthwise convolution over time: x [b, t, c], kernel
    [k, c] (tap k-1 on the current position), bias [c] (None: none),
    `tail` [k-1, b, c] the k-1 rows before x (None: zeros, a
    sequence's start).  float32 out, before the activation."""
    k = kernel.shape[0]
    b, t, c = x.shape
    xf = x.astype(jnp.float32)
    if tail is None:
        before = jnp.zeros((b, k - 1, c), jnp.float32)
    else:
        before = jnp.swapaxes(tail, 0, 1).astype(jnp.float32)
    padded = jnp.concatenate([before, xf], axis=1)      # [b, k-1+t, c]
    w = kernel.astype(jnp.float32)
    out = sum(padded[:, j:j + t] * w[j] for j in range(k))
    return out if bias is None else out + bias.astype(jnp.float32)


def conv_tail(x, length, k: int):
    """The last k-1 rows of x [b, t, c] before position `length` [b]
    (zeros where the sequence is shorter), as [k-1, b, c]: what the
    next position's convolution needs of the past."""
    b, t, _ = x.shape
    idx = length[None, :] - (k - 1) + jnp.arange(k - 1)[:, None]  # [k-1,b]
    rows = x[jnp.arange(b)[None, :], jnp.clip(idx, 0, t - 1)]
    return jnp.where((idx >= 0)[..., None], rows, 0).astype(x.dtype)


def ssm_scan(x, dt, A, B, C, D, *, chunk: int, h0=None,
             impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a sequence, by chunks.

    x [b, t, H, P], dt [b, t, H] (after the softplus; 0 where a
    position must not advance the state), A [H] (negative), B, C
    [b, t, G, S] (H a multiple of G: H/G heads share a group), D [H],
    h0 [b, H, P, S] the state before the first position (None: zeros).
    Returns y [b, t, H, P] float32 and the state after the last
    position [b, H, P, S] float32."""
    _check_impl(impl)
    b, t, H, P = x.shape
    G, S = B.shape[-2:]
    rep = H // G
    pad = (-t) % chunk
    f32 = jnp.float32
    x, dt, B, C = (v.astype(f32) for v in (x, dt, B, C))
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)]
                               + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    n = (t + pad) // chunk
    x = x.reshape(b, n, chunk, G, rep, P)
    dt = dt.reshape(b, n, chunk, G, rep)
    B = B.reshape(b, n, chunk, G, S)
    C = C.reshape(b, n, chunk, G, S)
    a = dt * A.astype(f32).reshape(G, rep)              # log decay a step
    cum = jnp.cumsum(a, axis=2)                         # [b, n, l, G, r]
    xdt = x * dt[..., None]                             # dt_j x_j

    # inside a chunk: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
    cb = jnp.einsum("bnigs,bnjgs->bngij", C, B, precision=_HIGHEST)
    by_head = jnp.moveaxis(cum, 2, -1)                  # [b, n, G, r, l]
    seg = by_head[..., :, None] - by_head[..., None, :]  # [b,n,G,r,i,j]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # (the exponent is masked too: above the diagonal it is positive
    # and may overflow before the where drops it)
    mixed = cb[:, :, :, None] * jnp.where(
        causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    y = jnp.einsum("bngrij,bnjgrp->bnigrp", mixed, xdt,
                   precision=_HIGHEST)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)              # [b, n, l, G, r]
    local = jnp.einsum("bnjgs,bnjgr,bnjgrp->bngrps", B, to_end, xdt,
                       precision=_HIGHEST)
    # across chunks: the recurrence over the end states
    whole = jnp.exp(cum[:, :, -1])                      # [b, n, G, r]
    start = (jnp.zeros((b, G, rep, P, S), f32) if h0 is None
             else h0.astype(f32).reshape(b, G, rep, P, S))

    def carry(h, step):
        decay_n, local_n = step
        return decay_n[..., None, None] * h + local_n, h

    last, before = jax.lax.scan(
        carry, start, (jnp.moveaxis(whole, 1, 0),
                       jnp.moveaxis(local, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                 # [b,n,G,r,P,S]
    # what the state before a chunk gives each of its positions
    y = y + jnp.einsum("bnigs,bngrps,bnigr->bnigrp", C, before,
                       jnp.exp(cum), precision=_HIGHEST)
    y = y + x * D.astype(f32).reshape(G, rep)[..., None]
    y = y.reshape(b, n * chunk, H, P)[:, :t]
    return y, last.reshape(b, H, P, S)


def ssm_step(h, x, dt, A, B, C, D, *, impl: str = "auto"
             ) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every row: h [b, H, P, S]
    float32, x [b, H, P], dt [b, H], A, D [H], B, C [b, G, S].
    Returns y [b, H, P] float32 and the new state."""
    _check_impl(impl)
    b, H, P, S = h.shape
    G = B.shape[-2]
    rep = H // G
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), rep, axis=1)         # [b, H, S]
    Ch = jnp.repeat(C.astype(f32), rep, axis=1)
    decay = jnp.exp(dt * A.astype(f32))                 # [b, H]
    h = (decay[..., None, None] * h.astype(f32)
         + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    y = (h * Ch[:, :, None, :]).sum(-1) + D.astype(f32)[:, None] * x
    return y, h
