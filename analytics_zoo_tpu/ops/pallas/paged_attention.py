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
engine, never as a materialized context tensor.  Per block the kernel
runs the standard online-softmax update (running max / denominator /
output in f32 VMEM scratch, exactly the flash_attention bookkeeping at
q_len=1), masks by the lane's `ctx_len`, folds the new token's
self-attention into the initialization (a decode token always attends
to itself), and finalizes to an f32 output.

Quantized pools (int8 KV, serving/generation/kv_cache.py): when
`k_scale`/`v_scale` [num_blocks, block_size] ride along, the kernel
dequantizes ON READ by folding each token's scale into the score /
probability COLUMNS (s_col *= k_scale[col]; p_col *= v_scale[col])
— algebraically identical to scaling K/V rows, but it stays in the
2-D [h, block] layouts the VPU likes and never materializes a
dequantized block.

The tunable is `block_gather` (G): how many pool blocks one grid step
processes.  G > 1 passes the pool G times with G table-indexed
BlockSpecs, so one grid step streams G blocks and amortizes the
per-step softmax bookkeeping over a G*block_size-wide score tile —
the decode analog of flash's block_k.  Registered with `ops/tuning`
under the fwd-only key family

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
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: builtin-fallback block gather width (one pool block per grid step —
#: always legal; the tuner widens it where VMEM and the table allow)
DEFAULT_BLOCK_GATHER = 1
#: candidate VMEM ceiling (same headroom discipline as flash_attention)
_VMEM_BUDGET = 12 * 1024 * 1024


def paged_decode_candidates(bs: int, mb: int, h: int, d: int
                            ) -> List[Dict[str, int]]:
    """The autotuner's candidate grid: block-gather widths that fit the
    VMEM budget (k+v staged f32-equivalent, plus q/new-token tiles and
    the online-softmax scratch) and don't exceed the per-lane table."""
    out = []
    for g in (1, 2, 4, 8):
        if g > max(1, mb):
            continue
        vmem = (2 * g * bs * h * d * 4      # k+v tiles
                + 3 * h * d * 4             # q, new_k, new_v
                + h * d * 4 + 2 * h * 128 * 4   # o/m/l scratch
                + 2 * g * bs * 4)           # scale vectors
        if vmem <= _VMEM_BUDGET:
            out.append({"block_gather": g})
    return out or [{"block_gather": DEFAULT_BLOCK_GATHER}]


def _kernel(tbl_ref, cl_ref, q_ref, nk_ref, nv_ref, *rest, g: int,
            bs: int, num_j: int, quantized: bool, scale: float):
    # scalar prefetch: tbl_ref [S, MB] block tables, cl_ref [S] ctx
    # lengths.  q/nk/nv_ref: [1, h, d] lane tiles.  rest: g gathered
    # K blocks [1, bs, h, d], g V blocks, (g k-scale + g v-scale
    # [1, bs] rows when quantized), then o_ref [1, h, d] and the o/m/l
    # VMEM scratch carried across the block axis.
    rest = list(rest)
    ks = [rest.pop(0) for _ in range(g)]
    vs = [rest.pop(0) for _ in range(g)]
    kscl = [rest.pop(0) for _ in range(g)] if quantized else None
    vscl = [rest.pop(0) for _ in range(g)] if quantized else None
    o_ref, o_scr, m_scr, l_scr = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # the new token always attends to itself: seed the online
        # softmax with its own score (p_self = exp(0) = 1, l = 1,
        # o = new_v) instead of a NEG_INF/0 init — no empty-context
        # special case, no 0/0 at finalize
        qv = q_ref[0].astype(jnp.float32)
        s_self = (qv * nk_ref[0].astype(jnp.float32)).sum(
            axis=-1, keepdims=True) * scale              # [h, 1]
        m_scr[:] = jnp.broadcast_to(s_self, m_scr.shape)
        l_scr[:] = jnp.ones_like(l_scr)
        o_scr[:] = nv_ref[0].astype(jnp.float32)

    cl = cl_ref[s_idx]

    # block groups entirely past the lane's context are all-masked:
    # skip their compute (the DMAs still stream by, cheaply — the
    # shapes stay static, which is the zero-recompile contract)
    @pl.when(j * g * bs < cl)
    def _compute():
        # Mosaic's matmul wants the batch (head) dimension LEADING on
        # both operands and a non-contracting dimension on each: q
        # rides as [h, 1, d] and the staged [bs, h, d] tile is turned
        # to [h, bs, d] in VMEM (the pool's layout is untouched)
        qv = q_ref[0].astype(jnp.float32)[:, None, :]    # [h, 1, d]
        for i in range(g):
            kt = jnp.swapaxes(ks[i][0].astype(jnp.float32), 0, 1)
            vt = jnp.swapaxes(vs[i][0].astype(jnp.float32), 0, 1)
            pos = (j * g + i) * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs), 1)
            valid = pos < cl                             # [1, bs]
            s = jax.lax.dot_general(
                qv, kt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32
            )[:, 0, :] * scale                           # [h, bs]
            if quantized:
                # dequant-on-read, folded into the score columns
                s = s * kscl[i][...]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[:, 0:1]
            l_prev = l_scr[:, 0:1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
            if quantized:
                p = p * vscl[i][...]
            o_scr[:] = o_scr[:] * alpha + jax.lax.dot_general(
                p[:, None, :], vt, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)[:, 0, :]  # [h, d]
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_j - 1)
    def _finalize():
        o_ref[0] = (o_scr[:] / l_scr[:, 0:1]).astype(o_ref.dtype)


def paged_decode_pallas(q, new_k, new_v, k_pool, v_pool, block_tables,
                        ctx_len, *, k_scale=None, v_scale=None,
                        block_gather: int = DEFAULT_BLOCK_GATHER,
                        interpret: bool = False):
    """The raw kernel call (dispatch through
    `ops.attention.paged_decode_attention`, which picks impl and asks
    the tuner for `block_gather`).

    q, new_k, new_v: [S, h, d] — lane S's pending token's query and
    its key/value (it attends to itself).
    k_pool / v_pool: [num_blocks, block_size, h, d] — the paged pool
    (block 0 = the null block; any float dtype, or int8 with scales).
    k_scale / v_scale: [num_blocks, block_size] f32 per-token-slot
    dequant scales (required iff the pool is quantized).
    block_tables: [S, max_blocks] int32; ctx_len: [S] int32 valid
    lengths (cached position p lives at table[p // bs], slot p % bs).
    Returns [S, h, d] float32.
    """
    s, h, d = q.shape
    nb, bs, _, _ = k_pool.shape
    mb = block_tables.shape[1]
    g = max(1, int(block_gather))
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    # pad the table up to a multiple of g with null blocks — their
    # positions sit past every ctx_len, so the mask kills them
    if mb % g:
        pad = g - mb % g
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        mb += pad
    num_j = mb // g
    block_tables = block_tables.astype(jnp.int32)
    ctx_len = jnp.asarray(ctx_len, jnp.int32)

    lane = pl.BlockSpec((1, h, d), lambda si, j, tbl, cl: (si, 0, 0))

    def _pool_spec(i):
        return pl.BlockSpec(
            (1, bs, h, d),
            partial(lambda si, j, tbl, cl, i: (tbl[si, j * g + i],
                                               0, 0, 0), i=i))

    def _scale_spec(i):
        # over the [num_blocks, 1, bs] view below: a (1, bs) block of a
        # (num_blocks, bs) array breaks Mosaic's (8, 128) block rule,
        # while here it is the array's own last two dims
        return pl.BlockSpec(
            (None, 1, bs),
            partial(lambda si, j, tbl, cl, i: (tbl[si, j * g + i],
                                               0, 0), i=i))

    in_specs = ([lane, lane, lane]
                + [_pool_spec(i) for i in range(g)] * 2)
    args = [q, new_k, new_v] + [k_pool] * g + [v_pool] * g
    if quantized:
        in_specs += [_scale_spec(i) for i in range(g)] * 2
        args += [k_scale.astype(jnp.float32).reshape(nb, 1, bs)] * g \
            + [v_scale.astype(jnp.float32).reshape(nb, 1, bs)] * g

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, num_j),
        in_specs=in_specs,
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        partial(_kernel, g=g, bs=bs, num_j=num_j, quantized=quantized,
                scale=1.0 / (d ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), jnp.float32),
        interpret=interpret,
    )(block_tables, ctx_len, *args)


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
    if jnp.dtype(dtype) == jnp.int8:
        k_pool = jnp.asarray(rng.integers(-127, 128, (nb, bs, h, d)),
                             jnp.int8)
        v_pool = jnp.asarray(rng.integers(-127, 128, (nb, bs, h, d)),
                             jnp.int8)
        k_scale = jnp.asarray(rng.uniform(0.005, 0.02, (nb, bs)),
                              jnp.float32)
        v_scale = jnp.asarray(rng.uniform(0.005, 0.02, (nb, bs)),
                              jnp.float32)
    else:
        k_pool = jnp.asarray(rng.normal(size=(nb, bs, h, d)), dtype)
        v_pool = jnp.asarray(rng.normal(size=(nb, bs, h, d)), dtype)
        k_scale = v_scale = None
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1)[:lanes * mb].reshape(lanes, mb),
        jnp.int32)
    ctx = jnp.full(lanes, mb * bs - 1, jnp.int32)
    q0 = jnp.asarray(rng.normal(size=(lanes, h, d)), jnp.float32)
    nk = jnp.asarray(rng.normal(size=(lanes, h, d)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(lanes, h, d)), jnp.float32)

    @jax.jit
    def many(q):
        def body(c, _):
            o = paged_decode_pallas(
                c, nk, nv, k_pool, v_pool, tables, ctx,
                k_scale=k_scale, v_scale=v_scale,
                block_gather=cfg["block_gather"])
            return o, None
        c, _ = jax.lax.scan(body, q, None, length=iters)
        return c[0, 0, 0]

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
                                    h, d)
    cfg = tuning.get_config(
        "paged_decode", shape, dtype,
        default={"block_gather": DEFAULT_BLOCK_GATHER},
        candidates=cands,
        bench=lambda c: _bench_paged_decode(bs, lanes, h, d, dtype, c),
        allow_search=allow_search)
    return int(cfg["block_gather"])


def tune_paged_decode(bs, lanes, h, d, dtype=jnp.float32,
                      mb: Optional[int] = None, force=False) -> int:
    """Search NOW (bench.py's kernel stage on a real TPU): benchmark
    the candidate gather widths, persist the winner to
    `OrcaContext.kernel_tuning_cache_dir`, return it."""
    from analytics_zoo_tpu.ops import tuning
    shape = {"bs": bs, "lanes": lanes, "d": d}
    cfg = tuning.tune(
        "paged_decode", shape, dtype,
        paged_decode_candidates(bs, mb if mb is not None else 8, h, d),
        lambda c: _bench_paged_decode(bs, lanes, h, d, dtype, c),
        force=force)
    return int(cfg["block_gather"])
