"""The paged decode kernel with grouped query heads and a window
(docs/kernels.md "Grouped queries and windows"), through the
interpreter, against the XLA form it falls back to — and the XLA form
against attention written out by hand.

Contexts under, at and past the window, block-aligned and not; a dead
lane; every gather width; f32 and bf16 pools.  The MHA kernel without a
window is the one `tests/test_paged_attention.py` already pins; here it
only gains the window."""

import numpy as np
import pytest

import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (
    dot_product_attention,
    paged_decode_attention,
    paged_verify_attention,
)

L, LAYER = 3, 1
BS, MB = 8, 6
WINDOW = 12


def scene(ctx, kv_heads=2, q_per_kv=2, d=16, seed=0, dtype=np.float32):
    """A pool [L, 2, nb, BS, kv_heads*d] of garbage, a table a lane
    that covers exactly its context (null beyond), and the pending
    token's q [S, h, d] / new_k / new_v [S, kv_heads, d]."""
    rng = np.random.default_rng(seed)
    s = len(ctx)
    nb = s * MB + 1
    pool = jnp.asarray(rng.normal(size=(L, 2, nb, BS, kv_heads * d)),
                       dtype)
    tables = np.zeros((s, MB), np.int32)
    perm = 1 + rng.permutation(nb - 1)
    for i, c in enumerate(ctx):
        used = -(-int(c) // BS)
        tables[i, :used] = perm[i * MB:i * MB + used]
    h = kv_heads * q_per_kv
    return dict(
        q=rng.normal(size=(s, h, d)).astype(np.float32),
        nk=rng.normal(size=(s, kv_heads, d)).astype(np.float32),
        nv=rng.normal(size=(s, kv_heads, d)).astype(np.float32),
        pool=pool, tables=tables, ctx=np.asarray(ctx, np.int32))


def paged(sc, impl, window, block_gather=None):
    return np.asarray(paged_decode_attention(
        jnp.asarray(sc["q"]), jnp.asarray(sc["nk"]), jnp.asarray(sc["nv"]),
        sc["pool"], jnp.asarray(sc["tables"]), jnp.asarray(sc["ctx"]),
        layer=LAYER, impl=impl, block_gather=block_gather, window=window,
        interpret=True if impl == "pallas" else None))


def by_hand(sc, window):
    """Each lane and query head on its own: the visible cached
    positions read out of the pool by the table, the new token last."""
    s, h, d = sc["q"].shape
    g = sc["nk"].shape[1]
    pool = np.asarray(sc["pool"].astype(jnp.float32))[LAYER]
    out = np.zeros((s, h, d), np.float32)
    for lane in range(s):
        c = int(sc["ctx"][lane])
        rows = np.concatenate(
            [pool[:, b] for b in sc["tables"][lane]], axis=1)[:, :c]
        rows = rows.reshape(2, c, g, d)
        lo = 0 if window is None else max(0, c - window + 1)
        for head in range(h):
            kv = head // (h // g)
            k = np.concatenate([rows[0, lo:, kv], sc["nk"][lane, kv][None]])
            v = np.concatenate([rows[1, lo:, kv], sc["nv"][lane, kv][None]])
            score = k @ sc["q"][lane, head] / np.sqrt(d)
            p = np.exp(score - score.max())
            out[lane, head] = (p / p.sum()) @ v
    return out


#: a dead lane; under the window; one short of it (the window holds the
#: new token and 11 cached); exactly at it; past it and ending inside a
#: block; past it and block-aligned; the table full
CONTEXTS = [0, 5, WINDOW - 1, WINDOW, 29, 32, MB * BS]


@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("kv_heads,q_per_kv", [(2, 2), (2, 4), (4, 1)])
def test_xla_form_is_attention_by_hand(kv_heads, q_per_kv, window):
    sc = scene(CONTEXTS, kv_heads, q_per_kv, seed=3)
    np.testing.assert_allclose(paged(sc, "xla", window),
                               by_hand(sc, window), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_gather", [1, 2, 4])
@pytest.mark.parametrize("window", [None, WINDOW, 1, 200])
@pytest.mark.parametrize("kv_heads,q_per_kv", [(2, 2), (2, 4), (4, 1)])
def test_kernel_matches_the_xla_form(kv_heads, q_per_kv, window,
                                     block_gather):
    """`q_per_kv` > 1 and a window, through the interpreter: whatever
    the gather width, whichever blocks the window lets the grid skip."""
    sc = scene(CONTEXTS, kv_heads, q_per_kv, seed=5 + block_gather)
    np.testing.assert_allclose(
        paged(sc, "pallas", window, block_gather),
        paged(sc, "xla", window), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [None, WINDOW])
def test_kernel_on_a_bf16_pool(window):
    """The deployed pool: one bf16 MXU pass over the three bf16 pieces
    of the f32 operand is exact to f32, so the kernel and the XLA form
    (f32 compute over the same bf16 rows) agree as closely."""
    sc = scene(CONTEXTS, 2, 4, seed=11, dtype=jnp.bfloat16)
    np.testing.assert_allclose(
        paged(sc, "pallas", window, 2), paged(sc, "xla", window),
        atol=3e-5, rtol=3e-5)


def test_window_layer_reads_only_the_blocks_in_sight():
    """Garbage that is no number behind the window: the kernel's grid
    starts at the window's first block, so a NaN further back is never
    staged into a sum (the XLA form masks it after the product and
    would keep it)."""
    sc = scene([40, 47], 2, 4, seed=13)
    pool = np.asarray(sc["pool"]).copy()
    for lane, c in enumerate(sc["ctx"]):
        first = (int(c) - WINDOW + 1) // BS
        for b in sc["tables"][lane][:first]:
            pool[:, :, b] = np.nan
    clean = paged(sc, "pallas", WINDOW, 2)
    sc["pool"] = jnp.asarray(pool)
    dirty = paged(sc, "pallas", WINDOW, 2)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


def test_int8_pool_is_refused_with_grouped_heads():
    sc = scene([5, 9], 2, 2)
    with pytest.raises(NotImplementedError, match="int8"):
        paged_decode_attention(
            jnp.asarray(sc["q"]), jnp.asarray(sc["nk"]),
            jnp.asarray(sc["nv"]), sc["pool"].astype(jnp.int8),
            jnp.asarray(sc["tables"]), jnp.asarray(sc["ctx"]),
            layer=LAYER, impl="pallas", interpret=True, block_gather=1,
            kv_scale=jnp.ones(sc["pool"].shape[:4], jnp.float32))


@pytest.mark.parametrize("window", [None, 5])
def test_prefill_form_with_grouped_heads_and_window(window):
    """`dot_product_attention`'s whole-prompt form: causal, a padding
    mask, 2 KV heads under 4 query heads, against the same by hand."""
    rng = np.random.default_rng(17)
    t, g, r, d = 11, 2, 2, 8
    q = rng.normal(size=(1, t, g * r, d)).astype(np.float32)
    k = rng.normal(size=(1, t, g, d)).astype(np.float32)
    v = rng.normal(size=(1, t, g, d)).astype(np.float32)
    real = 9
    mask = (1.0 - (np.arange(t) < real)[None, None, None]) * -1e9
    out = np.asarray(dot_product_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        mask=jnp.asarray(mask, jnp.float32), causal=True,
        compute_dtype=jnp.float32, window=window))
    for p in range(real):
        lo = 0 if window is None else max(0, p - window + 1)
        for head in range(g * r):
            kk, vv = k[0, lo:p + 1, head // r], v[0, lo:p + 1, head // r]
            score = kk @ q[0, p, head] / np.sqrt(d)
            w = np.exp(score - score.max())
            np.testing.assert_allclose(out[0, p, head], (w / w.sum()) @ vv,
                                       atol=2e-5, rtol=2e-5)


def test_verify_form_agrees_with_decode_one_token_at_a_time():
    """`paged_verify_attention` over T new tokens with a window and
    grouped heads: row 0 is the decode step's answer."""
    sc = scene([5, 29, 32], 2, 2, seed=19)
    rng = np.random.default_rng(23)
    s, h, d = sc["q"].shape
    q = np.concatenate([sc["q"][:, None],
                        rng.normal(size=(s, 2, h, d))], 1)
    nk = np.concatenate([sc["nk"][:, None],
                         rng.normal(size=(s, 2, 2, d))], 1)
    nv = np.concatenate([sc["nv"][:, None],
                         rng.normal(size=(s, 2, 2, d))], 1)
    out = np.asarray(paged_verify_attention(
        jnp.asarray(q, jnp.float32), jnp.asarray(nk, jnp.float32),
        jnp.asarray(nv, jnp.float32), sc["pool"],
        jnp.asarray(sc["tables"]), jnp.asarray(sc["ctx"]), layer=LAYER,
        window=WINDOW))
    np.testing.assert_allclose(out[:, 0], paged(sc, "xla", WINDOW),
                               atol=2e-5, rtol=2e-5)
