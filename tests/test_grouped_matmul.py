"""The grouped product (ops/grouped.py, ops/pallas/grouped_matmul.py):
the Pallas kernel in interpret mode against `jax.lax.ragged_dot` and
against a plain float32 loop over the groups, the dispatcher's choice
of path and tiles, and `ExpertLayer` giving the same sums and counts
through either path.  Interpret mode says nothing about the chip:
tests/test_tpu_compile.py compiles the table's rows for it."""

import functools
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.ops import grouped, tuning
from analytics_zoo_tpu.serving.generation import (
    DecoderLM,
    GenerationEngine,
)
from analytics_zoo_tpu.serving.generation.decoder import ExpertLayer

# name -> (m, k, n, sizes, tile_m, dtype); tile_k = tile_n = 128
CASES = {
    "even_groups": (64, 128, 256, [16, 16, 16, 16], 16, jnp.bfloat16),
    "uneven_groups": (64, 128, 256, [3, 29, 1, 20, 11], 16, jnp.bfloat16),
    "groups_of_zero": (64, 128, 256, [0, 10, 0, 0, 22, 0, 32, 0], 16,
                       jnp.bfloat16),
    "all_rows_in_one_group": (64, 128, 256, [0, 0, 64, 0], 16,
                              jnp.bfloat16),
    "rows_past_the_groups": (96, 128, 256, [5, 0, 30, 5], 32,
                             jnp.bfloat16),
    "no_row_in_any_group": (64, 128, 256, [0, 0, 0, 0], 16, jnp.bfloat16),
    "m_no_multiple_of_the_tile": (50, 128, 256, [7, 19, 0, 12], 32,
                                  jnp.bfloat16),
    "group_wider_than_a_tile": (128, 128, 256, [70, 3, 40], 16,
                                jnp.bfloat16),
    "tile_wider_than_m": (24, 128, 256, [4, 9, 6], 128, jnp.bfloat16),
    "k_over_n": (64, 384, 128, [9, 0, 33, 14], 16, jnp.bfloat16),
    "n_over_k": (64, 128, 384, [9, 0, 33, 14], 16, jnp.bfloat16),
    "float32": (64, 128, 256, [3, 29, 1, 20], 16, jnp.float32),
}


def operands(m, k, n, g, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((m, k)), dtype)
    kernels = jnp.asarray(rng.standard_normal((g, k, n)) * k ** -0.5,
                          dtype)
    return rows, kernels


def plain_loop(rows, kernels, sizes):
    """float32, a group at a time; rows past the groups left at zero."""
    rows = np.asarray(rows, np.float32)
    kernels = np.asarray(kernels, np.float32)
    out = np.zeros((rows.shape[0], kernels.shape[2]), np.float32)
    start = 0
    for group, size in enumerate(sizes):
        out[start:start + size] = rows[start:start + size] @ kernels[group]
        start += size
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_against_ragged_dot_and_a_plain_loop(case):
    m, k, n, sizes, tile_m, dtype = CASES[case]
    rows, kernels = operands(m, k, n, len(sizes), dtype)
    sizes_d = jnp.asarray(sizes, jnp.int32)
    live = sum(sizes)
    want = plain_loop(rows, kernels, sizes)
    ragged = grouped.grouped_matmul(rows, kernels, sizes_d,
                                    impl="ragged_dot")
    got = grouped.grouped_matmul(rows, kernels, sizes_d, impl="kernel",
                                 tile_m=tile_m, tile_k=128, tile_n=128,
                                 interpret=True)
    assert got.shape == ragged.shape == (m, n)
    assert got.dtype == ragged.dtype == dtype
    # bfloat16 results: one rounding of a sum of about unit size
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(ragged, np.float32)[:live],
                               want[:live], atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:live],
                               want[:live], atol=tol, rtol=tol)
    # past the groups `ragged_dot` gives zeros; the kernel promises
    # nothing there (the interpreter leaves NaN) and the sums above
    # never saw them
    assert not np.asarray(ragged, np.float32)[live:].any()


def test_kernel_with_the_dispatchers_own_tiles():
    """No tile given: the table's row for the shape or the builtin
    tiling, whole `k` and `n` here (one block each)."""
    m, k, n, sizes = 64, 256, 384, [3, 29, 1, 20]
    rows, kernels = operands(m, k, n, len(sizes), jnp.bfloat16)
    before = grouped.BUILT[grouped.KERNEL, 16]
    got = grouped.grouped_matmul(rows, kernels,
                                 jnp.asarray(sizes, jnp.int32),
                                 impl="kernel", interpret=True)
    assert grouped.BUILT[grouped.KERNEL, 16] == before + 1
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:sum(sizes)],
        plain_loop(rows, kernels, sizes)[:sum(sizes)], atol=2e-2,
        rtol=2e-2)


def test_kernel_differentiates_like_ragged_dot():
    m, k, n, sizes = 64, 128, 256, [9, 0, 33, 22]
    rows, kernels = operands(m, k, n, len(sizes), jnp.float32)
    sizes_d = jnp.asarray(sizes, jnp.int32)

    def loss(impl, rows, kernels):
        kw = ({} if impl == "ragged_dot" else
              dict(tile_m=16, tile_k=128, tile_n=128, interpret=True))
        out = grouped.grouped_matmul(rows, kernels, sizes_d, impl=impl,
                                     **kw)
        return jnp.square(out).sum()
    want = jax.grad(functools.partial(loss, "ragged_dot"), (0, 1))(
        rows, kernels)
    got = jax.grad(functools.partial(loss, "kernel"), (0, 1))(
        rows, kernels)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------
# the dispatcher: path by platform and shape, tiles by shape
# ---------------------------------------------------------------------

@pytest.mark.parametrize("platform, k, n, dtype, want", [
    ("cpu", 256, 384, jnp.bfloat16, grouped.RAGGED_DOT),
    ("tpu", 256, 384, jnp.bfloat16, grouped.KERNEL),
    ("tpu", 256, 384, jnp.float32, grouped.KERNEL),
    ("tpu", 200, 384, jnp.bfloat16, grouped.RAGGED_DOT),
    ("tpu", 256, 100, jnp.bfloat16, grouped.RAGGED_DOT),
    ("tpu", 256, 384, jnp.float16, grouped.RAGGED_DOT),
])
def test_path_follows_platform_and_shape(monkeypatch, platform, k, n,
                                         dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    rows = jax.ShapeDtypeStruct((64, k), dtype)
    kernels = jax.ShapeDtypeStruct((4, k, n), dtype)
    took = (grouped.KERNEL if grouped._kernel_supported(rows, kernels)
            else grouped.RAGGED_DOT)
    assert took == want


def test_a_partitioned_program_keeps_ragged_dot(monkeypatch):
    """Mosaic refuses a kernel GSPMD would have to partition."""
    from jax.sharding import Mesh

    from analytics_zoo_tpu.parallel.sharding import declare_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
    kernels = jax.ShapeDtypeStruct((4, 256, 384), jnp.bfloat16)
    assert grouped._kernel_supported(rows, kernels)
    with declare_mesh(Mesh(np.asarray(jax.devices()[:2]), ("tp",))):
        assert not grouped._kernel_supported(rows, kernels)


def table_rows():
    with open(tuning.DEFAULT_TABLE_PATH) as f:
        entries = json.load(f)["entries"]
    return {key: row for key, row in entries.items()
            if key.startswith("grouped_matmul|tpu|")}


#: the shapes the two expert cells meet: decode and the three prefill
#: buckets, (k, n) of each projection
CELL_SHAPES = [
    (m, g, k, n)
    for g, ms, pairs in (
        (16, (512, 2048, 4096, 8192), ((6144, 2048), (2048, 6144))),
        (128, (2816, 5632, 11264, 22528), ((1024, 2688), (2688, 1024))))
    for m in ms for k, n in pairs]


@pytest.mark.parametrize("m, g, k, n", CELL_SHAPES)
def test_table_answers_for_every_shape_of_the_cells(monkeypatch, m, g, k,
                                                    n):
    """A measured row a shape, found from the shape at set-up (the
    platform is part of the key), its tiles dividing the shape and its
    provenance naming the chip run that measured it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tuning.clear_memo()
    try:
        cfg = grouped._tiling(m, g, k, n, jnp.bfloat16)
        shape = {"m": m, "g": g, "k": k, "n": n}
        assert tuning.config_source(
            "grouped_matmul", shape, jnp.bfloat16) == "default_table"
        row = table_rows()[tuning.make_key("grouped_matmul", shape,
                                           jnp.bfloat16)]
    finally:
        tuning.clear_memo()
    assert cfg == row["config"]
    assert k % cfg["tile_k"] == 0 and n % cfg["tile_n"] == 0
    assert m % cfg["tile_m"] == 0 and cfg["tile_m"] <= 256
    assert re.search(r"chip run.*PR 36", row["provenance"]), row


def test_a_neighbours_row_that_does_not_divide_is_left(monkeypatch):
    """Keys are bucketed to powers of two: k = 8192 finds the row
    measured at 6144, whose `tile_k` does not divide it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tuning.clear_memo()
    try:
        cfg = grouped._tiling(512, 16, 8192, 2048, jnp.bfloat16)
    finally:
        tuning.clear_memo()
    assert cfg == grouped.default_tiling(512, 16, 8192, 2048, 2)
    assert 8192 % cfg["tile_k"] == 0 and 2048 % cfg["tile_n"] == 0


@pytest.mark.parametrize("m, g, k, n, itemsize", [
    (512, 16, 6144, 2048, 2), (22528, 128, 2688, 1024, 2),
    (8, 16, 1024, 2688, 2), (4096, 8, 4096, 14336, 2),
    (512, 16, 6144, 2048, 4), (64, 64, 128, 128, 4)])
def test_builtin_tiling_fits(m, g, k, n, itemsize):
    """Row tile 16-128 about a group's size, weight tiles that divide
    the shape, everything the pipeline holds inside 16 MiB."""
    t = grouped.default_tiling(m, g, k, n, itemsize)
    tm, tk, tn = t["tile_m"], t["tile_k"], t["tile_n"]
    assert 16 <= tm <= 128 and tm & (tm - 1) == 0
    assert tm >= min(128, m // g) and k % tk == 0 and n % tn == 0
    held = 2 * (tk * tn + tm * tk + tm * tn) * itemsize + tm * tn * 4
    assert held <= 13 << 20


# ---------------------------------------------------------------------
# the expert layer through either path
# ---------------------------------------------------------------------

LAYERS = {
    "gated": dict(num_experts=16, experts_held=(4, 6), top_k=3,
                  width=256),
    "latent_ungated": dict(num_experts=16, experts_held=(0, 8), top_k=4,
                           width=256, gated=False, latent=128,
                           shared_width=64),
}


@pytest.mark.parametrize("form", sorted(LAYERS))
def test_expert_layer_is_the_same_through_either_path(monkeypatch, form):
    """The dispatcher forced each way by ITS argument (the module has
    none for it): the same sums to bfloat16's rounding, the same
    counts, none dropped; padding routed nowhere."""
    layer = ExpertLayer(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                        **LAYERS[form])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 12, 128)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 12)) < 0.8)
    params = layer.init(jax.random.PRNGKey(0), x)
    plain = grouped.grouped_matmul
    taken = []

    def forced(impl):
        def call(rows, kernels, sizes):
            taken.append(impl)
            return plain(rows, kernels, sizes, impl=impl, interpret=True)
        return call
    results = {}
    for impl in (grouped.RAGGED_DOT, grouped.KERNEL):
        monkeypatch.setattr(grouped, "grouped_matmul", forced(impl))
        results[impl] = layer.apply(params, x, mask)
    products = 3 if LAYERS[form].get("gated", True) else 2
    assert taken == ([grouped.RAGGED_DOT] * products
                     + [grouped.KERNEL] * products)
    monkeypatch.setattr(grouped, "grouped_matmul", plain)
    before, _ = layer.apply(params, x, mask)     # the CPU's own path
    (y_r, counts_r), (y_k, counts_k) = (results[grouped.RAGGED_DOT],
                                        results[grouped.KERNEL])
    np.testing.assert_array_equal(before, y_r)
    assert np.isfinite(np.asarray(y_k)).all()
    np.testing.assert_allclose(y_k, y_r, atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(counts_k, counts_r)
    held = LAYERS[form]["experts_held"][1]
    assert int(counts_k[:held].sum()) == int(counts_k[held]) > 0
    assert int(counts_k[held + 1]) == int(mask.sum()) * layer.top_k


def test_engine_counts_the_products_it_built():
    """On the CPU every grouped product of an engine's programs takes
    `ragged_dot`, and the engine's registry says so beside the
    `generation_moe_*` counters."""
    model = DecoderLM(
        vocab=61, hidden_size=32, n_head=4, n_kv_head=2, head_dim=8,
        layer_types=("sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "sparse"), intermediate_size=48,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
        experts_held=(2, 4), sliding_window=8, max_position_len=64)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    reg = MetricsRegistry()
    engine = GenerationEngine(model, params, max_slots=2, block_size=4,
                              max_context=32, prefill_buckets=[32],
                              registry=reg)
    engine.generate([5, 6, 7, 8, 9], max_new_tokens=4)
    snap = reg.snapshot()
    # one sparse layer, three products, in `prefill` and in `decode`
    assert snap["generation_grouped_products_total_ragged_dot"] == 6
    assert not any(name.startswith("generation_grouped_")
                   and "ragged_dot" not in name for name in snap)
    assert snap["generation_moe_dropped_total"] == 0
