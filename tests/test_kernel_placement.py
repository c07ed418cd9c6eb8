"""Pallas kernels inside programs that span several devices.

Mosaic refuses a kernel that GSPMD would have to partition, so in a
multi-device program the dispatchers of `ops/` carry their kernel in a
shard_map over the mesh the program's owner declared
(`parallel/sharding.py`: `declare_mesh` / `traced_mesh` /
`place_row_kernel`).  Here that placement is run — interpret mode, the
suite's virtual CPU devices — against each op's XLA form on the same
sharded inputs, values and gradients; that the chip's compiler accepts
the placed kernel is `tests/test_tpu_compile.py`'s case `*_tp4`.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import paged_decode_attention
from analytics_zoo_tpu.ops.dense import dense_bias_gelu
from analytics_zoo_tpu.ops.normalization import layer_norm
from analytics_zoo_tpu.parallel.sharding import declare_mesh, traced_mesh


def _mesh(axis):
    return Mesh(np.asarray(jax.devices()[:4]).reshape(4), (axis,))


def _row_op_case(op):
    """value and grads of sum(op(x, a, b)**2), rows split over dp=4."""
    mesh = _mesh("dp")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 16, 128)).astype(np.float32)
    if op is layer_norm:
        a = rng.normal(size=(128,)).astype(np.float32)
        b = rng.normal(size=(128,)).astype(np.float32)
    else:
        a = (rng.normal(size=(128, 256)) * 0.1).astype(np.float32)
        b = rng.normal(size=(256,)).astype(np.float32)
    rep = NamedSharding(mesh, P())
    args = (jax.device_put(x, NamedSharding(mesh, P("dp"))),
            jax.device_put(a, rep), jax.device_put(b, rep))

    def run(impl):
        def loss(x, a, b):
            with declare_mesh(mesh):
                y = op(x, a, b, impl=impl, interpret=True)
            return (y.astype(jnp.float32) ** 2).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(*args)
    return run


def _paged_case():
    """the decode kernel over a head-sharded pool, tp=4."""
    mesh = _mesh("tp")
    rng = np.random.default_rng(1)
    s, h, d, bs, mb = 4, 4, 32, 8, 4
    nb = s * mb + 1
    q, nk, nv = (rng.normal(size=(s, h, d)).astype(np.float32)
                 for _ in range(3))
    # the whole pool in its block view, two layers; layer 1 is read
    kv = rng.normal(size=(2, 2, nb, bs, h * d)).astype(np.float32)
    tables = (1 + rng.permutation(nb - 1)).reshape(s, mb).astype(np.int32)
    ctx = np.asarray([3, 17, 31, 9], np.int32)
    lane = NamedSharding(mesh, P(None, "tp", None))
    pool = NamedSharding(mesh, P(None, None, None, None, "tp"))
    rep = NamedSharding(mesh, P())
    args = (jax.device_put(q, lane), jax.device_put(nk, lane),
            jax.device_put(nv, lane), jax.device_put(kv, pool),
            jax.device_put(tables, rep), jax.device_put(ctx, rep))

    def run(impl):
        def fn(*a):
            with declare_mesh(mesh):
                return paged_decode_attention(
                    *a, layer=1, impl=impl, block_gather=2,
                    interpret=True)
        out = jax.jit(fn)(*args)
        if impl == "pallas":
            # the kernel ran per device on its own heads: the result
            # comes back head-sharded, nothing was gathered for it
            assert "tp" in str(out.sharding.spec)
        return out
    return run


@pytest.mark.parametrize("case", ["layer_norm_dp4", "bias_gelu_dp4",
                                  "paged_decode_tp4"])
def test_placed_kernel_matches_xla_form(case):
    run = {"layer_norm_dp4": lambda: _row_op_case(layer_norm),
           "bias_gelu_dp4": lambda: _row_op_case(dense_bias_gelu),
           "paged_decode_tp4": _paged_case}[case]()
    got = jax.tree_util.tree_leaves(run("pallas"))
    want = jax.tree_util.tree_leaves(run("xla"))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_traced_mesh_is_none_outside_a_declared_program():
    """One-device programs, and the inside of a shard_map, get the
    kernel as it is."""
    mesh = _mesh("dp")
    seen = {}

    def probe(x):
        seen["plain"] = traced_mesh()
        with declare_mesh(mesh):
            seen["declared"] = traced_mesh()

            def inner(y):
                seen["manual"] = traced_mesh()
                return y
            jax.shard_map(inner, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"))(x)
        return x
    jax.jit(probe)(jnp.zeros((8, 4)))
    assert seen["plain"] is None and seen["manual"] is None
    assert dict(seen["declared"].shape) == {"dp": 4}


def test_tp_engine_runs_the_paged_kernel_sharded():
    """The tp engine with the kernel pinned on (interpret mode): same
    greedy tokens as the single-device engine, one decode program."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.observability.registry import MetricsRegistry
    from analytics_zoo_tpu.serving.generation import (
        CausalLM,
        GenerationEngine,
    )
    stop_orca_context()
    init_orca_context(cluster_mode="local", mesh_shape={"tp": 2})
    try:
        model = CausalLM(vocab=61, hidden_size=32, n_head=4, n_block=2,
                         intermediate_size=64, max_position_len=64,
                         paged_attention_impl="pallas")
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32),
                            jnp.arange(8)[None])["params"]
        rng = np.random.default_rng(3)
        prompts = [list(rng.integers(0, 61, n)) for n in (9, 6, 13)]

        def run(**kw):
            eng = GenerationEngine(model, params, max_slots=4,
                                   block_size=8, max_context=64,
                                   registry=MetricsRegistry(), **kw)
            streams = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                       for p in prompts]
            eng.run_until_idle()
            assert eng.decode_compile_count == 1
            return [s.tokens() for s in streams]
        assert run(tensor_parallel=2) == run()
    finally:
        stop_orca_context()
