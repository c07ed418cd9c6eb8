"""The generation step programs as a module of their own
(serving/generation/steps.py): built from a model and a pool's
geometry with no engine in sight, wrapped the same way on one device
and under tensor parallelism, and the engine configured by its
constructor alone."""

import ast
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.generation import (
    CausalLM,
    DecoderLM,
    GenerationEngine,
    lane_state,
    steps,
)
from analytics_zoo_tpu.serving.generation.kv_cache import (
    PagedKVCache,
    pool_geometry,
)

FAMILIES = {
    "prefill": ("params", "kv", "kv_scale", "lanes", "request"),
    "chunk_prefill": ("params", "kv", "kv_scale", "tokens", "start",
                      "length", "block_table", "temperature", "top_k",
                      "rng"),
    "decode": ("params", "kv", "kv_scale", "lanes", "patch"),
    "spec_verify": ("params", "kv", "kv_scale", "tokens",
                    "block_tables", "start", "length", "active"),
    "copy_block": ("kv", "kv_scale", "src", "dst"),
    "host_restore": ("kv", "kv_scale", "dst", "rows", "srows"),
}
BS, LANES, BLOCKS_A_LANE = 4, 2, 4


def causal_lm():
    return CausalLM(vocab=61, hidden_size=32, n_head=4, n_block=2,
                    intermediate_size=64, max_position_len=64)


def decoder_lm():
    return DecoderLM(
        vocab=61, hidden_size=32, n_head=4, n_kv_head=2, head_dim=8,
        layer_types=("sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "sparse"), intermediate_size=48,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
        experts_held=(2, 4), sliding_window=8, max_position_len=64)


def init(model):
    ids = jnp.zeros((1, 8), jnp.int32)
    return model.init(jax.random.PRNGKey(0), ids,
                      jnp.arange(8)[None])["params"]


def build(model, counted, tp=None):
    _, kv_heads, _ = pool_geometry(model)
    return steps.build_steps(
        model, block_size=BS, n_head=kv_heads, quantized=False,
        paged=True, width=lane_state.TABLE + BLOCKS_A_LANE,
        counted=counted, tp=tp, prefill_variants=3)


def state_by_hand(model):
    """((params, pool, scales), lane state, a lane row's width) for
    `build`'s programs: an empty pool and no lane active."""
    layers, kv_heads, head_dim = pool_geometry(model)
    cache = PagedKVCache(layers, 1 + LANES * BLOCKS_A_LANE, BS, kv_heads,
                         head_dim)
    width = lane_state.TABLE + BLOCKS_A_LANE
    lanes = {"rows": jnp.zeros((LANES, width), jnp.int32),
             "rng": jax.random.PRNGKey(3)}
    return ((init(model), cache.kv, jnp.zeros((1,), jnp.float32)),
            lanes, width)


@pytest.mark.parametrize("make, counted", [(causal_lm, False),
                                           (decoder_lm, True)],
                         ids=["CausalLM", "DecoderLM"])
def test_builder_needs_no_engine(make, counted):
    """The six families from a model and a geometry, no engine: named
    and argument-named for the ledger, and `decode` runs on a state
    made by hand (no lane active: every write lands in the null block,
    the rows come back as they went, the key split)."""
    model = make()
    built = build(model, counted)
    assert [s.family for s in built] == list(FAMILIES)
    assert [s.argnames for s in built] == list(FAMILIES.values())
    state, lanes, width = state_by_hand(model)
    decode = built[2]
    kv, scale, nxt, last, after, *counts = decode(
        *state, lanes, jnp.zeros((LANES, 1 + width), jnp.int32))
    assert kv.shape == state[1].shape and nxt.shape == (LANES,)
    assert not np.asarray(last).any()            # dead lanes' logits
    np.testing.assert_array_equal(after["rows"], lanes["rows"])
    assert not np.array_equal(after["rng"], lanes["rng"])
    assert len(counts) == int(counted)
    if counted:
        assert not np.asarray(counts[0]).any()   # no lane, no token


def sampler_work(jaxpr, vocab, inside=False):
    """(primitive, inside a `cond` branch) of every sort over the
    vocabulary and every draw of random bits in `jaxpr`, however deep."""
    from jax._src import core
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "random_bits" or (
                name == "sort"
                and eqn.invars[0].aval.shape[-1:] == (vocab,)):
            found.append((name, inside))
        for sub in core.jaxprs_in_params(eqn.params):
            found += sampler_work(sub, vocab, inside or name == "cond")
    return found


@pytest.mark.parametrize("family", ["prefill", "chunk_prefill", "decode"])
@pytest.mark.parametrize("make, counted", [(causal_lm, False),
                                           (decoder_lm, True)],
                         ids=["CausalLM", "DecoderLM"])
def test_sort_and_draw_sit_inside_a_branch(make, counted, family):
    """The sampler's sort over the vocabulary and its random bits are
    traced into every program that samples, and only ever inside a
    `cond` branch: a round whose live lanes are greedy runs neither
    (sampling.py).  The key's split stays outside, so the key sequence
    does not depend on the branch."""
    model = make()
    built = dict(zip(FAMILIES, build(model, counted)))
    state, lanes, width = state_by_hand(model)
    args = {
        "prefill": (lanes, jnp.zeros((1 + width + 8,), jnp.int32)),
        "chunk_prefill": (
            jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(5),
            jnp.zeros((BLOCKS_A_LANE,), jnp.int32),
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            lanes["rng"]),
        "decode": (lanes, jnp.zeros((LANES, 1 + width), jnp.int32)),
    }[family]
    jaxpr = jax.make_jaxpr(built[family].fn)(*state, *args).jaxpr
    work = sampler_work(jaxpr, model.vocab)
    assert {name for name, _ in work} == {"sort", "random_bits"}
    assert all(inside for _, inside in work), work
    (program,) = jaxpr.eqns               # the jitted program itself
    top = {eqn.primitive.name
           for eqn in program.params["jaxpr"].jaxpr.eqns}
    assert {"random_split", "argmax", "cond"} <= top


def test_both_placements_wrap_the_same_families():
    """On one device and under tensor parallelism the ledger sees the
    same names and argument names: one wrap, two placements.  (The
    host tier is off under tp, so there is no restore program.)"""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.serving.distributed import (
        TensorParallelPlacement,
    )
    model = causal_lm()
    stop_orca_context()
    init_orca_context(cluster_mode="local", mesh_shape={"tp": 2})
    try:
        sharded = build(model, False,
                        tp=TensorParallelPlacement.build(2, model))
    finally:
        stop_orca_context()
    single = build(model, False)
    assert sharded[-1] is None and single[-1].family == "host_restore"
    assert [(s.family, s.argnames) for s in sharded[:-1]] \
        == [(s.family, s.argnames) for s in single[:-1]] \
        == list(FAMILIES.items())[:-1]


def test_steps_module_does_not_import_the_engine():
    """steps.py is below the engine: it takes no `self` and imports
    nothing of engine.py (and the engine jits and ledgers nothing
    itself)."""
    def imported(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                yield node.module or ""
                yield from (f"{node.module}.{a.name}" for a in node.names)
            elif isinstance(node, ast.Import):
                yield from (a.name for a in node.names)
    assert not [m for m in imported(steps.__file__)
                if m.split(".")[-1] == "engine"]
    engine_py = os.path.join(os.path.dirname(steps.__file__), "engine.py")
    with open(engine_py) as f:
        source = f.read()
    assert "jax.jit" not in source
    assert "profiling.instrument" not in source


def test_an_engine_is_configured_by_its_constructor_alone():
    """No process-global switches a feature on behind a constructor's
    back: `OrcaContext` has none of the nine knobs, and an engine built
    with no feature argument has every feature off whatever was built
    before it in the process."""
    for knob in ("prefix_caching", "chunked_prefill",
                 "speculative_decoding", "speculative_k",
                 "kv_cache_quantization", "kv_host_tier_bytes",
                 "decode_tensor_parallel", "router_phase_aware",
                 "serving_replicas"):
        assert not hasattr(OrcaContext, knob), knob
    model = causal_lm()
    params = init(model)
    geometry = dict(max_slots=2, block_size=BS, max_context=32)
    armed = GenerationEngine(
        model, params, registry=MetricsRegistry(), prefix_caching=True,
        chunked_prefill=True, speculative_decoding=True,
        speculative_k=2, kv_quantization="int8", kv_host_tier=1 << 20,
        **geometry)
    assert armed.speculation.k == 2 and armed.host_tier is not None
    plain = GenerationEngine(model, params, registry=MetricsRegistry(),
                             **geometry)
    assert (plain.prefix_caching, plain.chunked_prefill,
            plain.speculative_decoding, plain.tensor_parallel,
            plain.kv_quantization) == (False, False, False, 0, None)
    assert plain.prefix_cache is None and plain.host_tier is None
    assert plain.speculation is None and plain._tp is None
    assert plain.cache.kv.dtype == jnp.float32
