"""The Pallas kernels of the training and generation paths, compiled
for a TPU v5e that is described and not attached (the chip's own
compiler is installed here) at the shapes `chip_smoke.py` runs.

Interpret mode accepts kernels Mosaic refuses — a `dot_general` with
the batch dim in the middle, a one-row block of a many-row array — so
these compiles are what keeps a later PR from shipping a kernel that
cannot start on the chip.  A compile is not a run: nothing here says
anything about results or times.

All in ONE file, the topology described inside a module-scoped fixture:
only one process may hold the TPU library, and under xdist
(`--dist loadfile`) this file goes to one worker.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # or the compiler logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next run warns
    # and compiles again): cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# ---------------------------------------------------------------------
# cases: name -> builder(place) returning (fn, abstract args); `place`
# maps (shape, dtype[, spec]) to a ShapeDtypeStruct on the described
# device(s)
# ---------------------------------------------------------------------

def _paged(pool_dtype, block_gather, lanes=8, d=64, bs=16):
    """The engine's decode attention, by default at the smoke's serve
    shapes: 8 bf16 lanes, 12 heads x 64 merged into rows of 768,
    block 16, tables for a 1024 context, the full lanes*blocks+1-block
    pool handed over whole (two layers of it, the second one read)."""
    def build(place):
        from analytics_zoo_tpu.ops.pallas.paged_attention import (
            paged_decode_pallas)
        h, mb = 12, 1024 // bs
        nb = lanes * mb + 1
        lane = place((lanes, h * d), jnp.bfloat16)
        args = [lane, lane, lane, place((2, 2, nb, bs, h * d), pool_dtype),
                place((lanes, mb), jnp.int32), place((lanes,), jnp.int32)]
        if pool_dtype == jnp.int8:
            args += [place((2, 2, nb, bs), jnp.float32)]

        def fn(q, nk, nv, pool, tbl, cl, scale=None):
            return paged_decode_pallas(
                q, nk, nv, pool, tbl, cl, layer=1, head_dim=d,
                kv_scale=scale, block_gather=block_gather,
                interpret=False)
        return fn, args
    return build


def _paged_gqa(block_gather, window, lanes=64, h=64, d=128,
               context=1024):
    """The grouped-query kernel, by default at `kexaone_236b_ep8_serve`'s
    decode shapes: 64 lanes, 64 query heads over 8 KV heads of 128
    (pool rows of 1024), block 16, tables for a 1024 context, a bf16
    pool; with the 128-position window of a window layer or without."""
    def build(place):
        from analytics_zoo_tpu.ops.pallas.paged_attention import (
            paged_decode_pallas)
        g, bs = 8, 16
        mb = context // bs
        nb = lanes * mb + 1
        args = [place((lanes, h * d), jnp.bfloat16),
                place((lanes, g * d), jnp.bfloat16),
                place((lanes, g * d), jnp.bfloat16),
                place((2, 2, nb, bs, g * d), jnp.bfloat16),
                place((lanes, mb), jnp.int32), place((lanes,), jnp.int32)]

        def fn(q, nk, nv, pool, tbl, cl):
            return paged_decode_pallas(
                q, nk, nv, pool, tbl, cl, layer=1, head_dim=d,
                block_gather=block_gather, interpret=False,
                q_per_kv=h // g, window=window)
        return fn, args
    return build


def _latent(block_gather):
    """The latent kernel at `sarvam_105b_ep8_serve`'s decode shapes:
    128 lanes, 64 heads on rows of 576 columns stored 640 wide (values:
    the first 512), block 16, tables for a 5,120 context, the whole
    5-layer bf16 pool of one row a token handed over, the last layer
    read."""
    def build(place):
        from analytics_zoo_tpu.ops.pallas.paged_attention import (
            latent_decode_pallas)
        lanes, h, w, bs = 128, 64, 640, 16
        mb = 5120 // bs
        nb = lanes * mb + 1
        args = [place((lanes, h, w), jnp.bfloat16),
                place((lanes, w), jnp.bfloat16),
                place((5, 1, nb, bs, w), jnp.bfloat16),
                place((lanes, mb), jnp.int32), place((lanes,), jnp.int32)]

        def fn(q, new, pool, tbl, cl):
            return latent_decode_pallas(
                q, new, pool, tbl, cl, layer=4, value_width=512,
                scale=192 ** -0.5 * 1.36889 ** 2,
                block_gather=block_gather, interpret=False)
        return fn, args
    return build


def _paged_window(place):
    """The multi-head kernel with a window (no model serves it yet; it
    shares the index map and the mask with the grouped one)."""
    from analytics_zoo_tpu.ops.pallas.paged_attention import (
        paged_decode_pallas)
    lanes, h, d, bs, mb = 8, 12, 64, 16, 64
    lane = place((lanes, h * d), jnp.bfloat16)
    return (lambda q, nk, nv, pool, tbl, cl: paged_decode_pallas(
                q, nk, nv, pool, tbl, cl, layer=1, head_dim=d,
                block_gather=8, interpret=False, window=128),
            [lane, lane, lane,
             place((2, 2, lanes * mb + 1, bs, h * d), jnp.bfloat16),
             place((lanes, mb), jnp.int32), place((lanes,), jnp.int32)])


def _layer_norm(dtype):
    """BERT-base's LayerNorm (batch 32 x seq 128 rows, hidden 768),
    forward and backward."""
    def build(place):
        from analytics_zoo_tpu.ops.pallas.layer_norm import (
            layer_norm_pallas)

        def loss(x, scale, bias):
            return layer_norm_pallas(
                x, scale, bias, interpret=False
            ).astype(jnp.float32).sum()
        return (jax.grad(loss, argnums=(0, 1, 2)),
                [place((4096, 768), dtype),
                 place((768,), jnp.float32),
                 place((768,), jnp.float32)])
    return build


def _bias_gelu(place):
    """BERT-base's fc1 (768 -> 3072) with the GELU epilogue, forward
    (its backward is plain XLA)."""
    from analytics_zoo_tpu.ops.pallas.fused_dense import (
        dense_bias_gelu_pallas)
    return (lambda x, w, b: dense_bias_gelu_pallas(
                x, w, b, interpret=False),
            [place((4096, 768), jnp.bfloat16),
             place((768, 3072), jnp.bfloat16),
             place((3072,), jnp.bfloat16)])


def _flash(b, t):
    """Flash attention forward and backward at BERT-base's heads."""
    def build(place):
        from analytics_zoo_tpu.ops.pallas.flash_attention import (
            flash_attention)

        def loss(q, k, v):
            return flash_attention(
                q, k, v, interpret=False).astype(jnp.float32).sum()
        qkv = place((b, t, 12, 64), jnp.bfloat16)
        return jax.grad(loss, argnums=(0, 1, 2)), [qkv, qkv, qkv]
    return build


def _paged_tp(place):
    """The paged kernel as the tp=4 engine places it: the dispatcher
    itself (`ops.attention.paged_decode_attention`, impl pinned since
    `auto` asks the backend, which is the CPU here) inside a program
    GSPMD partitions over a 4-device mesh, pool and lanes head-sharded
    as `serving/distributed/tp.py` shards them (a head shard is a
    contiguous slice of the pool's merged axis).  Mosaic refuses a
    kernel GSPMD would have to partition, so this compiles only while
    the dispatcher carries the kernel in a shard_map."""
    from analytics_zoo_tpu.ops.attention import paged_decode_attention
    from analytics_zoo_tpu.parallel.sharding import declare_mesh
    s, h, d, bs, mb = 8, 12, 64, 16, 64
    nb = s * mb + 1
    lane = place((s, h, d), jnp.bfloat16, P(None, "tp", None))
    pool = place((2, 2, nb, bs, h * d), jnp.bfloat16,
                 P(None, None, None, None, "tp"))
    mesh = lane.sharding.mesh

    def fn(q, nk, nv, kv, tbl, cl):
        with declare_mesh(mesh):
            return paged_decode_attention(
                q, nk, nv, kv, tbl, cl, layer=1, impl="pallas",
                block_gather=8, interpret=False)
    return fn, [lane, lane, lane, pool,
                place((s, mb), jnp.int32), place((s,), jnp.int32)]


def _paged_traced_layer(place):
    """The paged kernel as a looped decoder's decode calls it
    (`ouro_2p6b_serve`: 16 lanes, 16 heads x 128, tables of 20 blocks,
    a pool of 4 x 48 = 192 slots): the slot a traced int32 scalar,
    carried in by scalar prefetch, at the table's gather of 8."""
    from analytics_zoo_tpu.ops.pallas.paged_attention import (
        paged_decode_pallas)
    s, hd, bs, mb = 16, 16 * 128, 16, 20

    def fn(q, nk, nv, kv, tbl, cl, slot):
        return paged_decode_pallas(q, nk, nv, kv, tbl, cl, layer=slot,
                                   head_dim=128, block_gather=8)
    lane = place((s, hd), jnp.float32)
    return fn, [lane, lane, lane,
                place((192, 2, s * mb + 1, bs, hd), jnp.bfloat16),
                place((s, mb), jnp.int32), place((s,), jnp.int32),
                place((), jnp.int32)]


CASES = {
    # 16 rows a grid step: the head-indicator body; 32 and up: the
    # block-diagonal one (`_BLOCK_DIAGONAL_ROWS`), both for each pool
    "paged_bf16_g1": _paged(jnp.bfloat16, 1),
    "paged_bf16_g2": _paged(jnp.bfloat16, 2),
    "paged_int8_g1": _paged(jnp.int8, 1),
    # 8 = what ops/tuning/default_tables.json names for this key
    "paged_bf16_g8": _paged(jnp.bfloat16, 8),
    "paged_int8_g8": _paged(jnp.int8, 8),
    # 8 = the table's row for lanes=64, d=128 (the expert-layer cell)
    "paged_gqa_bf16_g8_full": _paged_gqa(8, None),
    "paged_gqa_bf16_g8_window128": _paged_gqa(8, 128),
    "paged_gqa_bf16_g1_window128": _paged_gqa(1, 128),
    "paged_bf16_g8_window128": _paged_window,
    # `lfm2_24b_a2b_serve`'s decode: 128 lanes, 32 query heads over 8 KV
    # heads of 64 — a KV head's columns half a lane tile of the staged
    # block — tables for a 2816 context
    "paged_gqa_bf16_d64_g1": _paged_gqa(1, None, 128, 32, 64, 2816),
    "paged_gqa_bf16_d64_g8": _paged_gqa(8, None, 128, 32, 64, 2816),
    # None = `LATENT_BLOCK_GATHER` (32), what the latent op asks for
    "latent_bf16_default": _latent(None),
    "latent_bf16_g1": _latent(1),
    "latent_bf16_g8": _latent(8),
    "layer_norm_fwd_bwd_bf16": _layer_norm(jnp.bfloat16),
    "layer_norm_fwd_bwd_f32": _layer_norm(jnp.float32),
    "bias_gelu_fwd_bf16": _bias_gelu,
    "flash_fwd_bwd_b32_t128": _flash(32, 128),
    "flash_fwd_bwd_b8_t512": _flash(8, 512),
    "paged_bf16_g8_tp4": _paged_tp,
    "paged_bf16_g8_traced_layer": _paged_traced_layer,
}


def _one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _assert_kernel_compiles(build, place, what):
    fn, args = build(place)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{what}: compiled for v5e without the Pallas kernel in it")


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, case):
    if case.endswith("_tp4"):
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("tp",))

        def place(shape, dtype, spec=P()):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))
    else:
        place = _one_chip(topo)
    _assert_kernel_compiles(CASES[case], place, case)


def test_tuning_table_block_gathers_compile_for_v5e(topo):
    """Every `block_gather` that `ops/tuning/default_tables.json` names
    for a `paged_decode|tpu|*` key is one Mosaic accepts at that key's
    shape (the rows are warm starts nobody measured; this keeps them
    at least runnable)."""
    import json
    import re

    from analytics_zoo_tpu.ops.tuning.autotuner import DEFAULT_TABLE_PATH
    with open(DEFAULT_TABLE_PATH) as f:
        entries = json.load(f)["entries"]
    rows = {k: v for k, v in entries.items()
            if k.startswith("paged_decode|tpu|")}
    assert rows, "no paged_decode|tpu rows left in the default table"
    for key, row in rows.items():
        _, _, dtype, dims = key.split("|")
        dim = {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", dims)}
        build = _paged(jnp.dtype(dtype), row["config"]["block_gather"],
                       lanes=dim["lanes"], d=dim["d"], bs=dim["bs"])
        _assert_kernel_compiles(build, _one_chip(topo), key)


def test_tuning_table_grouped_matmuls_compile_for_v5e(topo):
    """Every `grouped_matmul|tpu|*` row of the table compiles at the
    shape it was measured at (its `shape`: the key holds the same dims
    rounded up to powers of two), with the Pallas kernel in it."""
    import json

    from analytics_zoo_tpu.ops import grouped, tuning
    with open(tuning.DEFAULT_TABLE_PATH) as f:
        entries = json.load(f)["entries"]
    rows = {k: v for k, v in entries.items()
            if k.startswith("grouped_matmul|tpu|")}
    assert rows, "no grouped_matmul|tpu rows in the default table"
    for key, row in rows.items():
        dtype = jnp.dtype(key.split("|")[2])
        shape = row["shape"]
        assert tuning.make_key("grouped_matmul", shape, dtype,
                               platform="tpu") == key
        m, g, k, n = (shape[d] for d in "mgkn")

        def build(place, m=m, g=g, k=k, n=n, dtype=dtype, row=row):
            def product(rows, kernels, sizes):
                return grouped.grouped_matmul(
                    rows, kernels, sizes, impl="kernel", interpret=False,
                    **row["config"])
            return product, (place((m, k), dtype), place((g, k, n), dtype),
                             place((g,), jnp.int32))
        _assert_kernel_compiles(build, _one_chip(topo), key)


def _assert_sampler_in_a_branch(text, vocab, what):
    """In a compiled step's `text`: the sampler's sort over
    `f32[lanes, vocab]` is there, inside a conditional's branch, and
    the entry computation — what every round runs — neither sorts nor
    copies an array of the logits' shape: handing the logits to the
    conditional moves them nowhere (sampling.py; PERF.md section 6,
    PR 38).  A relayout the sort wants for itself sits in its branch
    and is paid with it."""
    import re
    over_vocab = re.compile(rf"= \(?f32\[\d+,{vocab}\]\S*,? "
                            r".*\b(?:copy|sort)\(")
    entry, sorts, inside = [], [], False
    for ln in text.splitlines():
        if ln and not ln[0].isspace():      # a computation's first line
            inside = ln.startswith("ENTRY")
        elif over_vocab.search(ln):
            (entry if inside else sorts).append(ln.strip()[:300])
    assert not entry, f"{what}: every round pays for {entry}"
    assert any(" sort(" in ln and "/cond/branch_1_fun/" in ln
               for ln in sorts), f"{what}: {sorts}"


def _expert_models():
    """One expert layer of each expert configuration of the benchmark
    at its published widths (`kexaone_236b_ep8_serve`: gated experts
    at the hidden size; `nemotron3_super_120b_ep4_serve`: un-gated, in
    a latent space; `sarvam_105b_ep8_serve`: gated, behind latent
    attention; `lfm2_24b_a2b_serve`: gated, no shared expert), with the
    lanes its cell decodes."""
    from analytics_zoo_tpu.serving.generation import DecoderLM
    from analytics_zoo_tpu.serving.generation.hybrid import HybridLM
    bf16 = dict(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return {
        # gated experts at the hidden size with no shared expert, behind
        # attention with q/k norms and rotary positions
        "lfm2": (128, HybridLM(
            vocab=65536, hidden_size=2048, pattern="*E", n_head=32,
            n_kv_head=8, head_dim=64, rope_theta=1e6, qk_norm=True,
            moe_intermediate_size=1536, moe_gated=True,
            num_shared_experts=0, num_experts=64, num_experts_per_tok=4,
            norm_eps=1e-5, max_position_len=128000, **bf16)),
        "kexaone": (64, DecoderLM(
            vocab=19200, hidden_size=6144, n_head=64, n_kv_head=8,
            head_dim=128, layer_types=("full_attention",),
            mlp_layer_types=("sparse",), intermediate_size=18432,
            moe_intermediate_size=2048, num_experts=128,
            num_experts_per_tok=8, experts_held=(0, 16),
            routed_scaling_factor=2.5, **bf16)),
        "nemotron3": (128, HybridLM(
            vocab=32768, hidden_size=4096, pattern="*E", n_head=32,
            n_kv_head=2, head_dim=128, mamba_num_heads=128,
            mamba_head_dim=64, n_groups=8, ssm_state_size=128,
            conv_kernel=4, chunk_size=128, moe_intermediate_size=2688,
            moe_latent_size=1024,
            moe_shared_expert_intermediate_size=5376, num_experts=512,
            num_experts_per_tok=22, experts_held=(0, 128),
            routed_scaling_factor=5.0, norm_eps=1e-5,
            max_position_len=262144, **bf16)),
        # the same gated experts at a hidden size of 4096, behind
        # latent attention: the latent kernel in the same program
        "sarvam": (128, DecoderLM(
            vocab=32768, hidden_size=4096, n_head=64, n_kv_head=64,
            head_dim=576, layer_types=("latent_attention",),
            mlp_layer_types=("sparse",), intermediate_size=16384,
            moe_intermediate_size=2048, num_experts=128,
            num_experts_per_tok=8, experts_held=(0, 16),
            routed_scaling_factor=2.5, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=10000.0, rms_norm_eps=1e-6, rope_scaling=dict(
                beta_fast=32, beta_slow=1, factor=40, mscale=1,
                mscale_all_dim=1, original_max_position_embeddings=4096),
            **bf16)),
    }


@pytest.mark.parametrize("config", ["kexaone", "nemotron3", "sarvam",
                                    "lfm2"])
def test_expert_decode_holds_the_grouped_kernel(topo, monkeypatch, config):
    """The `decode` program of each expert configuration, lowered for
    the described v5e from shapes alone (no weight is made): every
    grouped product is the Pallas kernel — no `ragged-dot` left, whose
    TPU lowering multiplies a whole row tile for every group (PERF.md
    section 6, PR 36) — and no stacked expert kernel is copied on the
    way to it.  The sampler at its end sorts in a branch alone, at the
    cell's lanes and held vocabulary."""
    import re

    from analytics_zoo_tpu.ops import grouped
    from analytics_zoo_tpu.serving.generation import lane_state, steps
    from analytics_zoo_tpu.serving.generation.kv_cache import (
        PagedKVCache, pool_geometry, pool_rows)
    lanes_n, model = _expert_models()[config]
    # the dispatchers ask the backend whether to take their Pallas
    # form, the builder whether to donate: here it is a TPU
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, kv_heads, head_dim = pool_geometry(model)
    bs, blocks = 16, 64
    width = lane_state.TABLE + blocks
    decode = steps.build_steps(
        model, block_size=bs, n_head=kv_heads, quantized=False,
        paged=True, width=width, counted=True, prefill_variants=3)[2]
    place = _one_chip(topo)
    params, kv, key = jax.eval_shape(lambda: (
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   jnp.arange(8)[None])["params"],
        PagedKVCache(layers, 1 + lanes_n * blocks, bs, kv_heads,
                     head_dim, dtype=jnp.bfloat16,
                     rows=pool_rows(model)).kv,
        jax.random.PRNGKey(0)))
    args = jax.tree_util.tree_map(
        lambda x: place(x.shape, x.dtype),
        (params, kv, jax.ShapeDtypeStruct((1,), jnp.float32),
         {"rows": jax.ShapeDtypeStruct((lanes_n, width), jnp.int32),
          "rng": key},
         jax.ShapeDtypeStruct((lanes_n, 1 + width), jnp.int32)))
    built = dict(grouped.BUILT)
    text = decode.fn.lower(*args).compile().as_text()
    took = {k: v - built.get(k, 0) for k, v in grouped.BUILT.items()
            if v - built.get(k, 0)}
    assert took and all(path == grouped.KERNEL for path, _ in took), took
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    stacked = {leaf.shape for leaf in jax.tree_util.tree_leaves(params)
               if leaf.ndim == 3 and leaf.shape[0] == model.held[1]}
    assert len(stacked) == 2, stacked
    shapes = "|".join(re.escape("bf16[" + ",".join(map(str, s)) + "]")
                      for s in stacked)
    copies = [ln.strip()[:200] for ln in text.splitlines()
              if re.search(rf"= (?:{shapes})\S* copy\(", ln)]
    assert not copies, f"stacked expert kernels copied: {copies}"
    _assert_sampler_in_a_branch(text, model.vocab, config)


def test_engine_steps_leave_the_pool_where_it_lies(topo, monkeypatch):
    """The engine's own `decode` and 1024-bucket `prefill` programs at
    `gpt2_small_serve`'s widths and full pool (32 lanes x 64 blocks of
    16 + the null block; 2 layers are enough, a relayout scales with
    the pool and not with the depth), compiled for the described v5e:
    no `copy` whose result has the pool's shape, and temporaries under
    a quarter of the pool.  The pool relaid out whole was 75% of
    serving's device time until PR 29 (PERF.md section 6); the cure
    engages on every step or not at all, so this compile is its
    guard.  And the sampler's (PR 38): its sort over the vocabulary
    survives in a conditional's branch alone, and handing the logits
    to that conditional copies them nowhere."""
    import re

    from analytics_zoo_tpu.serving.generation import (
        CausalLM, GenerationEngine)
    model = CausalLM(vocab=50257, hidden_size=768, n_head=12, n_block=2,
                     intermediate_size=3072, max_position_len=1024,
                     compute_dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            jnp.arange(8)[None])["params"]))
    # the engine asks the backend whether to donate the pool, and the
    # dispatchers whether to take their Pallas form: here it is a TPU
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = GenerationEngine(
        model, params, max_slots=32, block_size=16, max_context=1024,
        prefill_buckets=[128, 256, 512, 1024], cache_dtype=jnp.bfloat16)
    place = _one_chip(topo)

    def like(x):
        return place(x.shape, x.dtype)
    s, width = 32, eng._lanes.width
    state = (jax.tree_util.tree_map(like, eng.params),
             like(eng.cache.kv), like(eng._kv_scale),
             jax.tree_util.tree_map(like, eng._lanes.state))
    programs = {
        "decode": (eng._decode_jit.fn, place((s, 1 + width), jnp.int32)),
        "prefill": (eng._prefill_jit.fn,
                    place((1 + width + 1024,), jnp.int32)),
    }
    pool = eng.cache.kv
    pool_bytes = pool.size * pool.dtype.itemsize
    l, _, slots, hd = pool.shape
    shapes = "|".join(
        re.escape("bf16[" + ",".join(map(str, dims)) + "]")
        for dims in ((l, 2, slots, hd), (l, 2, slots // 16, 16, hd)))
    pool_copy = re.compile(rf"= (?:{shapes})\S* copy\(")
    # the prefill keeps f32 logits of all 1024 positions to use one row
    # (PERF.md section 5): 206 MB that are not the pool's
    allowance = {"decode": 0, "prefill": 1024 * model.vocab * 4}
    for name, (fn, upload) in programs.items():
        compiled = fn.lower(*state, upload).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, name
        copies = [ln.strip()[:200] for ln in text.splitlines()
                  if pool_copy.search(ln)]
        assert not copies, f"{name} copies the whole pool: {copies}"
        _assert_sampler_in_a_branch(text, model.vocab, name)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < pool_bytes // 4 + allowance[name], (
            f"{name}: {temp} bytes of temporaries beside a pool of "
            f"{pool_bytes}")
        if name != "prefill":
            continue
        # a prompt's rows go in by whole blocks (PR 40,
        # kv_cache.write_kv_blocks): the one scatter left is on the
        # block view (as it lies, or its leading axes merged) with a
        # window of a whole block, and none on the row view, whose
        # window is a row — 1,024 of them cost the chip what 1,024
        # blocks would
        scatters = [ln.strip() for ln in text.splitlines()
                    if re.search(r"= bf16\[[\d,]+\]\S* scatter\(", ln)]
        on_rows = re.compile(
            rf"= bf16\[(?:{l},2,{slots}|{l * 2 * slots}),{hd}\]")
        on_blocks = re.compile(
            rf"= bf16\[(?:{l},2,{slots // 16}|{l * 2 * slots // 16})"
            rf",16,{hd}\]")
        assert not [ln[:200] for ln in scatters if on_rows.search(ln)]
        by_block = [ln for ln in scatters if on_blocks.search(ln)]
        assert len(by_block) == 1, [ln[:200] for ln in scatters]
        window = re.search(r"update_window_dims=\{([\d,]+)\}",
                           by_block[0]).group(1)
        assert len(window.split(",")) == 2, by_block[0][:400]


def test_conv_tails_stay_where_they_lie(topo, monkeypatch):
    """The `decode` and 2048-bucket `prefill` programs of an LFM2 stack
    cut to two layers (a dense convolution layer, a routed attention
    layer) at `lfm2_24b_a2b_serve`'s widths and lanes, compiled from
    shapes for the described v5e: the recurrent pool holds the tail
    alone (no scan state), both programs alias it — and the KV pool —
    in place, and neither copies a tail."""
    import re

    from analytics_zoo_tpu.serving.generation import (
        HybridLM, RecurrentStatePool, lane_state, steps)
    from analytics_zoo_tpu.serving.generation.kv_cache import (
        PagedKVCache, pool_geometry)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = HybridLM.from_config(dict(
        model_type="lfm2_moe", vocab_size=65536, hidden_size=2048,
        layer_types=["conv", "full_attention"], num_dense_layers=1,
        num_attention_heads=32, num_key_value_heads=8, conv_L_cache=3,
        conv_bias=False, intermediate_size=11776,
        moe_intermediate_size=1536, num_experts=64, num_experts_per_tok=4,
        use_expert_bias=True, norm_topk_prob=True,
        routed_scaling_factor=1.0, norm_eps=1e-5,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        max_position_embeddings=128000), compute_dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    lanes, bs, blocks = 128, 16, 2816 // 16
    width = lane_state.TABLE + blocks
    layers, kv_heads, head_dim = pool_geometry(model)
    prefill, _, decode = steps.build_steps(
        model, block_size=bs, n_head=kv_heads, quantized=False,
        paged=True, width=width, counted=True, stateful=True,
        prefill_variants=4)[:3]
    pool = RecurrentStatePool(model.state_geometry(), lanes)
    assert set(pool.shapes) == {"conv"}
    params, kv, key, tails = jax.eval_shape(lambda: (
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   jnp.arange(8)[None])["params"],
        PagedKVCache(layers, 1 + lanes * blocks, bs, kv_heads, head_dim,
                     dtype=jnp.bfloat16).kv,
        jax.random.PRNGKey(0), pool.zeros()))
    assert tails["ssm"] == ()
    place = _one_chip(topo)

    def like(x):
        return place(x.shape, x.dtype)
    state = jax.tree_util.tree_map(like, (
        params, kv, jax.ShapeDtypeStruct((1,), jnp.float32),
        {"rows": jax.ShapeDtypeStruct((lanes, width), jnp.int32),
         "rng": key, "recurrent": tails}))
    in_place = kv.size * 2 + pool.nbytes
    tail = re.compile(r"= bf16\[2,128,2048\]\S* copy\(")
    for name, fn, upload in (
            ("decode", decode, (lanes, 1 + width)),
            ("prefill", prefill, (1 + width + 2048,))):
        compiled = fn.fn.lower(
            *state, place(upload, jnp.int32)).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, name
        assert compiled.memory_analysis().alias_size_in_bytes >= in_place
        copies = [ln.strip()[:200] for ln in text.splitlines()
                  if tail.search(ln)]
        assert not copies, f"{name} copies a tail: {copies}"
