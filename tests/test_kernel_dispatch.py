"""Tier-1 wiring for scripts/check_kernel_dispatch.py: the build goes
red if models/ or keras/layers/ grow an ad-hoc `nn.LayerNorm` or a
hand-rolled attention-scores einsum instead of routing through the
`ops` dispatch layer (which is where the fused Pallas kernels and the
autotuner live — docs/kernels.md), or if serving/generation/ (the
decode hot path) grows a raw concat-attend einsum or a direct Pallas
import instead of dispatching through
`ops.attention.paged_decode_attention`."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "scripts", "check_kernel_dispatch.py")


def test_kernel_dispatch_clean():
    proc = subprocess.run([sys.executable, SCRIPT],
                          capture_output=True, text=True)
    assert proc.returncode == 0, (
        "ad-hoc attention/LayerNorm reimplementations crept in:\n"
        + proc.stderr)


def test_lint_detects_violation():
    """Guard against the checker silently scanning the wrong tree: the
    live tree is clean AND the patterns match the forbidden idioms."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("azt_kernel_lint",
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the live tree is clean ...
    assert mod.find_violations() == []

    # ... and the patterns really match the forbidden idioms
    def matches(line):
        return any(pat.search(line) for pat, _fix in mod.PATTERNS)

    assert matches('x = nn.LayerNorm(name="ln1")(x)')
    assert matches("y = linen.LayerNorm()(x)")
    assert matches("from flax.linen import LayerNorm")
    assert matches('s = jnp.einsum("bqhd,bkhd->bhqk", q, k)')
    assert matches('o = jnp.einsum("bhqk,bkhd->bqhd", p, v)')
    # a scores-softmax over cached latent rows, outside ops/
    assert matches('s = jnp.einsum("shw,scw->shc", q_abs, rows)')
    assert matches('o = jnp.einsum("shc,scv->shv", p, rows[..., :512])')
    # the sanctioned dispatch forms stay legal
    assert not matches("x = OpsLayerNorm(name=\"ln1\")(x)")
    assert not matches(
        "from analytics_zoo_tpu.ops.normalization import LayerNorm")
    assert not matches("out = dot_product_attention(q, k, v)")

    # the decode path's stricter set: raw einsums AND direct Pallas
    # imports are both reimplementations there
    def gen_matches(line):
        return any(pat.search(line)
                   for pat, _fix in mod.GENERATION_PATTERNS)

    assert gen_matches('s = jnp.einsum("bqhd,bkhd->bhqk", q, keys)')
    assert gen_matches(
        "from analytics_zoo_tpu.ops.pallas.paged_attention "
        "import paged_decode_pallas")
    assert gen_matches("from jax.experimental import pallas as pl")
    assert gen_matches("out = pl.pallas_call(kernel, ...)(x)")
    # the sanctioned decode dispatch stays legal
    assert not gen_matches(
        "from analytics_zoo_tpu.ops.attention import "
        "paged_decode_attention")
    assert not gen_matches("a = paged_decode_attention(q, k, v, kp, "
                           "vp, tables, ctx_len)")
    # ... and so does the latent dispatch point, with the projections
    # a model folds around it
    assert gen_matches('s = jnp.einsum("shw,scw->shc", q_abs, rows)')
    assert not gen_matches(
        "from analytics_zoo_tpu.ops.attention import "
        "latent_decode_attention")
    assert not gen_matches("o = latent_decode_attention(q_abs, row, pool, "
                           "tables, ctx_len, layer=i, value_width=r, "
                           "scale=scale)")
    assert not gen_matches('q_abs = jnp.einsum("bhd,rhd->bhr", q, w_uk)')
    # serving/generation IS scanned — and the prefix-cache (PR 8),
    # speculation (PR 15) and host-tier (PR 18) subsystems actually
    # live under that root, so a raw einsum or a private Pallas wire
    # in any of them would fail the build
    gen_root = next(r for r in mod.SCANNED_DIRS
                    if r.endswith(os.path.join("serving", "generation")))
    for fn in ("engine.py", "model.py", "prefix_cache.py",
               "speculation.py", "host_tier.py"):
        assert os.path.exists(os.path.join(gen_root, fn)), fn
