"""LayerNorm op — the ONE dispatch point for layer normalization.

Every model/keras-layer consumer routes here (enforced by
scripts/check_kernel_dispatch.py) instead of instantiating
`flax.linen.LayerNorm` or hand-rolling the math, so the fused Pallas
kernel (ops/pallas/layer_norm.py) lands everywhere at once and the
fallback numerics stay in one place.

Dispatch rules (impl="auto"):
  * "pallas" — on TPU, when rows tile 8 and d is lane-aligned (128);
    the fused fwd/bwd kernels with tuned `block_rows` (ops/tuning).
  * "xla" — everywhere else (CPU tests included): a plain-jnp mirror
    of `flax.linen.LayerNorm`'s exact formula (f32 fast-variance
    stats, `(x - mu) * (rsqrt(var + eps) * scale) + bias`, output at
    the promoted dtype), so switching the dispatch in cannot move a
    single test's numerics off the pre-fusion flax layer.

`LayerNorm` (below) is the drop-in flax module: same param names and
initializers as `nn.LayerNorm` ("scale" = ones, "bias" = zeros), so
existing checkpoints and the pretrained-BERT loaders keep working
unchanged.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def _xla_layer_norm(x, scale, bias, eps: float, out_dtype):
    """The `flax.linen.LayerNorm` formula, mirrored operation-for-
    operation (fast variance clipped at zero, scale folded into the
    rsqrt multiplier before it touches x)."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(0.0, jnp.mean(xf * xf, axis=-1, keepdims=True)
                      - mu * mu)
    mul = jax.lax.rsqrt(var + eps) * scale
    y = (x - mu) * mul + bias
    return y.astype(out_dtype)


def _pallas_supported(rows: int, d: int) -> bool:
    try:
        platform = jax.default_backend()
    except Exception:
        return False
    return (platform == "tpu" and rows % 8 == 0 and rows >= 8
            and d % 128 == 0)


def layer_norm(x, scale, bias, *, eps: float = 1e-6, impl: str = "auto",
               out_dtype=None, block_rows: Optional[int] = None,
               interpret: Optional[bool] = None):
    """LayerNorm over the last axis of `x` [..., d]; `scale`/`bias`
    are [d].  impl: "auto" | "pallas" | "xla" (see module docstring).
    `block_rows=None` asks the autotuner (ops/tuning) for the row tile;
    `interpret=True` runs the Pallas kernel on the CPU interpreter
    (parity tests)."""
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if out_dtype is None:
        out_dtype = jnp.result_type(x.dtype, scale.dtype, bias.dtype)
    # a program over several devices carries the kernel in a shard_map
    # (parallel/sharding.py): each device normalizes its share of the
    # rows, so that share is what has to tile
    from analytics_zoo_tpu.parallel.sharding import place_row_kernel
    place, shards = place_row_kernel(x)
    rows //= shards
    if impl == "auto":
        impl = "pallas" if _pallas_supported(rows, d) else "xla"
    if impl == "xla":
        return _xla_layer_norm(x, scale, bias, eps, out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown layer_norm impl {impl!r}; "
                         "use 'auto', 'pallas' or 'xla'")
    from analytics_zoo_tpu.ops.pallas import layer_norm as ln_kernel
    if block_rows is None:
        from analytics_zoo_tpu.ops import tuning
        cfg = tuning.get_config(
            "layer_norm", {"rows": rows, "d": d}, out_dtype,
            default={"block_rows": ln_kernel.DEFAULT_BLOCK_ROWS},
            candidates=[{"block_rows": r}
                        for r in (128, 256, 512, 1024, 2048)
                        if r <= rows],
            bench=_make_bench(rows, d, out_dtype))
        block_rows = cfg["block_rows"]

    def kernel(x, scale, bias):
        return ln_kernel.layer_norm_pallas(
            x, scale, bias, eps=eps, block_rows=block_rows,
            out_dtype=out_dtype, interpret=interpret)

    return place(kernel)(x, scale, bias)


def _make_bench(rows: int, d: int, dtype):
    """Benchmark closure for the autotuner: fwd+bwd of the Pallas
    kernel at the bucketed shape, iterations chained through one
    compiled scan so per-dispatch latency cannot masquerade as kernel
    time."""
    def bench(cfg, iters: int = 8):
        from analytics_zoo_tpu.observability import now
        from analytics_zoo_tpu.ops.pallas.layer_norm import (
            layer_norm_pallas)
        rows_b, d_b = (max(8, rows), max(128, d))
        k0 = jax.random.PRNGKey(0)
        x = jax.random.normal(k0, (rows_b, d_b), jnp.float32)
        scale = jnp.ones((d_b,), jnp.float32)
        bias = jnp.zeros((d_b,), jnp.float32)

        def loss(x, scale, bias):
            return layer_norm_pallas(
                x, scale, bias, block_rows=cfg["block_rows"],
                interpret=False).astype(jnp.float32).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def many(x, scale, bias):
            def body(c, _):
                dx, ds, db = g(c, scale, bias)
                return c + dx * jnp.asarray(1e-8, c.dtype), None
            c, _ = jax.lax.scan(body, x, None, length=iters)
            return c[0, 0]

        float(many(x, scale, bias))                 # compile + warm
        dt = float("inf")
        for _ in range(2):
            t0 = now()
            float(many(x, scale, bias))             # value-fetch sync
            dt = min(dt, now() - t0)
        return dt / iters
    return bench


class LayerNorm(nn.Module):
    """Drop-in replacement for `flax.linen.LayerNorm` (same "scale"/
    "bias" params, ones/zeros init, epsilon default) that routes the
    computation through `layer_norm` above — which is how every
    Estimator-trained BERT / pipelined-BERT picks up the fused kernel
    with no model changes."""
    epsilon: float = 1e-6
    dtype: Optional[Any] = None
    impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (d,))
        bias = self.param("bias", nn.initializers.zeros_init(), (d,))
        return layer_norm(x, scale, bias, eps=self.epsilon,
                          impl=self.impl, out_dtype=self.dtype)


def rms_norm(x, scale, *, eps: float = 1e-5, out_dtype=None):
    """RMSNorm over the last axis of `x` [..., d]: x * rsqrt(mean(x^2)
    + eps) * scale, the statistics and the product in float32.  Plain
    XLA on every backend (a fused kernel would land here, as the
    LayerNorm one did)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype if out_dtype is None else out_dtype)


class RMSNorm(nn.Module):
    """`rms_norm` with its learned "scale" [d] (ones; held in
    `param_dtype`); `dtype` is what the result is handed on in."""
    epsilon: float = 1e-5
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), self.param_dtype)
        return rms_norm(x, scale, eps=self.epsilon, out_dtype=self.dtype)
