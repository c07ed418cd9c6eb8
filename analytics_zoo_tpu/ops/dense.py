"""Dense + bias + GELU op — the dispatch point for the transformer
MLP's fused first projection.

impl="auto" picks the Pallas fused-epilogue kernel
(ops/pallas/fused_dense.py) on TPU when the matmul tiles, and the
plain XLA form `gelu(x @ w + b, approximate=True)` everywhere else —
which is EXACTLY what `nn.Dense` + `get_activation("gelu")` computed
before the fusion existed, so CPU tests see unchanged numerics.

`DenseGelu` is the flax module twin of `nn.Dense(features)(x)` +
gelu: same "kernel"/"bias" param names, same lecun-normal/zeros
initializers, same `dtype` promotion — existing param trees and
checkpoints are untouched.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype


def _xla_dense_gelu(x, w, b):
    return jax.nn.gelu(jnp.dot(x, w) + b, approximate=True)


def _pallas_supported(m: int, k: int, n: int) -> bool:
    try:
        platform = jax.default_backend()
    except Exception:
        return False
    return (platform == "tpu"
            and m % 8 == 0 and k % 128 == 0 and n % 128 == 0)


def dense_bias_gelu(x, w, b, *, impl: str = "auto",
                    block_m: Optional[int] = None,
                    block_n: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """gelu(x @ w + b) — x [..., k], w [k, n], b [n].  Inputs are used
    at their given dtypes (promote before calling, as `DenseGelu`
    does).  Block sizes default to the autotuner's answer
    (ops/tuning)."""
    k = x.shape[-1]
    n = w.shape[1]
    m = 1
    for s in x.shape[:-1]:
        m *= s
    # a program over several devices carries the kernel in a shard_map
    # (parallel/sharding.py): each device multiplies its share of the
    # rows by the whole weight, so that share is what has to tile
    from analytics_zoo_tpu.parallel.sharding import place_row_kernel
    place, shards = place_row_kernel(x)
    m //= shards
    if impl == "auto":
        impl = "pallas" if _pallas_supported(m, k, n) else "xla"
    if impl == "xla":
        return _xla_dense_gelu(x, w, b)
    if impl != "pallas":
        raise ValueError(f"unknown dense_bias_gelu impl {impl!r}; "
                         "use 'auto', 'pallas' or 'xla'")
    from analytics_zoo_tpu.ops.pallas import fused_dense
    if block_m is None or block_n is None or block_k is None:
        from analytics_zoo_tpu.ops import tuning
        cfg = tuning.get_config(
            "bias_gelu", {"m": m, "k": k, "n": n}, x.dtype,
            default={"block_m": fused_dense.DEFAULT_BLOCK_M,
                     "block_n": fused_dense.DEFAULT_BLOCK_N,
                     "block_k": fused_dense.DEFAULT_BLOCK_K},
            candidates=bias_gelu_candidates(m, k, n),
            bench=_make_bench(m, k, n, x.dtype))
        block_m = block_m or cfg["block_m"]
        block_n = block_n or cfg["block_n"]
        block_k = block_k or cfg["block_k"]

    def kernel(x, w, b):
        return fused_dense.dense_bias_gelu_pallas(
            x, w, b, block_m=block_m, block_n=block_n,
            block_k=block_k, interpret=interpret)

    return place(kernel)(x, w, b)


def bias_gelu_candidates(m: int, k: int, n: int):
    """The tuner's candidate grid: MXU-shaped tiles bounded by the
    ~16 MB VMEM budget (x + w + bias + f32 accumulator + out)."""
    out = []
    for bm in (128, 256, 512):
        for bn in (256, 512, 1024):
            for bk in (256, 512):
                vmem = (bm * bk + bk * bn) * 2 + bm * bn * 6 + bn * 4
                if vmem <= 12 * 1024 * 1024 and bm <= m and bn <= n \
                        and bk <= k:
                    out.append({"block_m": bm, "block_n": bn,
                                "block_k": bk})
    return out or [{"block_m": 128, "block_n": 256, "block_k": 256}]


def _make_bench(m: int, k: int, n: int, dtype):
    """Autotuner benchmark: fwd-only (the backward is plain XLA
    matmuls regardless of the block choice), iterations chained
    through one compiled scan."""
    def bench(cfg, iters: int = 8):
        from analytics_zoo_tpu.observability import now
        from analytics_zoo_tpu.ops.pallas.fused_dense import (
            dense_bias_gelu_pallas)
        k0 = jax.random.PRNGKey(0)
        x = jax.random.normal(k0, (m, k), dtype)
        w = (jax.random.normal(jax.random.fold_in(k0, 1), (k, n), dtype)
             * (1.0 / k) ** 0.5)
        b = jnp.zeros((n,), dtype)

        @jax.jit
        def many(x, w, b):
            def body(c, _):
                o = dense_bias_gelu_pallas(
                    c, w, b, block_m=cfg["block_m"],
                    block_n=cfg["block_n"], block_k=cfg["block_k"],
                    interpret=False)
                # row-sum feedback gives each iteration a data
                # dependency on the last without assuming n >= k
                return c + o.sum(axis=1, keepdims=True).astype(c.dtype) \
                    * jnp.asarray(1e-8, c.dtype), None
            c, _ = jax.lax.scan(body, x, None, length=iters)
            return c[0, 0].astype(jnp.float32)

        float(many(x, w, b))
        dt = float("inf")
        for _ in range(2):
            t0 = now()
            float(many(x, w, b))
            dt = min(dt, now() - t0)
        return dt / iters
    return bench


class DenseGelu(nn.Module):
    """`nn.Dense(features, dtype=...)` + tanh-GELU as ONE op, with the
    epilogue fused on TPU.  Param tree is identical to nn.Dense
    ("kernel" lecun-normal, "bias" zeros), so models swap it in with
    no checkpoint migration."""
    features: int
    dtype: Optional[Any] = None
    impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias,
                                        dtype=self.dtype)
        return dense_bias_gelu(x, kernel, bias, impl=self.impl)
