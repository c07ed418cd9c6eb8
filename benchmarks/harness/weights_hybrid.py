"""`weights.make_params` for a model with state-space layers: the same
values for the leaves both families share (kernels and embedding tables
N(0, 0.02), biases 0, norm scales 1), and the Mamba-2 leaves by their
published initialisers (`state-spaces/mamba`, `Mamba2.__init__`, which
the `nemotron_h` modelling code follows):

  * `A_log` = log of U(1, 16);
  * `dt_bias` = the inverse softplus of a log-uniform draw between
    `time_step_min` and `time_step_max` (0.001 and 0.1), floored at
    `time_step_floor` (0.0001);
  * `D` = 1, `norm_scale` = 1, `conv_bias` = 0;
  * `conv_kernel` = U(-1/2, 1/2): PyTorch's default for a depthwise
    `Conv1d` of 4 taps (a fan-in of 4), which is what the module keeps.

Made on the device in one jitted call, from `--seed`, in the type the
program holds each leaf in."""

from __future__ import annotations

import math

from benchmarks.harness import weights

DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def make_params(abstract, seed: int):
    """`abstract`: a tree of ShapeDtypeStructs.  Returns the tree filled
    from `seed`, on the default device."""
    import jax
    import jax.numpy as jnp

    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
    kinds = [str(getattr(path[-1], "key", path[-1])) for path, _ in leaves]

    def value(kind, key, shape):
        if kind in ("kernel", "embedding"):
            return weights.STD * jax.random.normal(key, shape, jnp.float32)
        if kind in ("scale", "norm_scale", "D"):
            return jnp.ones(shape, jnp.float32)
        if kind in ("bias", "conv_bias"):
            return jnp.zeros(shape, jnp.float32)
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(DT_MIN),
                math.log(DT_MAX)))
            dt = jnp.maximum(dt, DT_FLOOR)
            return dt + jnp.log(-jnp.expm1(-dt))
        if kind == "conv_kernel":
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        raise ValueError(f"no initialiser for parameter {kind!r}")

    def fill(key):
        return [value(kind, jax.random.fold_in(key, i), leaf.shape
                      ).astype(leaf.dtype)
                for i, ((_, leaf), kind) in enumerate(zip(leaves, kinds))]

    filled = jax.jit(fill)(weights.seed_key(seed))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), filled)
