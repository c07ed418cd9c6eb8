"""Weights made by the benchmark from `--seed`, on the device, in one
jitted call, in the type the program holds them in (float32).

The tree's structure and shapes are asked of the model
(`jax.eval_shape` of its `init`: no value of the program's is taken);
the values follow the published initialisers of both model families:
kernels and embedding tables N(0, 0.02), biases 0, LayerNorm scale 1.
The program and the plain reference are handed the same tree."""

from __future__ import annotations

STD = 0.02


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_params(abstract, seed: int):
    """`abstract`: a tree of ShapeDtypeStructs.  Returns the tree filled
    from `seed`, on the default device."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    kinds = [str(getattr(path[-1], "key", path[-1])) for path, _ in leaves]

    def fill(key):
        out = []
        for i, ((_, leaf), kind) in enumerate(zip(leaves, kinds)):
            if kind in ("kernel", "embedding"):
                v = STD * jax.random.normal(jax.random.fold_in(key, i),
                                            leaf.shape, jnp.float32)
            elif kind == "scale":
                v = jnp.ones(leaf.shape, jnp.float32)
            elif kind == "bias":
                v = jnp.zeros(leaf.shape, jnp.float32)
            else:
                raise ValueError(f"no initialiser for parameter {kind!r}")
            out.append(v.astype(leaf.dtype))
        return out

    filled = jax.jit(fill)(seed_key(seed))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), filled)
