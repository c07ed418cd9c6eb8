"""Tensor-parallel decode placement (docs/distributed-serving.md).

Shards the generation path over the mesh's ``tp`` axis the way
`parallel/ring_attention.py` shards training attention: heads split
across devices, every host-side input (tokens, block tables, context
lengths, lane masks) stays replicated, so the scheduler and the
one-static-shape jitted decode contract are untouched — with tp armed
the engine still compiles exactly one decode program
(`decode_compile_count == 1`) and greedy output is token-identical to
the single-device engine.

Layout rules (`TP_PARAM_RULES`, applied through
`infer_param_shardings`/`logical_to_sharding`):

* every projection kernel is COLUMN-sharded (output dim over "tp"):
  qkv/fc1 split heads / hidden units across devices, proj/fc2/lm_head
  keep their output features split, and each bias shards with its
  kernel's output dim.  No kernel is ever sharded on its contraction
  dim, so each device computes full-precision local matmuls and the
  only cross-device reductions are the ones GSPMD inserts to
  re-assemble a sharded activation — head-local attention itself never
  crosses a shard boundary.
* embeddings and LayerNorm params fall through to the replicated
  default (they are small and read every step).
* the `PagedKVCache` pool ``[L, 2, tokens, heads * head_dim]`` shards
  by HEADS: a head shard is a contiguous slice of the merged axis
  (`kv_cache.KV_TP_SPEC`); the int8 scale vectors ``[L, 2, tokens]``
  are per-token (their amax spans the head dim, and max is exact
  under any reduction order) and stay replicated, as do sampled tokens
  and logits, pinned by `out_shardings` on every compiled step.

A dim that the axis does not divide (e.g. a vocab head with
``vocab % tp != 0``) silently stays replicated — the rule table
degrades per-parameter instead of failing the whole model.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel.sharding import (
    declare_mesh,
    infer_param_shardings,
    mesh_axis_size,
    shard_map_compat,
)
from analytics_zoo_tpu.serving.generation.kv_cache import KV_TP_SPEC

#: param-path substring -> sharding rule (pinned-dim form of
#: `logical_to_sharding`).  Column sharding only: ":1" pins a kernel's
#: output dim, ":0" its bias.  Order matters — first matching rule
#: that shards something wins.
TP_PARAM_RULES = {
    "qkv/kernel": "tp:1",
    "qkv/bias": "tp:0",
    "proj/kernel": "tp:1",
    "proj/bias": "tp:0",
    "fc1/kernel": "tp:1",
    "fc1/bias": "tp:0",
    "fc2/kernel": "tp:1",
    "fc2/bias": "tp:0",
    "lm_head/kernel": "tp:1",
    "lm_head/bias": "tp:0",
}


class TensorParallelPlacement:
    """Device placement for one tensor-parallel generation engine.

    Owns the mesh handle, the pool/param shardings and the
    `jit_step()` wrapper the engine routes its compiled steps through.
    Constructed by `GenerationEngine(tensor_parallel=N)`; the mesh
    must already carry a ``tp`` axis of size N
    (``init_orca_context(mesh_shape={"tp": N})``)."""

    def __init__(self, mesh: Mesh, degree: int):
        self.mesh = mesh
        self.degree = int(degree)
        self.kv_sharding = NamedSharding(mesh, KV_TP_SPEC)
        self.replicated = NamedSharding(mesh, P())

    @classmethod
    def build(cls, degree: int, model,
              mesh: Optional[Mesh] = None) -> "TensorParallelPlacement":
        """Validate the runtime mesh against the requested degree and
        the model's head geometry."""
        from analytics_zoo_tpu.common.context import OrcaContext
        degree = int(degree)
        if degree < 2:
            raise ValueError(
                f"tensor_parallel degree must be >= 2, got {degree} "
                "(use 0 to disable)")
        mesh = mesh if mesh is not None else OrcaContext.mesh
        if mesh is None:
            raise RuntimeError(
                f"tensor_parallel={degree} needs an initialized mesh "
                "with a 'tp' axis — call "
                f"init_orca_context(mesh_shape={{'tp': {degree}}}) "
                "first")
        have = mesh_axis_size("tp", mesh)
        if have != degree:
            raise ValueError(
                f"tensor_parallel={degree} but the mesh's 'tp' axis "
                f"has size {have} (mesh axes: "
                f"{dict(mesh.shape)}) — init_orca_context("
                f"mesh_shape={{'tp': {degree}}})")
        if model.n_head % degree:
            raise ValueError(
                f"model.n_head {model.n_head} is not divisible by "
                f"tensor_parallel={degree}; the KV pool shards on the "
                "merged heads * head_dim axis by whole heads")
        return cls(mesh, degree)

    # -- placement -----------------------------------------------------

    def put_params(self, params: Any) -> Any:
        """Shard the param tree per `TP_PARAM_RULES` (everything the
        rules do not cover replicates)."""
        return jax.device_put(
            params,
            infer_param_shardings(params, self.mesh, TP_PARAM_RULES))

    def put_kv(self, kv: jax.Array) -> jax.Array:
        """Shard the KV pool by heads (`kv_cache.KV_TP_SPEC`)."""
        return jax.device_put(kv, self.kv_sharding)

    def put_replicated(self, x: Any) -> Any:
        """Commit a host value replicated over the whole mesh (scale
        vectors, the sampling PRNG key) so every committed step input
        lives on the same device set."""
        return jax.device_put(x, self.replicated)

    # -- compiled-step wrapper ----------------------------------------

    def jit_step(self, fn, donate_argnums, n_outputs: int):
        """`jax.jit` with output shardings pinned: output 0 is always
        the KV pool (head-sharded), everything after it (scale
        vectors, sampled tokens, logits) replicated — so each step's
        outputs feed the next step with identical layouts and the
        zero-recompile contract holds with tp armed."""
        outs = (self.kv_sharding,) + (self.replicated,) * (n_outputs - 1)

        @functools.wraps(fn)
        def step(*args):
            # traced knowing the mesh, so the paged kernel can place
            # itself over "tp" (ops/attention.py)
            with declare_mesh(self.mesh):
                return fn(*args)

        return jax.jit(step, donate_argnums=donate_argnums,
                       out_shardings=outs)

    # -- collectives / introspection ----------------------------------

    def gather_kv_heads(self, kv: jax.Array) -> jax.Array:
        """All-gather the head-sharded pool back into one replicated
        array (the explicit collective step: parity tests and the
        dryrun stage compare the tp engine's pool contents against the
        single-device engine's bit-for-bit)."""
        gather = shard_map_compat(
            lambda x: jax.lax.all_gather(x, "tp", axis=3, tiled=True),
            mesh=self.mesh, in_specs=KV_TP_SPEC, out_specs=P())
        return gather(kv)

    def per_device_kv_bytes(self, cache) -> int:
        """Resident pool bytes per device: the value tensor splits
        1/degree ways by heads, the per-token scale vectors
        replicate (docs/distributed-serving.md's residency math)."""
        scale = cache.kv_scale
        return (cache.kv.nbytes // self.degree
                + (scale.nbytes if scale is not None else 0))
