"""Distributed serving: tensor-parallel decode + the replica router.

Two independent layers above the single-device generation engine
(docs/distributed-serving.md):

* `tp.TensorParallelPlacement` — shards the `CausalLM` param tree
  column-wise and the `PagedKVCache` pool head-wise over the mesh's
  ``tp`` axis, preserving the one-static-shape jitted decode contract
  (`GenerationEngine(tensor_parallel=N)`).
* `router.ReplicaRouter` — owns N engine replicas and admits via
  least-loaded scoring off their live queue-depth / KV-occupancy
  gauges, with drain/undrain, heartbeat health, sticky request ids
  and one re-queue of a request whose replica dies mid-stream
  (`ReplicaRouter.build(n_replicas=N)`, `ServingServer(router=...)`).
"""

from analytics_zoo_tpu.serving.distributed.router import (
    ReplicaRouter,
    RouterStream,
)
from analytics_zoo_tpu.serving.distributed.tp import (
    TP_PARAM_RULES,
    TensorParallelPlacement,
)

__all__ = [
    "ReplicaRouter",
    "RouterStream",
    "TP_PARAM_RULES",
    "TensorParallelPlacement",
]
