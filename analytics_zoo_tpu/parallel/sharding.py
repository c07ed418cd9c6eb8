"""Sharding helpers: the single SPMD substrate that replaces the reference's
eight data-parallel backends (SURVEY.md §2.3, DP-1..DP-8).

The reference synchronizes gradients through a parameter-server allreduce
built on Spark BlockManager (BigDL `AllReduceParameter`,
zoo/src/main/scala/.../keras/models/Topology.scala:1204) or per-framework
collectives (gloo DDP, TF collective ops, Horovod, MXNet KVStore).  Here the
equivalent is *implicit*: batches are global `jax.Array`s sharded over the
mesh's data axes, parameters are sharded (or replicated) per a rule table,
and XLA inserts the reduce-scatter/all-gather collectives over ICI when the
jitted train step computes a global-mean loss.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.context import DATA_AXES, OrcaContext


def shard_map_compat(f, *, mesh, in_specs, out_specs,
                     check_vma: bool = False):
    """`jax.shard_map` with the package's default: no replication
    (`vma`) checking — the bodies here hold Pallas kernels and explicit
    collectives, neither of which carries replication rules.  Every
    shard_map consumer in the package goes through this."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def declare_mesh(mesh: Mesh):
    """Context for the body of a jitted function that GSPMD partitions
    over `mesh`: inside it the code being traced can see which mesh
    that is (`traced_mesh`).  The owners of multi-device programs —
    SPMDEngine around the model call, the tp engine around its steps —
    enter it; nothing else about the trace changes."""
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def traced_mesh():
    """The mesh GSPMD partitions the program now being traced over, or
    None for a one-device program and inside a shard_map (where the
    code is already per-device).  Mosaic refuses a Pallas kernel that
    GSPMD would have to partition ("Mosaic kernels cannot be
    automatically partitioned"), so the kernel dispatchers in `ops/`
    ask this and put their kernel in a shard_map of their own."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return None
    return mesh


def place_row_kernel(x):
    """Where a row-wise Pallas kernel `fn(x, *params) -> y` goes in the
    program now being traced.  Returns `(place, shards)`: `place(fn)`
    is `fn` itself in a one-device program, and in a program over a
    mesh a shard_map over every mesh axis — x and y split on dim 0 over
    the data axes where those divide it (whole on every device
    otherwise: a serving mesh has no data axis), the parameters whole
    on every device; `shards` is the number of pieces dim 0 is split
    into, so the caller can ask whether one piece tiles."""
    mesh = traced_mesh() if x.ndim > 1 else None
    if mesh is None:
        return (lambda fn: fn), 1
    axes, shards = data_axes(mesh), data_parallelism(mesh)
    if not axes or x.shape[0] % shards:
        axes, shards = None, 1
    spec = P(axes)

    def place(fn):
        return lambda x, *params: shard_map_compat(
            fn, mesh=mesh, in_specs=(spec,) + (P(),) * len(params),
            out_specs=spec)(x, *params)
    return place, shards


def _present_axes(mesh: Mesh, axes: Sequence[str]) -> Tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.axis_names)


def data_axes(mesh: Optional[Mesh] = None) -> Tuple[str, ...]:
    """The mesh axes a batch dimension is sharded over."""
    mesh = mesh or OrcaContext.mesh
    return _present_axes(mesh, DATA_AXES)


def data_parallelism(mesh: Optional[Mesh] = None) -> int:
    """Number of data-parallel shards (product of data-axis sizes)."""
    mesh = mesh or OrcaContext.mesh
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def named_sharding(spec: P, mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or OrcaContext.mesh
    return NamedSharding(mesh, spec)


def batch_sharding(mesh: Optional[Mesh] = None, ndim: int = None) -> NamedSharding:
    """Sharding for a batch tensor: dim 0 split over the data axes, the rest
    replicated.  (The global-batch semantics of the reference's TFDataset
    per-core batch math, pyzoo/zoo/tfpark/tf_dataset.py:148-153.)"""
    mesh = mesh or OrcaContext.mesh
    axes = data_axes(mesh)
    spec = P(axes if axes else None)
    return NamedSharding(mesh, spec)


def stacked_batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding for a [steps, batch, ...] device-cached dataset: the
    per-step batch axis (dim 1) splits over the data axes, so indexing a
    step yields exactly a `batch_sharding` batch with no resharding."""
    mesh = mesh or OrcaContext.mesh
    axes = data_axes(mesh)
    return NamedSharding(mesh, P(None, axes if axes else None))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or OrcaContext.mesh
    return NamedSharding(mesh, P())


def mesh_axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    """Size of a mesh axis, 1 when the axis is absent — the query the
    serving tp layer (serving/distributed/tp.py) uses to validate that
    `init_orca_context(mesh_shape={"tp": N})` actually provisioned the
    requested degree."""
    mesh = mesh or OrcaContext.mesh
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return int(mesh.shape[axis])


def shard_batch(batch: Any, mesh: Optional[Mesh] = None) -> Any:
    """Turn a pytree of *process-local* numpy arrays into global sharded
    `jax.Array`s, batch dim split over the data axes.

    Single-host fast path: one asynchronous `jax.device_put` of the whole
    pytree — the transfer overlaps the previous step's compute, which is
    what keeps `Estimator.fit` near the raw-loop ceiling (a per-leaf
    `make_array_from_process_local_data` costs ~10ms/batch of host-side
    assembly and blocks the pipeline).

    Multi-host: `jax.make_array_from_process_local_data` assembles a global
    array from each host's local shard (the TPU-native analog of
    RayXShards' locality-aware partition→actor assignment,
    pyzoo/zoo/orca/data/ray_xshards.py:252).
    """
    mesh = mesh or OrcaContext.mesh
    sharding = batch_sharding(mesh)

    if jax.process_count() == 1:
        host = jax.tree_util.tree_map(np.asarray, batch)
        _count_device_put_bytes(host)
        return jax.device_put(host, sharding)

    def _one(x):
        x = np.asarray(x)
        _count_device_put_bytes(x)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(_one, batch)


def _count_device_put_bytes(tree: Any) -> None:
    """Account host→device transfer volume (the JAX-aware counter the
    span layer annotates from): `jax_device_put_bytes_total` in the
    global registry covers every batch staged by `shard_batch` plus
    the DEVICE-tier dataset uploads (`SPMDEngine.cache_dataset`)."""
    from analytics_zoo_tpu.observability import annotate, get_registry
    nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(tree)
                 if hasattr(a, "nbytes"))
    get_registry().counter(
        "jax_device_put_bytes_total",
        help="bytes staged host->device by shard_batch/cache_dataset",
    ).inc(nbytes)
    annotate(device_put_bytes=nbytes)


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

def logical_to_sharding(rules: Dict[str, Optional[str]],
                        path: Tuple[str, ...],
                        shape: Tuple[int, ...],
                        mesh: Mesh) -> NamedSharding:
    """Map a parameter (by its pytree path) to a NamedSharding using
    substring rules: ``{"kernel": "tp", ...}`` shards the *largest
    divisible* dimension of any param whose joined path contains the key
    over the named axis.  An explicit dim can be pinned with
    ``"axis:dim"`` — e.g. ``{"experts": "ep:0"}`` shards the expert
    dimension (dim 0) over "ep" regardless of size ordering (expert-
    parallel tables must split on the expert axis, not their largest).

    A rule may name several comma-separated entries — ``"tp,fsdp"`` —
    applied in order, each to the largest still-unsharded divisible
    dim; each entry may independently pin its dim — ``"pp:0,fsdp"``
    stacks pipeline stages on dim 0 AND fully-shards the largest
    remaining dim (the dp×pp×fsdp composition).  Axes absent from the
    mesh (or of size 1) are skipped, so one rule table serves every
    mesh: on a dp×tp mesh the "fsdp" part is a no-op, on a dp×fsdp mesh
    the "tp" part is, and on dp×fsdp×tp the param is sharded 2-D — the
    scaling-playbook composition of tensor + fully-sharded layouts."""
    joined = "/".join(str(p) for p in path)
    ndim = len(shape)
    for key, rule in rules.items():
        if key not in joined or rule is None:
            continue
        if ndim == 0:
            continue
        spec = [None] * ndim
        for entry in rule.split(","):
            entry = entry.strip()
            if not entry:
                continue
            axis, _, dim_s = entry.partition(":")
            if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
                continue
            if dim_s:
                # pinned-dim form: "ep:0" / the "pp:0" part of
                # "pp:0,fsdp"
                dim = int(dim_s)
                if (dim < ndim and spec[dim] is None
                        and shape[dim] % mesh.shape[axis] == 0):
                    spec[dim] = axis
                continue
            # shard the largest still-unsharded dim this axis divides
            order = sorted((i for i in range(ndim) if spec[i] is None),
                           key=lambda i: -shape[i])
            for dim in order:
                if shape[dim] % mesh.shape[axis] == 0:
                    spec[dim] = axis
                    break
        if any(a is not None for a in spec):
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def infer_param_shardings(params: Any,
                          mesh: Optional[Mesh] = None,
                          rules: Optional[Dict[str, str]] = None) -> Any:
    """Produce a sharding pytree for `params`.

    Default policy: replicate everything (pure DP — capability parity with
    the reference).  With `rules` (and a mesh that has "fsdp"/"tp" axes),
    large parameters get sharded, giving FSDP/TP "for free" — the
    capability the reference lacks entirely (SURVEY.md §2.3).
    """
    mesh = mesh or OrcaContext.mesh
    rules = rules or {}

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, leaf in flat:
        names = tuple(getattr(p, "key", getattr(p, "idx", p)) for p in path)
        shape = np.shape(leaf)
        shardings.append(logical_to_sharding(rules, names, shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, shardings)
