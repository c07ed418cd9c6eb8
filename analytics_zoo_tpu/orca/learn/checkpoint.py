"""Checkpoint / resume on orbax (reference: BigDL optimizer snapshots +
`find_latest_checkpoint`, /root/reference/pyzoo/zoo/orca/learn/utils.py:24,
and the DP-1 retry-restore loop, Topology.scala:1255-1310).

Crash consistency (r7): every save goes through ONE atomic commit
protocol — `write_committed`:

    1. orbax-write the state into a hidden sibling temp dir,
    2. `os.replace` the temp dir onto the final path (atomic on the
       POSIX stores training writes to),
    3. write the epoch/step sidecar (`<path>.meta.json`), then the
       commit marker (`<path>.commit`, itself written temp->rename and
       fsynced).

`find_latest_checkpoint` trusts ONLY the marker: a crash at ANY point
before step 3 leaves either an invisible temp dir or a marker-less
directory, both skipped — an elastic restart provably never loads a
torn or uncommitted write (pinned by tests/test_checkpoint_crash.py,
which kills the writer at every phase via the fault plan).  Legacy
directories written by plain orbax (no marker anywhere in the parent)
keep working through the orbax-finalized fallback.

Async saves: the r4 orbax-AsyncCheckpointer experiments left XLA:CPU
aborting inside later collective dispatches when driven from a thread,
so background saves now run through the resilience layer's
`BackgroundCheckpointer` instead — the caller thread snapshots the
state to host numpy and the writer thread runs this module's
`write_committed` over host arrays only (nothing XLA owns ever crosses
the thread boundary).  The platform gate is unchanged: async by
default off-CPU, sync on CPU; `ZOO_ASYNC_CHECKPOINT=0|1` overrides,
and `OrcaContext.background_checkpointing` arms it explicitly for
Estimator trigger saves.  Transient checkpoint I/O errors retry under
a deterministic `RetryPolicy`.

Fault-injection sites (docs/fault-tolerance.md): `checkpoint.
before_write` / `mid_write` / `before_rename` / `before_commit` /
`after_commit` / `load`.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import re
import shutil
import time
from typing import Any, Dict, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from analytics_zoo_tpu.resilience.faults import fault_point
from analytics_zoo_tpu.resilience.retry import RetryPolicy

#: marker suffix of the commit protocol; the marker's presence is the
#: definition of "this checkpoint is durable"
COMMIT_SUFFIX = ".commit"

#: transient-I/O retry for the orbax write/read calls (deterministic
#: backoff; OSError only — a corrupt checkpoint must fail loudly)
_IO_RETRY = RetryPolicy(max_attempts=3, backoff_s=0.1,
                        name="checkpoint_io")

_tmp_counter = 0


def async_save_enabled() -> bool:
    """True when unqualified saves run in the background
    (BackgroundCheckpointer).  Gated to non-CPU platforms — the r4
    XLA:CPU thread abort (module docstring) plus CPU CI determinism;
    `ZOO_ASYNC_CHECKPOINT` overrides.  On a TPU host the device->host
    snapshot is all the training loop waits for; the write, rename and
    commit happen on the writer thread."""
    env = os.environ.get("ZOO_ASYNC_CHECKPOINT")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "")
    return jax.devices()[0].platform != "cpu"


def wait_for_checkpoints():
    """Block until any in-flight background save has committed.
    Called before any restore (read-your-write) and at interpreter
    exit (no lost saves on clean shutdown).  Write FAILURES do not
    raise here — the pure read paths that call this skip the missing
    checkpoint anyway; `BackgroundCheckpointer.drain()` is where a
    failed write surfaces."""
    from analytics_zoo_tpu.resilience.checkpointing import (
        drain_background)
    drain_background(raise_on_error=False)


atexit.register(wait_for_checkpoints)


def write_committed(path: str, state,
                    meta: Optional[Dict[str, Any]] = None) -> str:
    """The atomic commit protocol (module docstring).  `state` may be
    device arrays (sync path) or a host snapshot (background writer).
    Returns `path`, durable on return."""
    global _tmp_counter
    path = os.path.abspath(path)
    parent, name = os.path.split(path)
    os.makedirs(parent, exist_ok=True)
    fault_point("checkpoint.before_write", path=path)
    # sweep temp leftovers of CRASHED previous saves of this same
    # target (a killed writer cleans nothing up — recovery happens on
    # the next save, not in the crashing process)
    for stale in os.listdir(parent):
        if stale.startswith(f".tmp-{name}-"):
            shutil.rmtree(os.path.join(parent, stale),
                          ignore_errors=True)
    _tmp_counter += 1
    tmp = os.path.join(parent,
                       f".tmp-{name}-{os.getpid()}-{_tmp_counter}")

    def _orbax_write():
        ckptr = ocp.StandardCheckpointer()
        try:
            ckptr.save(tmp, state, force=True)
            ckptr.wait_until_finished()
        finally:
            ckptr.close()

    _IO_RETRY.run(_orbax_write, retryable=(OSError,))
    fault_point("checkpoint.mid_write", path=tmp)
    fault_point("checkpoint.before_rename", path=path)
    if os.path.isdir(path):
        # overwrite (force semantics): UN-commit before destroying the
        # old version — a crash between these steps must leave the
        # path marker-less, never marked-but-torn
        if os.path.exists(path + COMMIT_SUFFIX):
            os.remove(path + COMMIT_SUFFIX)
        shutil.rmtree(path)
    os.replace(tmp, path)
    fault_point("checkpoint.before_commit", path=path)
    if meta is not None:
        _atomic_write_json(path + ".meta.json", dict(meta))
    _atomic_write_json(path + COMMIT_SUFFIX,
                       {"name": name, "wall_time": time.time(),
                        **({"meta": dict(meta)} if meta else {})})
    fault_point("checkpoint.after_commit", path=path)
    from analytics_zoo_tpu.observability import get_registry
    get_registry().counter(
        "checkpoint_committed_total",
        help="checkpoints whose commit marker landed").inc()
    return path


def _atomic_write_json(path: str, payload: Dict[str, Any]) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def save_checkpoint(path: str, state, block: Optional[bool] = None,
                    meta: Optional[Dict[str, Any]] = None) -> str:
    """Write `state` to `path` via the commit protocol.  `block=None`
    -> platform gate (background off-CPU, sync on CPU).

    DURABILITY: on the background path the returned path is NOT yet
    durable — the commit marker lands on the writer thread.
    In-process readers are covered (`load_checkpoint`/
    `find_latest_checkpoint` drain first), but before handing the path
    to ANOTHER process, or gating external work on its existence, call
    `wait_for_checkpoints()` (or `BackgroundCheckpointer.drain()`,
    which also surfaces write failures) yourself."""
    path = os.path.abspath(path)
    if block is None:
        block = not async_save_enabled()
    if block:
        return write_committed(path, state, meta=meta)
    from analytics_zoo_tpu.resilience.checkpointing import (
        get_background_checkpointer)
    return get_background_checkpointer().submit(path, state, meta=meta)


def load_checkpoint(path: str, target_state):
    """Restore into the sharding/structure of `target_state`.

    Transformer checkpoints written before scan-over-layers store one
    `block_i` subtree per layer; current modules stack them under a
    single `blocks` subtree with a leading layer axis.  On a structure
    mismatch the raw checkpoint is re-read and old-layout subtrees are
    stacked before mapping onto the target."""
    wait_for_checkpoints()          # read-your-write for async saves
    path = os.path.abspath(path)
    fault_point("checkpoint.load", path=path)
    ckptr = ocp.StandardCheckpointer()
    try:
        restored = _IO_RETRY.run(
            lambda: ckptr.restore(path, target_state),
            retryable=(OSError,))
    except Exception:
        raw = ckptr.restore(path)
        converted = _stack_block_subtrees(raw)
        flat, treedef = jax.tree_util.tree_flatten_with_path(target_state)
        leaves = []
        for key_path, target_leaf in flat:
            v = _lookup_path(converted, key_path)
            arr = np.asarray(v)
            if hasattr(target_leaf, "sharding"):
                arr = jax.device_put(arr, target_leaf.sharding)
            leaves.append(arr)
        restored = jax.tree_util.tree_unflatten(treedef, leaves)
    ckptr.close()
    return restored


def _lookup_path(tree, key_path):
    """Walk a raw-restored (nested dict/list) checkpoint by a pytree key
    path from the target state (GetAttrKey for dataclass fields, DictKey,
    SequenceKey; orbax may store sequences as int-keyed dicts)."""
    node = tree
    for k in key_path:
        if hasattr(k, "name"):        # GetAttrKey
            node = node[k.name]
        elif hasattr(k, "key"):       # DictKey
            node = node[k.key]
        elif hasattr(k, "idx"):       # SequenceKey
            if isinstance(node, dict):
                if k.idx in node:
                    node = node[k.idx]
                elif str(k.idx) in node:
                    node = node[str(k.idx)]
                else:
                    raise KeyError(
                        f"checkpoint missing sequence index {k.idx} "
                        f"(has {sorted(node, key=str)[:8]})")
            else:
                node = node[k.idx]
        else:
            raise KeyError(f"unsupported key entry {k!r}")
    return node


def _stack_block_subtrees(tree):
    """Recursively replace {"block_0": ..., "block_1": ...} families
    with {"blocks": stacked} (leading layer axis), matching nn.scan's
    parameter layout."""
    if isinstance(tree, (list, tuple)):
        # optimizer-state containers restore as sequences; the per-block
        # subtrees they mirror live beneath them
        return type(tree)(_stack_block_subtrees(v) for v in tree)
    if not isinstance(tree, dict):
        return tree
    out = {k: _stack_block_subtrees(v) for k, v in tree.items()}
    block_keys = sorted(
        (k for k in out if k.startswith("block_")
         and k.split("_", 1)[1].isdigit()),
        key=lambda k: int(k.split("_", 1)[1]))
    if block_keys and "blocks" not in out:
        stacked = jax.tree_util.tree_map(
            lambda *leaves: np.stack([np.asarray(x) for x in leaves]),
            *[out[k] for k in block_keys])
        for k in block_keys:
            del out[k]
        out["blocks"] = stacked
    return out


def has_commit_marker(path: str) -> bool:
    """Marker AND directory: a marker whose directory vanished (crash
    mid-overwrite on a non-atomic store) is not a loadable commit."""
    return os.path.isfile(path + COMMIT_SUFFIX) and os.path.isdir(path)


def _is_committed_legacy(path: str) -> bool:
    """Pre-marker fallback for directories written by plain orbax.
    Local-fs orbax saves commit via atomic tmp-dir rename, but
    GCS-style destinations mark completion with a commit file instead;
    torn directories must be skipped or an elastic restart crashes on
    its newest checkpoint instead of resuming from the intact previous
    one."""
    try:
        from orbax.checkpoint.utils import is_checkpoint_finalized
        if not is_checkpoint_finalized(path):
            return False
    except Exception as e:
        # predicate unavailable/errored: fall through to the metadata
        # check rather than refusing every checkpoint — but SAY so,
        # because the fallback is weaker on non-atomic-rename stores
        logging.getLogger(__name__).warning(
            "orbax is_checkpoint_finalized unavailable (%s: %s); "
            "falling back to the _CHECKPOINT_METADATA presence check",
            type(e).__name__, e)
    # on local fs the predicate is name-based (atomic-rename world) and
    # passes ANY directory; orbax writes _CHECKPOINT_METADATA at
    # FINALIZE, so its absence marks a torn/foreign directory there
    # too.  _METADATA is deliberately NOT accepted: the pytree metadata
    # file can exist before the write finalizes on non-atomic-rename
    # destinations — exactly the torn state this predicate must reject
    # (ADVICE r5 #2).
    try:
        return "_CHECKPOINT_METADATA" in os.listdir(path)
    except OSError:
        return False


def find_latest_checkpoint(model_dir: str,
                           version: Optional[int] = None) -> str:
    """Newest COMMITTED `ckpt-N` under `model_dir`.

    Commit policy: when ANY candidate carries a `.commit` marker the
    directory is running the r7 protocol — marker-less candidates are
    presumed uncommitted (a crash between rename and marker) and
    skipped, counted in `checkpoint_torn_skipped_total`.  A directory
    with no markers at all is legacy (plain orbax writers) and falls
    back to the orbax-finalized predicate."""
    wait_for_checkpoints()          # an in-flight save IS the latest
    pat = re.compile(r"^ckpt-(\d+)$")
    candidates = []
    for name in os.listdir(model_dir):
        m = pat.match(name)
        if m:
            candidates.append((int(m.group(1)), os.path.join(model_dir, name)))
    if not candidates:
        raise FileNotFoundError(f"no checkpoints under {model_dir}")
    if version is not None:
        for v, p in candidates:
            if v == version:
                return p
        raise FileNotFoundError(f"no checkpoint version {version}")
    marked = [c for c in candidates if has_commit_marker(c[1])]
    if marked:
        skipped = len(candidates) - len(marked)
        if skipped:
            from analytics_zoo_tpu.observability import get_registry
            get_registry().counter(
                "checkpoint_torn_skipped_total",
                help="uncommitted/torn checkpoint directories skipped "
                     "by find_latest_checkpoint").inc(skipped)
        committed = marked
    else:
        committed = [c for c in candidates
                     if _is_committed_legacy(c[1])]
    if not committed:
        raise FileNotFoundError(
            f"only uncommitted (torn) checkpoints under {model_dir}: "
            f"{sorted(p for _, p in candidates)}")
    return max(committed)[1]
