"""Host-RAM KV offload tier — the layer below the device block pool.

The prefix cache (prefix_cache.py) is capped by the device block pool:
at production working sets the radix tree evicts cold prefixes long
before traffic stops reusing them, and every re-miss recomputes
prefill the fleet already paid for.  This module is the
mooncake/vLLM-style tiering answer: when the tree evicts a
refcount-1 block, its KV rows (and their int8 dequant scales) are
copied into a bounded-bytes host-RAM LRU instead of being dropped, and
a later radix miss that extends into a host-resident prefix restores
the block with one `device_put` + pool write instead of a prefill
chunk.

Contract — **advisory, never authoritative**:

* The device pool and radix tree remain the only source of truth.  A
  full tier, a failed spill, an evicted entry, a corrupted buffer or a
  crashed restore can only cost SPEED (the lane recomputes the prefix
  exactly as it would have without the tier) — never correctness.
  Both directions are fault-injection sites (``generation.host_spill``
  / ``generation.host_restore``, resilience/faults.py) and both
  degrade to the no-tier path when they fire.
* Keys are full token-id prefixes (every block keyed by the ENTIRE
  prompt prefix it terminates), so entries are engine-independent:
  a block spilled by one replica is adoptable by any replica sharing
  the tier — the transport under the router's prefill/decode
  disaggregation (serving/distributed/router.py).
* Restores are double-buffered ahead of admission
  (`stage_prefix` — the PR 8 `host_input_prefetch` pattern pointed
  device-ward): the engine starts the async `device_put` for waiting
  requests BEFORE the scheduling round, so the host→device DMA hides
  inside the decode dispatch already in flight.

Observability: `kv_host_*` counters, the ``kv_host`` memory provider
(→ `memory_kv_host_*` gauges), and a module DMA ring feeding the
timeline's `kv_dma` track (`host_spill` / `host_restore` slices —
observability/timeline.py, docs/observability.md).

jax is imported lazily (inside the two methods that touch device
memory) so host-only consumers — the timeline exporter, the schema
lint — never pay the import.
"""

from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.observability import now
from analytics_zoo_tpu.resilience.faults import FaultInjected, fault_point

#: recent host<->device tier copies, oldest dropped — the timeline's
#: `kv_dma` track reads this ring (one X slice per copy, one lane per
#: engine/replica)
_DMA_RING: deque = deque(maxlen=512)


def record_dma(kind: str, dur_s: float, nbytes: int,
               lane: str = "engine") -> None:
    """Record one tier copy (`kind` = "host_spill" / "host_restore")
    for the timeline's DMA track."""
    _DMA_RING.append({"ts": time.time(), "dur_s": float(dur_s),
                      "kind": str(kind), "nbytes": int(nbytes),
                      "lane": str(lane)})


def dma_events(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The most recent `n` DMA ring entries (all when None), oldest
    first."""
    entries = list(_DMA_RING)
    return entries[-int(n):] if n is not None else entries


def reset_dma() -> None:
    _DMA_RING.clear()


class _HostEntry:
    """One spilled block: the full token-id prefix it terminates, its
    KV rows ``[L, 2, block_size, heads * head_dim]`` (pool dtype — int8
    values when the pool is quantized), the matching dequant scales
    ``[L, 2, block_size]`` (None unquantized), and — while a restore
    is staged — the in-flight device copies."""

    __slots__ = ("key", "kv", "scale", "nbytes",
                 "staged_kv", "staged_scale")

    def __init__(self, key: Tuple[int, ...], kv: np.ndarray,
                 scale: Optional[np.ndarray]):
        self.key = key
        self.kv = kv
        self.scale = scale
        self.nbytes = int(kv.nbytes
                          + (scale.nbytes if scale is not None else 0))
        self.staged_kv = None
        self.staged_scale = None


class HostKVTier:
    """Bounded-bytes host-RAM LRU of spilled KV blocks, keyed by full
    token-id prefixes.  Engine-lock serialized like the prefix cache
    when private to one engine; shared across a router's replicas it
    relies on the put/fetch granularity being one whole entry (a lost
    race is a miss, i.e. a recompute — never corruption)."""

    def __init__(self, capacity_bytes: int, registry=None):
        self.capacity_bytes = int(capacity_bytes)
        self._entries: "OrderedDict[Tuple[int, ...], _HostEntry]" = \
            OrderedDict()
        self._bytes = 0
        #: (`PagedKVCache.slab_shape`, dtype, quantized) — bound by
        #: the first engine; a mismatched slab is refused so a
        #: heterogeneous fleet cannot adopt garbage
        self._geometry: Optional[tuple] = None
        if registry is None:
            from analytics_zoo_tpu.observability import get_registry
            registry = get_registry()
        self._c_spilled = registry.counter(
            "kv_host_spilled_total",
            help="evicted prefix-cache blocks copied to the host tier")
        self._c_restored = registry.counter(
            "kv_host_restored_total",
            help="host-tier blocks restored into the device pool "
                 "(each one a prefill chunk not recomputed)")
        self._c_restore_failed = registry.counter(
            "kv_host_restore_failed_total",
            help="restores abandoned (corrupt/injected-fault entry, "
                 "geometry mismatch) — the lane recomputed instead")
        self._c_evictions = registry.counter(
            "kv_host_evictions_total",
            help="host-tier entries dropped by the bounded-bytes LRU")
        from analytics_zoo_tpu.observability import memory
        memory.register_provider("kv_host", self._stats)

    # ------------------------------------------------------------------

    def _stats(self) -> Dict[str, float]:
        return {
            "entries": len(self._entries),
            "bytes_used": self._bytes,
            "bytes_capacity": self.capacity_bytes,
        }

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes_used(self) -> int:
        return self._bytes

    def bind_geometry(self, cache) -> None:
        """Pin the slab geometry to `cache`'s pool.  A tier re-bound
        to an incompatible pool drops its entries (advisory: losing
        them only costs recomputes)."""
        geo = (cache.slab_shape, str(cache.kv.dtype),
               cache.kv_scale is not None)
        if self._geometry is not None and self._geometry != geo:
            self.clear()
        self._geometry = geo

    def _fits(self, kv: np.ndarray, scale: Optional[np.ndarray]
              ) -> bool:
        if self._geometry is None:
            return True
        slab, dt, quant = self._geometry
        if tuple(kv.shape) != slab or str(kv.dtype) != dt:
            return False
        if quant != (scale is not None):
            return False
        return scale is None or tuple(scale.shape) == slab[:3]

    # ------------------------------------------------------------------

    def put(self, key: Sequence[int], kv: np.ndarray,
            scale: Optional[np.ndarray], dur_s: float = 0.0,
            lane: str = "engine") -> bool:
        """Admit one spilled block under the bounded-bytes LRU,
        evicting least-recently-used entries to fit.  Advisory: a
        refused or injected-fault spill returns False and the caller
        proceeds exactly as if the tier were absent."""
        key = tuple(int(t) for t in key)
        try:
            fault_point("generation.host_spill", key_blocks=len(key),
                        nbytes=int(kv.nbytes))
        except FaultInjected:
            return False
        if self.capacity_bytes <= 0 or not self._fits(kv, scale):
            return False
        if key in self._entries:
            self._entries.move_to_end(key)
            return True
        entry = _HostEntry(key, kv, scale)
        if entry.nbytes > self.capacity_bytes:
            return False
        while self._bytes + entry.nbytes > self.capacity_bytes \
                and self._entries:
            _k, old = self._entries.popitem(last=False)
            self._bytes -= old.nbytes
            self._c_evictions.inc()
        self._entries[key] = entry
        self._bytes += entry.nbytes
        self._c_spilled.inc()
        record_dma("host_spill", dur_s, entry.nbytes, lane)
        return True

    def fetch(self, key: Sequence[int]) -> Optional[_HostEntry]:
        """The entry for `key`, None on a miss.  The restore fault
        site fires here: an injected fault (or a "nan" corruption
        action) counts `kv_host_restore_failed_total`, DROPS the entry
        (it is suspect) and reports a miss — the lane recomputes."""
        key = tuple(int(t) for t in key)
        try:
            action = fault_point("generation.host_restore",
                                 key_blocks=len(key))
        except FaultInjected:
            action = "nan"
        if action == "nan":
            self._c_restore_failed.inc()
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._bytes -= entry.nbytes
            return None
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        return entry

    def count_restored(self) -> None:
        """One host block landed in the device pool (the caller —
        PrefixCache.restore — writes the pool; the tier just keeps
        score)."""
        self._c_restored.inc()

    def match_tokens(self, tokens: Sequence[int]) -> int:
        """Longest host-resident prefix of `tokens` in tokens (whole
        blocks, capped one short of the query like the radix tree).
        Read-only — no LRU touch, no counters; the router's phase
        classifier calls this on every submit."""
        if self._geometry is None or not self._entries:
            return 0
        bs = self._geometry[0][2]       # slab_shape's block_size
        usable = (len(tokens) - 1) // bs
        j = 0
        while j < usable:
            key = tuple(int(t) for t in tokens[:(j + 1) * bs])
            if key not in self._entries:
                break
            j += 1
        return j * bs

    def stage_prefix(self, tokens: Sequence[int], n_matched: int,
                     depth: int = 2, device=None) -> int:
        """Start the async host→device copy of up to `depth` entries
        extending the device-matched prefix — called ahead of
        admission so the DMA overlaps the running decode round.  A
        staged entry that later loses the race (evicted, fault) is
        simply refetched as a miss.  Returns how many entries were
        staged (already-staged entries count)."""
        if self._geometry is None or not self._entries:
            return 0
        bs = self._geometry[0][2]       # slab_shape's block_size
        usable = (len(tokens) - 1) // bs
        staged = 0
        j = n_matched // bs
        while j < usable and staged < depth:
            key = tuple(int(t) for t in tokens[:(j + 1) * bs])
            entry = self._entries.get(key)
            if entry is None:
                break
            if entry.staged_kv is None:
                import jax
                entry.staged_kv = jax.device_put(entry.kv, device)
                if entry.scale is not None:
                    entry.staged_scale = jax.device_put(entry.scale,
                                                        device)
            staged += 1
            j += 1
        return staged

    def clear(self) -> int:
        """Drop every entry (advisory — only future restores are
        lost).  Returns how many were dropped."""
        n = len(self._entries)
        self._entries.clear()
        self._bytes = 0
        return n
