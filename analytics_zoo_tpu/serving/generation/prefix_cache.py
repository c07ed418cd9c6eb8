"""Prefix cache: radix-tree prompt reuse over the paged KV block pool.

Millions-of-users traffic is dominated by REPEATED prompt prefixes —
system prompts, few-shot templates, multi-turn histories — and without
reuse every request recomputes the full prompt and owns its KV blocks
exclusively.  This module is the SGLang-RadixAttention / vLLM-prefix-
caching idea on the PR 2 substrate: the `PagedKVCache` pool already
stores KV in fixed-size, indexed blocks, so a prompt prefix that is a
whole number of blocks can be SHARED between requests by pointing
their block tables at the same committed blocks.

Structure: a radix tree whose edges are `block_size`-token chunks of
prompt token ids.  Each node owns one committed pool block (the KV of
exactly that chunk, at the absolute positions the path from the root
spells) and holds its own reference on it via the allocator's refcount
(`BlockAllocator.share`).  On admission the scheduler walks the tree
for the longest cached prefix (`lookup`, pinning one reference per
matched block for the sequence), prefills only the uncovered tail, and
after a sequence's prompt is fully prefilled `commit` inserts its full
prompt blocks — deduplicating against concurrently-prefilled identical
prefixes by adopting the cached block and dropping the duplicate.

Sharing is safe without copies because committed blocks are NEVER
written again: only blocks fully covered by prompt tokens are
committed, matches are whole-block (and capped one token short of the
query, so at least one tail token always prefills), and decode writes
land strictly past the prompt — the scheduler still runs a
copy-on-write guard (`SlotScheduler`/engine) that un-shares a block
before any write that would hit refcount > 1, so a future fork/beam
path cannot corrupt a shared block either.

Eviction: unreferenced cached blocks (refcount 1 — the tree is the
only holder) are evicted leaves-first in LRU order when the allocator
runs dry, BEFORE the scheduler resorts to preempting a running lane —
cold cache entries are cheaper to lose than live work.  Cached blocks
count toward the existing `generation_cache_occupancy` gauge; the
`prefix_cache_*` counters/gauges below and the request-log
`prefix_hit` event make reuse observable (docs/observability.md
metric index, docs/generation.md).

`lookup` is also a fault-injection site (`generation.prefix_lookup`,
resilience/faults.py): a "raise" there must surface as a failed
admission, never a corrupted tree.

Host tier (host_tier.py, the engine's `kv_host_tier=`): with a
`HostKVTier` attached, `evict` copies each victim's KV rows to host
RAM before freeing the block, and `restore` extends a device radix
match with host-resident blocks — allocating a fresh pool block per
entry and delegating the device write to the engine's
`restore_writer`.  Both directions are advisory: any failure leaves
the tree exactly as the no-tier path would, and the lane recomputes.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.observability import now
from analytics_zoo_tpu.resilience.faults import fault_point
from analytics_zoo_tpu.serving.generation.kv_cache import PagedKVCache


class _Node:
    """One cached chunk: `chunk` (the block_size token ids of its
    edge), the pool block holding their KV, and an LRU stamp."""

    __slots__ = ("chunk", "block", "children", "parent", "last_use")

    def __init__(self, chunk: Tuple[int, ...], block: int,
                 parent: Optional["_Node"]):
        self.chunk = chunk
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.parent = parent
        self.last_use = 0


class PrefixCache:
    """Radix tree over token-id block chunks mapping prompt prefixes
    to committed KV pool blocks.  Host-side only, engine-lock
    serialized like the scheduler (no locking here)."""

    def __init__(self, cache: PagedKVCache, registry=None,
                 host_tier=None):
        self.cache = cache
        self.allocator = cache.allocator
        self.block_size = cache.block_size
        #: host-RAM spill tier (host_tier.HostKVTier) — None keeps
        #: the legacy eviction path bitwise untouched
        self.host_tier = host_tier
        if host_tier is not None:
            host_tier.bind_geometry(cache)
        #: device-write callback for restores, set by the engine:
        #: ``restore_writer(block, entry) -> bool`` lands a host
        #: entry's rows in pool block `block` (False = fall back)
        self.restore_writer = None
        #: when True (the router's prefill replica), `commit` ALSO
        #: copies newly-inserted blocks to the host tier so decode
        #: replicas sharing it adopt them without waiting for an
        #: eviction
        self.host_write_through = False
        #: DMA-lane label for the timeline — the engine points this at
        #: itself so spills stamp the replica's spool name
        self.owner = None
        self._root = _Node((), -1, None)
        self._n_blocks = 0
        #: monotonic use counter — LRU recency without wall time
        self._clock = 0
        if registry is None:
            from analytics_zoo_tpu.observability import get_registry
            registry = get_registry()
        self._c_hits = registry.counter(
            "prefix_cache_hits_total",
            help="admissions that reused >=1 cached prefix block")
        self._c_misses = registry.counter(
            "prefix_cache_misses_total",
            help="admissions that found no cached prefix")
        self._c_hit_tokens = registry.counter(
            "prefix_cache_hit_tokens_total",
            help="prompt tokens whose prefill was skipped via the "
                 "prefix cache")
        self._c_evictions = registry.counter(
            "prefix_cache_evictions_total",
            help="cached blocks evicted (LRU, unreferenced only)")
        registry.gauge(
            "prefix_cache_blocks", fn=lambda: self._n_blocks,
            help="KV pool blocks held by the prefix-cache radix tree")
        registry.gauge(
            "prefix_cache_shared_blocks", fn=self.allocator.n_shared,
            help="pool blocks with more than one live reference "
                 "(tree + sequences)")
        registry.gauge(
            "prefix_cache_hit_rate", fn=self.hit_rate,
            help="hits / (hits + misses) over this process's "
                 "lifetime (nan before the first lookup)")

    # ------------------------------------------------------------------

    @property
    def n_blocks(self) -> int:
        """Blocks currently held by the tree."""
        return self._n_blocks

    def hit_rate(self) -> float:
        looked = self._c_hits.value + self._c_misses.value
        return (self._c_hits.value / looked) if looked else float("nan")

    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of `tokens` in whole blocks, capped
        one token short of the query so the caller always has at least
        one tail token to prefill (the final position's logits must be
        computed to sample).  Pins one reference per matched block for
        the caller (released with the rest of its block table via
        `BlockAllocator.free`).  Returns (matched block ids, matched
        token count)."""
        fault_point("generation.prefix_lookup", n_tokens=len(tokens))
        bs = self.block_size
        usable = (len(tokens) - 1) // bs
        self._clock += 1
        node = self._root
        blocks: List[int] = []
        for j in range(usable):
            child = node.children.get(
                tuple(int(t) for t in tokens[j * bs:(j + 1) * bs]))
            if child is None:
                break
            child.last_use = self._clock
            blocks.append(child.block)
            node = child
        if blocks:
            self.allocator.share(blocks)
            self._c_hits.inc()
            self._c_hit_tokens.inc(len(blocks) * bs)
        else:
            self._c_misses.inc()
        return blocks, len(blocks) * bs

    def commit(self, tokens: Sequence[int],
               block_table: Sequence[int]) -> List[int]:
        """Insert the blocks fully covered by `tokens` (a prompt whose
        KV is completely written into `block_table`'s blocks) into the
        tree, taking one tree-owned reference on each newly-inserted
        block.  When a chunk is already cached under a DIFFERENT block
        (two identical prompts prefilled concurrently), the cached
        block is adopted: the caller's duplicate is freed and the
        returned table points at the shared block.  Idempotent for
        already-committed prefixes (resume re-commits are no-ops).
        Returns the (possibly deduplicated) block table."""
        bs = self.block_size
        full = len(tokens) // bs
        table = list(block_table)
        self._clock += 1
        node = self._root
        for j in range(full):
            chunk = tuple(int(t) for t in tokens[j * bs:(j + 1) * bs])
            child = node.children.get(chunk)
            if child is None:
                child = _Node(chunk, int(table[j]), node)
                node.children[chunk] = child
                self.allocator.share([child.block])
                self._n_blocks += 1
                if self.host_write_through and self.host_tier is not None:
                    # disaggregation write-through: publish the fresh
                    # block host-side NOW so decode replicas sharing
                    # the tier adopt it (advisory, like any spill)
                    self._spill_block(child)
            elif child.block != table[j]:
                # duplicate prefill of an already-cached chunk: adopt
                # the cached block (contents are the KV of the same
                # token prefix) and drop ours — one reference swap
                self.allocator.share([child.block])
                self.allocator.free([int(table[j])])
                table[j] = child.block
            child.last_use = self._clock
            node = child
        return table

    def peek(self, tokens: Sequence[int]) -> int:
        """Length (in tokens) of the longest cached prefix of
        `tokens`, capped like `lookup` — but READ-ONLY: no reference
        pinned, no counters ticked, no LRU touch.  The router's phase
        classifier and the engine's restore pre-stager call this on
        paths that must not perturb cache accounting."""
        bs = self.block_size
        usable = (len(tokens) - 1) // bs
        node = self._root
        matched = 0
        for j in range(usable):
            child = node.children.get(
                tuple(int(t) for t in tokens[j * bs:(j + 1) * bs]))
            if child is None:
                break
            matched += 1
            node = child
        return matched * bs

    # ------------------------------------------------------------------
    # host tier (spill on evict, restore on miss) — all advisory
    # ------------------------------------------------------------------

    def _key_for(self, node: _Node) -> Tuple[int, ...]:
        """The full token-id prefix `node` terminates (root→node chunk
        concatenation) — the engine-independent host-tier key."""
        chunks: List[Tuple[int, ...]] = []
        while node is not self._root:
            chunks.append(node.chunk)
            node = node.parent
        out: List[int] = []
        for chunk in reversed(chunks):
            out.extend(chunk)
        return tuple(out)

    def _spill_block(self, victim: _Node) -> None:
        """Copy one tree block's KV rows (and int8 scales) to the host
        tier.  Advisory: any failure — full tier, injected fault,
        device read error — is swallowed and only costs a future
        restore."""
        tier = self.host_tier
        if tier is None or tier.capacity_bytes <= 0:
            return
        try:
            t0 = now()
            kv, scale = self.cache.read_block(victim.block)
            tier.put(self._key_for(victim), np.asarray(kv),
                     None if scale is None else np.asarray(scale),
                     dur_s=now() - t0,
                     lane=getattr(self.owner, "spool_name", "engine"))
        except Exception:
            pass

    def restore(self, tokens: Sequence[int], blocks: List[int],
                n_matched: int) -> Tuple[List[int], int]:
        """Extend a device radix match with host-resident blocks: for
        each tier entry continuing the matched prefix, allocate a pool
        block, let the engine's `restore_writer` land the rows, and
        insert the node exactly as a commit would — the caller ends
        with one pinned reference per block (alloc) and the tree with
        its own (share), identical to a device hit.  Stops at the
        first miss/failed restore, freeing that failed block: a
        partial extension is fine, the lane prefills the rest (the
        tier is advisory).  Returns the extended (blocks, matched
        token count)."""
        tier = self.host_tier
        if tier is None or self.restore_writer is None:
            return blocks, n_matched
        bs = self.block_size
        usable = (len(tokens) - 1) // bs
        blocks = list(blocks)
        j = n_matched // bs
        node = self._root
        for i in range(j):
            node = node.children[
                tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])]
        while j < usable:
            chunk = tuple(
                int(t) for t in tokens[j * bs:(j + 1) * bs])
            entry = tier.fetch(tokens[:(j + 1) * bs])
            if entry is None:
                break
            got = self.allocator.alloc(1)   # no evict: a restore must
            if got is None:                 # never churn live entries
                break
            blk = got[0]
            ok = False
            try:
                ok = bool(self.restore_writer(blk, entry))
            except Exception:
                ok = False
            if not ok:
                self.allocator.free([blk])
                break
            child = _Node(chunk, blk, node)
            node.children[chunk] = child
            child.last_use = self._clock
            self.allocator.share([blk])     # tree ref; alloc ref is
            self._n_blocks += 1             # the caller's pin
            blocks.append(blk)
            self._c_hit_tokens.inc(bs)
            tier.count_restored()
            node = child
            j += 1
        return blocks, j * bs

    # ------------------------------------------------------------------

    def _evictable(self) -> List[_Node]:
        """Leaf nodes whose block the tree is the only holder of
        (refcount 1) — the only thing eviction may free.  Interior
        nodes become leaves as their subtrees are peeled."""
        out: List[_Node] = []
        stack = [self._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if (n is not self._root and not n.children
                    and self.allocator.ref_count(n.block) == 1):
                out.append(n)
        return out

    def evict(self, n_blocks: int) -> int:
        """Free up to `n_blocks` unreferenced cached blocks, least-
        recently-used leaves first.  Returns how many were freed (0
        when everything cached is still pinned by running lanes)."""
        freed = 0
        while freed < n_blocks:
            leaves = self._evictable()
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.last_use)
            if self.host_tier is not None:
                # spill BEFORE the free: once the block returns to the
                # pool its rows may be overwritten any time
                self._spill_block(victim)
            del victim.parent.children[victim.chunk]
            self.allocator.free([victim.block])
            self._n_blocks -= 1
            self._c_evictions.inc()
            freed += 1
        return freed

    def clear(self) -> int:
        """Drop every tree reference (blocks still pinned by live
        sequences stay allocated until those lanes release them).
        Returns the number of tree references dropped."""
        dropped = 0
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.allocator.free([n.block])
            dropped += 1
        self._root.children.clear()
        self._n_blocks = 0
        return dropped
