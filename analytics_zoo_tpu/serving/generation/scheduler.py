"""Iteration-level scheduling (Orca-style) over a fixed slot grid.

The decode step is ONE compiled program over `max_slots` lanes;
sequences join and leave BETWEEN steps by claiming/releasing a lane in
the active-slot mask — the device never sees a shape change, admission
is pure host bookkeeping.  FCFS admission with a prefill token budget
per scheduling round (one long prompt cannot monopolize a round, and
at least one admission always proceeds so nothing starves); when the
block pool runs dry mid-decode the scheduler first LRU-evicts
unreferenced prefix-cache blocks (cold cache entries are cheaper to
lose than live work), then preempts the newest-admitted slotted
sequence — its blocks return to the pool and it re-queues at the FRONT
of the waiting line with its generated tokens intact, to be re-prefilled
(recompute-on-resume, the vLLM recovery strategy) when pressure clears.

Prefix caching (scheduler side — serving/generation/prefix_cache.py):
when a `PrefixCache` is attached, admission looks up the longest
cached whole-block prefix of the sequence's known context, pins those
blocks (refcounted sharing via `BlockAllocator`), allocates fresh
blocks only for the tail, and starts the sequence at
`prefill_pos = matched tokens` in the "prefilling" state — the engine
prefills the tail (in chunks when chunked prefill is on) and flips the
sequence to "running" when the first token is sampled.  Releasing or
preempting a lane frees its whole table through the refcounts, so
blocks still referenced by other lanes or the radix tree survive.

Copy-on-write guard: `resolve_write_conflicts` un-shares any block the
next decode write would land in while it has more than one reference —
a fresh block is allocated and returned to the engine, which copies
the block's KV device-side before swapping the table entry.  With
whole-block prompt-only sharing this never fires organically (decode
writes land strictly past committed prompt blocks); it is the safety
net that keeps a future fork/beam path from corrupting shared state,
and it is unit-tested via explicitly shared blocks.

What the device is told: the decode step reads each lane's row from a
state resident on the device (lane_state.py).  Every method here that
changes who holds a lane or what its block table says adds the lane to
`touched`; the engine uploads exactly those rows before the next
decode step and clears the set.

Invariant the engine relies on: a RUNNING sequence has KV written for
exactly `context_len - 1` tokens — the newest sampled token is pending,
and the next decode step feeds it, writes its KV, and samples its
successor.  With `in_flight` tokens of it enqueued and not collected,
the device is that many positions further on: capacity is grown for
where the device is, and everything else here (admission, preemption,
copy-on-write, release) runs with nothing in flight for the sequences
it reads.  A resume-prefill re-writes KV for all `context_len` known
tokens (minus any re-matched cached prefix) and samples the next,
restoring the same invariant.
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import Deque, List, Optional, Tuple

from analytics_zoo_tpu.observability import flight_recorder, request_log
from analytics_zoo_tpu.serving.generation.kv_cache import PagedKVCache

_UIDS = itertools.count()


class Sequence:
    """One generation request's host-side state."""

    __slots__ = ("uid", "prompt", "generated", "max_new_tokens",
                 "temperature", "top_k", "eos_id", "stream",
                 "block_table", "slot", "status", "finish_reason",
                 "n_preempted", "_admit_order", "request_id",
                 "prefill_pos", "prefix_tokens", "priority", "spec",
                 "in_flight")

    def __init__(self, prompt, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None, stream=None,
                 request_id: Optional[str] = None,
                 priority: int = 0):
        self.uid = next(_UIDS)
        #: lifecycle-log key, stable across preempt/resume (one id per
        #: request end to end — the X-Request-Id the HTTP layer echoes)
        self.request_id = request_id
        self.prompt = [int(t) for t in prompt]
        self.generated: List[int] = []
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self.stream = stream
        self.block_table: List[int] = []
        self.slot: Optional[int] = None
        self.status = "waiting"
        self.finish_reason: Optional[str] = None
        self.n_preempted = 0
        self._admit_order = -1
        #: request-class priority (control_plane.CLASS_PRIORITY): 0
        #: admits first and preempts last; ties stay FCFS / newest-
        #: preempted-first, so all-default traffic is bitwise legacy
        self.priority = int(priority)
        #: context tokens whose KV is already written (chunk-prefill
        #: progress; starts at the prefix-cache match length)
        self.prefill_pos = 0
        #: tokens skipped via the prefix cache at the LAST admission
        self.prefix_tokens = 0
        #: per-lane speculative-decoding draft state (a
        #: `speculation.SpecState`, attached lazily by the engine's
        #: Speculator; None while the lane has never drafted).  It
        #: survives preemption — drafting reads only the token
        #: history, which recompute-on-resume preserves.
        self.spec = None
        #: tokens the device has been asked for and the engine has not
        #: collected: a prefill's first, a decode round's (engine.py).
        #: `generated` and `context_len` lag the device by as many
        self.in_flight = 0

    @property
    def context_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def spent(self) -> bool:
        """Every token it may produce is sampled or in flight: the
        step itself stops the lane (lane_state.py), so it joins no
        further round and needs no further block."""
        return len(self.generated) + self.in_flight \
            >= self.max_new_tokens

    def should_finish(self) -> Optional[str]:
        if self.eos_id is not None and self.generated and \
                self.generated[-1] == self.eos_id:
            return "eos"
        if len(self.generated) >= self.max_new_tokens:
            return "length"
        return None


class SlotScheduler:
    """Admission, capacity and preemption over `max_slots` decode lanes
    backed by `cache`'s block allocator.  Host-side only; the engine
    loop is the single caller (no locking here — the engine serializes
    access).

    `prefix_cache` (optional) enables radix-tree prefix reuse on
    admission; `chunk_mode` makes admission claim lane + blocks only
    (status "prefilling") and leaves the prefill work — chunked under
    the token budget — to the engine's prefill round.  Both off keeps
    the legacy admit-and-prefill-whole-prompt behavior bitwise
    intact."""

    def __init__(self, cache: PagedKVCache, max_slots: int,
                 max_context: int, prefill_buckets,
                 prefill_token_budget: int, prefix_cache=None,
                 chunk_mode: bool = False):
        self.cache = cache
        self.max_slots = max_slots
        self.max_context = max_context
        self.prefill_buckets = sorted(prefill_buckets)
        self.prefill_token_budget = prefill_token_budget
        self.prefix_cache = prefix_cache
        self.chunk_mode = chunk_mode
        self.max_blocks_per_seq = cache.blocks_for(max_context)
        self.slots: List[Optional[Sequence]] = [None] * max_slots
        self.waiting: Deque[Sequence] = deque()
        self.n_preemptions = 0
        self._admit_counter = 0
        #: lanes whose holder or block table changed since the engine
        #: last told the device (lane_state.py reads and clears it)
        self.touched: set = set()
        #: lanes held by a request with a temperature, and those of
        #: them with `top_k` too: what the round's sampler will be
        #: asked for (sampling.py), kept in step where a lane changes
        #: hands (`_seat`) so that nobody walks the lanes to know it
        self.n_drawing = 0
        self.n_sorting = 0

    # ------------------------------------------------------------------

    def _seat(self, seq: Sequence, slot: Optional[int]) -> None:
        """`seq` takes lane `slot`, or (None) leaves the lane it
        holds: the one place a lane changes hands."""
        lane = seq.slot if slot is None else slot
        self.slots[lane] = None if slot is None else seq
        self.touched.add(lane)
        seq.slot = slot
        if seq.temperature > 0:
            n = -1 if slot is None else 1
            self.n_drawing += n
            self.n_sorting += n * (seq.top_k > 0)

    def submit(self, seq: Sequence) -> None:
        if seq.context_len + seq.max_new_tokens > self.max_context:
            raise ValueError(
                f"prompt ({seq.context_len}) + max_new_tokens "
                f"({seq.max_new_tokens}) exceeds max_context "
                f"{self.max_context}")
        # priority admission: queue ahead of the first strictly
        # lower-priority waiter (higher number = less important),
        # behind every peer — FCFS within a class, so all-default
        # traffic (priority 0 everywhere) is bitwise legacy append
        for i, other in enumerate(self.waiting):
            if other.priority > seq.priority:
                self.waiting.insert(i, seq)
                return
        self.waiting.append(seq)

    def has_work(self) -> bool:
        return bool(self.waiting) or any(
            s is not None for s in self.slots)

    def slotted(self) -> List[Sequence]:
        """Every sequence holding a lane (running or prefilling)."""
        return [s for s in self.slots if s is not None]

    def running(self) -> List[Sequence]:
        """Lanes participating in the decode step (prefill done,
        pending token waiting to be fed)."""
        return [s for s in self.slots
                if s is not None and s.status == "running"]

    def prefilling(self) -> List[Sequence]:
        """Lanes whose (tail) prefill is still in progress, in admit
        order — the engine's chunk-prefill work list."""
        return sorted((s for s in self.slots
                       if s is not None and s.status == "prefilling"),
                      key=lambda s: s._admit_order)

    def bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds the largest "
                         f"prefill bucket {self.prefill_buckets[-1]}")

    def expected_prefill_variants(self) -> int:
        """The compile budget the bucket geometry implies: any prompt
        length maps onto exactly one of these programs, so the
        dispatch ledger flags a prefill family exceeding this as
        over-budget (observability/profiling.py `declare_expected`)."""
        return len(self.prefill_buckets)

    # ------------------------------------------------------------------

    def _alloc_with_evict(self, n: int) -> Optional[List[int]]:
        """Allocate `n` blocks, LRU-evicting unreferenced prefix-cache
        blocks first when the free list can't cover the request —
        cache entries are recomputable, running lanes' work is not."""
        got = self.cache.allocator.alloc(n)
        if got is None and self.prefix_cache is not None:
            self.prefix_cache.evict(n - self.cache.allocator.available())
            got = self.cache.allocator.alloc(n)
        return got

    def _preempt_newest(self) -> Optional[Sequence]:
        """Free the newest-admitted slotted sequence's blocks and
        re-queue it at the front of the waiting line."""
        victims = self.slotted()
        if not victims:
            return None
        # lowest class first (shadow before batch before interactive),
        # newest-admitted within a class — priority composes with the
        # legacy newest-first rule instead of replacing it
        victim = max(victims, key=lambda s: (s.priority,
                                             s._admit_order))
        # per-lane decision trail for the flight recorder: a post-
        # mortem shows WHY lanes emptied under cache pressure
        flight_recorder.record("sched_preempt", uid=victim.uid,
                               slot=victim.slot,
                               blocks_freed=len(victim.block_table),
                               context_len=victim.context_len)
        request_log.event(victim.request_id, "preempt",
                          slot=victim.slot,
                          context_len=victim.context_len)
        self.cache.allocator.free(victim.block_table)
        victim.block_table = []
        self._seat(victim, None)
        victim.status = "waiting"
        victim.prefill_pos = 0
        victim.prefix_tokens = 0
        victim.n_preempted += 1
        self.n_preemptions += 1
        self.waiting.appendleft(victim)
        return victim

    def decode_blocks_short(self) -> int:
        """Blocks `ensure_decode_capacity` would have to find beyond
        the free list: above 0 it evicts or preempts, which needs
        every sequence exact (the engine collects what is in flight
        first)."""
        bs = self.cache.block_size
        grow = sum(
            max(0, (s.context_len - 1 + s.in_flight) // bs + 1
                - len(s.block_table))
            for s in self.running() if not s.spent)
        return grow - self.cache.allocator.available()

    def ensure_decode_capacity(self) -> None:
        """Before a decode step is enqueued: every running sequence
        writes one KV entry at the position the device has reached —
        context_len - 1 plus what is in flight; grow its block table
        (or evict cold cache blocks, then preempt, newest first, under
        cache pressure — possibly the needy sequence itself)."""
        # highest class then oldest first: under pressure the newest
        # and least-important lanes yield to the oldest interactive
        for seq in sorted(self.running(),
                          key=lambda s: (s.priority, s._admit_order)):
            if seq.slot is None or seq.spent:
                continue              # preempted this round; or done
            # position being written
            need = seq.context_len - 1 + seq.in_flight
            while len(seq.block_table) <= need // self.cache.block_size:
                got = self._alloc_with_evict(1)
                if got is not None:
                    seq.block_table.extend(got)
                    self.touched.add(seq.slot)
                    continue
                victim = self._preempt_newest()
                if victim is None or victim is seq:
                    break             # seq itself yielded its lane

    def grow_for_speculation(self, seq: Sequence,
                             last_pos: int) -> bool:
        """Extend `seq`'s block table to cover a speculative verify
        step's writes through position `last_pos` (the last drafted
        token's slot).  Speculation is opportunistic: allocation comes
        straight off the free list — no cache eviction, no preemption
        — and False means the lane simply decodes normally this round.
        The extension blocks are freshly allocated (refcount 1), so
        `rollback_speculation` can decref them without touching any
        shared prefix block."""
        need = last_pos // self.cache.block_size + 1
        added: List[int] = []
        while len(seq.block_table) < need:
            got = self.cache.allocator.alloc(1)
            if got is None:
                if added:
                    self.cache.allocator.free(added)
                    del seq.block_table[-len(added):]
                return False
            added.extend(got)
            seq.block_table.extend(got)
        if added:
            self.touched.add(seq.slot)
        return True

    def rollback_speculation(self, seq: Sequence) -> None:
        """The free-list half of speculative rollback: after the
        accepted prefix advanced `context_len`, decref every table
        block past the one the lane's next write (position
        context_len - 1) lands in.  Rejected drafted tokens' KV stays
        in retained blocks as garbage past ctx_len — every attention
        read masks by ctx_len and each future write overwrites exactly
        its own slot, so the write cursor rewind is purely this host-
        side bookkeeping (no device work, no recompile)."""
        if not seq.block_table:
            return
        keep = (seq.context_len - 1) // self.cache.block_size + 1
        if len(seq.block_table) > keep:
            extra = seq.block_table[keep:]
            del seq.block_table[keep:]
            self.cache.allocator.free(extra)
            self.touched.add(seq.slot)

    def resolve_write_conflicts(self) \
            -> List[Tuple[Sequence, int, int, int]]:
        """Copy-on-write guard, run after `ensure_decode_capacity`:
        for every running lane, the block its next decode write lands
        in must be exclusively owned.  A shared target (refcount > 1)
        gets a fresh block allocated here; the ENGINE copies the KV
        device-side and this method has already swapped the table
        entry and dropped the lane's reference on the shared source.
        Returns [(seq, block_index, src_block, dst_block)] copy work.
        Empty in normal operation — prompt-prefix sharing is whole-
        block and decode writes land strictly past it (see
        prefix_cache.py) — but a fork/beam path sharing suffix blocks
        would be caught here instead of corrupting a neighbor."""
        work: List[Tuple[Sequence, int, int, int]] = []
        for seq in sorted(self.running(),
                          key=lambda s: s._admit_order):
            if seq.slot is None:
                continue
            idx = (seq.context_len - 1) // self.cache.block_size
            if idx >= len(seq.block_table):
                continue              # capacity growth failed; lane
            src = seq.block_table[idx]  # will yield next round
            if self.cache.allocator.ref_count(src) <= 1:
                continue
            got = self._alloc_with_evict(1)
            if got is None:
                victim = self._preempt_newest()
                if victim is seq or victim is None:
                    continue
                got = self._alloc_with_evict(1)
                if got is None:
                    continue
            dst = got[0]
            seq.block_table[idx] = dst
            self.touched.add(seq.slot)
            self.cache.allocator.free([src])
            flight_recorder.record("sched_cow", uid=seq.uid,
                                   slot=seq.slot, src=src, dst=dst)
            work.append((seq, idx, src, dst))
        return work

    def admit(self) -> List[Sequence]:
        """FCFS admission into free slots.  Each admitted sequence gets
        blocks for its full known context — minus any cached prefix
        blocks the prefix cache shares with it.

        Legacy mode (`chunk_mode=False`): bucketed prefill sizes are
        capped by the per-round token budget (the first admission is
        always allowed through, so a long prompt larger than the budget
        still schedules eventually) and the sequence comes out
        "running" — the engine prefills it whole this round.

        Chunk mode: admission only claims the lane + blocks (status
        "prefilling", `prefill_pos` = cached tokens); the engine's
        prefill round spends the token budget on chunks."""
        admitted: List[Sequence] = []
        budget = self.prefill_token_budget
        while self.waiting:
            free_slots = [i for i, s in enumerate(self.slots)
                          if s is None]
            if not free_slots:
                break
            seq = self.waiting[0]
            cached_blocks: List[int] = []
            n_cached = 0
            if self.prefix_cache is not None:
                ctx = seq.prompt + seq.generated
                cached_blocks, n_cached = self.prefix_cache.lookup(ctx)
                if self.prefix_cache.host_tier is not None:
                    # host-tier extension of the device match: each
                    # restored block joins the table with the same
                    # refcounts as a device hit; a failed restore just
                    # shortens the match (the lane prefills the rest).
                    # `restoring_for` threads the beneficiary through
                    # to the engine's restore writer so the DMA wall
                    # lands in THIS request's blame ledger.
                    dev_cached = n_cached
                    self.prefix_cache.restoring_for = seq.request_id
                    try:
                        cached_blocks, n_cached = \
                            self.prefix_cache.restore(ctx, cached_blocks,
                                                      n_cached)
                    finally:
                        self.prefix_cache.restoring_for = None
                    if n_cached > dev_cached:
                        request_log.event(
                            seq.request_id, "host_restore",
                            tokens=n_cached - dev_cached)
            if not self.chunk_mode:
                bucket = self.bucket_for(seq.context_len - n_cached)
                if admitted and bucket > budget:
                    if cached_blocks:
                        self.cache.allocator.free(cached_blocks)
                    break
            blocks = self._alloc_with_evict(
                self.cache.blocks_for(seq.context_len)
                - len(cached_blocks))
            if blocks is None:
                if cached_blocks:
                    self.cache.allocator.free(cached_blocks)
                break                 # pressure: wait for releases
            self.waiting.popleft()
            seq.block_table = cached_blocks + blocks
            seq.prefill_pos = n_cached
            seq.prefix_tokens = n_cached
            self._seat(seq, free_slots[0])
            seq.status = "prefilling" if self.chunk_mode else "running"
            seq._admit_order = self._admit_counter
            self._admit_counter += 1
            if not self.chunk_mode:
                budget -= bucket
            admitted.append(seq)
            flight_recorder.record("sched_admit", uid=seq.uid,
                                   slot=seq.slot,
                                   blocks=len(seq.block_table),
                                   prefix_tokens=n_cached,
                                   resumed=seq.n_preempted > 0)
            request_log.event(
                seq.request_id,
                "resume" if seq.n_preempted > 0 else "admit",
                slot=seq.slot)
            if n_cached:
                # the reuse event an operator greps a slow request's
                # timeline for: how much prefill was skipped
                request_log.event(seq.request_id, "prefix_hit",
                                  tokens=n_cached,
                                  blocks=len(cached_blocks))
        return admitted

    def release(self, seq: Sequence, reason: str) -> None:
        """Finish: blocks back to the pool (one reference each —
        blocks shared with the radix tree or other lanes survive),
        lane freed for the next admission — the join/leave half of
        continuous batching."""
        if seq.block_table:
            self.cache.allocator.free(seq.block_table)
            seq.block_table = []
        if seq.slot is not None:
            self._seat(seq, None)
        seq.status = "finished"
        seq.finish_reason = reason
        flight_recorder.record("sched_release", uid=seq.uid,
                               reason=reason,
                               generated=len(seq.generated))
        request_log.finish(seq.request_id, reason)
