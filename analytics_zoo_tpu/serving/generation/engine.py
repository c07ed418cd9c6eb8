"""Continuous-batching decode engine.

The hot loop is ONE jitted decode step over a fixed `max_slots`-lane
grid — a row a lane (pending token, position, active flag, sampling
params, block table) and the sampling key, resident on the device and
advanced by the step itself (lane_state.py).  Sequences join and leave
between steps as dirty rows of that state, never as a change to the
compiled program: steady-state serving triggers ZERO recompiles after
`warmup()` (asserted in tests via the jit cache size).  Prefill runs
per-sequence over power-of-two length buckets, so any prompt length
hits one of O(log max_context) compiled programs.

A round is collected one round late.  The step needs nothing from the
host that depends on the round before it, and stops a lane that is
done itself, so `step()` enqueues this round's prefills and decode
step first and only then fetches, accounts and emits what was in
flight before that step, in dispatch order — the previous `step()`'s
decode round, this round's prefills: the host's part of a round runs
while the device works on the next.  Wherever the host has to be
exact — before a preemption, with chunked or prefix-matched prefill,
speculation or the host tier on, an eviction, an error, out of work —
what is in flight is collected first (`_drain`), decided from what the
round is about to do and from nothing else.

Paging: the decode step hands the pool and each lane's block table to
the model's paged path, and the paged-attention kernel
(ops/pallas/paged_attention.py via ops.attention.paged_decode_attention)
gathers blocks by table index INSIDE the kernel — no contiguous
[S, C, h, d] context tensor is materialized (`decode_attention=
"concat"` keeps the legacy XLA-gather+concat path as the parity
oracle).  New tokens' K/V are scattered back into block slots —
quantized on write when the pool is int8 (`kv_quantization="int8"`).
Inactive lanes carry the null block table and scribble into block 0
(kv_cache.py).

Streaming: `submit()` returns a `GenerationStream`; the engine loop
pushes each sampled token as it exists, so a consumer (the HTTP
/generate chunked response) emits tokens with per-token latency, not
per-request.

Prefix caching + chunked prefill (`prefix_caching=True` /
`chunked_prefill=True`, both default off → the legacy paths are
bitwise untouched): with either on, prefill runs through ONE extra
compiled family — the chunk step, which attends over the
already-written pool context and writes a bucket-sized slab of new
positions — so a prefix-cache hit prefills only the uncovered tail,
and (chunked mode) a long prompt spreads its prefill across scheduling
rounds under the existing token budget instead of stalling every
running lane.  The radix tree, refcounted block sharing and
copy-on-write live in prefix_cache.py + scheduler.py; the decode
program is identical in every mode, so the zero-recompile contract
survives with everything armed.

Speculative decoding (`speculative_decoding=True` +
`speculative_k`, default off → the decode path is bitwise untouched):
greedy lanes draft up to k continuation tokens from their own token
history (speculation.py's n-gram prompt lookup), and ONE spec-verify
step — a fixed [max_slots, 1+bucket] grid per pow2 k-bucket, the
chunk step's ctx-read shape over the pool — scores every drafted lane
at once, writing draft KV into freshly allocated blocks and taking
greedy argmax at every position.  The longest draft prefix matching
argmax is accepted plus the bonus token the verify logits yield for
free (1..k+1 tokens per lane per round); rejected tail blocks decref
straight back through the allocator (`rollback_speculation`) and the
non-drafting lanes run the unchanged decode step.  Verify tokens
charge the same per-round `prefill_token_budget` chunked prefill
spends, and the verify families are warmed in `warmup()` alongside
decode — zero recompiles with speculation armed.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import (Deque, Dict, List, NamedTuple, Optional,
                    Sequence as Seq, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.observability import (
    flight_recorder,
    get_registry,
    log_event,
    maybe_record,
    maybe_spool,
    maybe_watchdog,
    memory,
    now,
    profiling,
    request_log,
    step_clock,
    tracing,
)
from analytics_zoo_tpu.serving.generation import lane_state
from analytics_zoo_tpu.serving.generation.decoder import ExpertCounters
from analytics_zoo_tpu.serving.generation.kv_cache import (
    PagedKVCache,
    RecurrentStatePool,
    pool_geometry,
    pool_rows,
    state_geometry,
)
from analytics_zoo_tpu.resilience.faults import (
    FaultInjected,
    PoisonedRequestError,
    fault_point,
)
from analytics_zoo_tpu.serving.generation.speculation import Speculator
from analytics_zoo_tpu.serving.generation.host_tier import (
    HostKVTier,
    record_dma,
)
from analytics_zoo_tpu.serving.generation.prefix_cache import PrefixCache
from analytics_zoo_tpu.serving.generation.scheduler import (
    Sequence,
    SlotScheduler,
)
from analytics_zoo_tpu.serving.generation.steps import build_steps

_STREAM_END = object()

#: seconds the serving loop lets go of the interpreter lock after each
#: round (`_loop`).  A round enqueued ahead is hardly ever waited for,
#: so the loop is a thread that computes, and the interpreter takes
#: its lock from such a thread only when a waiting one has waited a
#: whole switch interval (5 ms): each wake-up of a handler — a request
#: to submit, a token to write — would cost that.  After a round's
#: collection is when they all have something to do
TURN_S = 0.0003

#: why what is in flight was collected before its time (the suffixes of
#: `generation_pipeline_drains_total_<reason>`): an allocation that
#: would fail, so that the preemption reads exact sequences; a
#: copy-on-write check; a host-tier restore; a chunked or
#: prefix-matched prefill; a verify round; a poisoned request's
#: eviction; a step that failed; no round left to enqueue (out of work,
#: `run_until_idle()`'s end, `stop()`)
DRAINS = ("preempt", "cow", "host_restore", "chunk", "verify", "evict",
          "error", "idle")

#: what a program's sampler ran (the suffixes of
#: `generation_sample_rounds_total_<path>`; sampling.py): the argmax
#: alone, the draw beside it, the sort over the vocabulary inside the
#: draw
SAMPLE_PATHS = ("argmax", "draw", "sort")

#: which pool writer a dispatched prefill's program was built with (the
#: suffixes of `generation_prefill_pool_writes_total_<form>`;
#: kv_cache.py): whole blocks for a whole prompt (`prefill`), single
#: rows for a chunk, whose start need not be a block's
#: (`chunk_prefill`)
POOL_WRITES = ("block", "row")

#: the fields of the `cpu.loop` mark a clocked round leaves in a
#: profiler trace (`step`) behind its microseconds of wall, and which
#: of the loop's phases, as the innermost open one, the stepping
#: thread's CPU is charged to.  It is the rule by which the benchmark
#: charges the device's idle time to the same spans
#: (`benchmarks/harness/span_metrics.py`, held to this by a test): a
#: decode span's own time goes with its dispatch, everything under a
#: `prefill` is the prefill's, and `wait`, `housekeeping` and the time
#: between rounds are `off_round`
CPU_BUCKETS = ("schedule", "prefill_host", "dispatch", "fetch",
               "account", "emit", "off_round")
CPU_PHASE = {"round": "schedule", "admit": "schedule",
             "capacity": "schedule", "stage": "dispatch",
             "dispatch": "dispatch", "decode": "dispatch",
             "spec_verify": "dispatch", "fetch": "fetch",
             "account": "account", "emit": "emit"}
CPU_UNDER = {"prefill": "prefill_host"}


class _Prefill(NamedTuple):
    """A prefill enqueued and not collected: its first token is still
    on the device."""
    seq: Sequence
    head: np.ndarray          # the step's columns it was given
    bucket: int
    tokens: int               # real tokens prefilled
    t0: float
    nxt: jax.Array
    moe: list
    rec: object               # its goodput record, laps so far


class _Decode(NamedTuple):
    """A decode round enqueued and not collected."""
    lanes: Dict[int, Sequence]   # who the host put in it, by lane
    t0: float
    nxt: jax.Array
    moe: list

# admission policy lives in the unified AdmissionCore
# (serving/control_plane/admission.py) — one door policy for the
# engine, the worker pool and the /predict batcher.  The exception
# types moved to serving/errors.py next to the taxonomy table; these
# re-exports keep every historical import path working.
from analytics_zoo_tpu.serving.control_plane.admission import (  # noqa: E402,E501
    AdmissionCore,
)
from analytics_zoo_tpu.serving.errors import (  # noqa: E402,F401
    QueueFull,
    RequestTooLarge,
)


class GenerationStream:
    """Consumer half of one request: iterate to receive token ids as
    they are sampled; `tokens()` drains to completion.  After the
    iterator is exhausted `finish_reason` is set ("length" | "eos" |
    "error: ...")."""

    def __init__(self, seq: Sequence, timeout: float = 120.0):
        self.seq = seq
        self.timeout = timeout
        self._q: "queue.Queue" = queue.Queue()

    def _put(self, token: int) -> None:
        self._q.put(int(token))

    def _close(self) -> None:
        self._q.put(_STREAM_END)

    @property
    def finish_reason(self) -> Optional[str]:
        return self.seq.finish_reason

    @property
    def request_id(self) -> Optional[str]:
        """The lifecycle-log id of this request (request_log.get(...)
        returns its full event timeline and derived TTFT/TPOT/e2e)."""
        return self.seq.request_id

    def __iter__(self):
        while True:
            item = self._q.get(timeout=self.timeout)
            if item is _STREAM_END:
                return
            yield item

    def tokens(self) -> List[int]:
        return list(self)


class GenerationEngine:
    """Continuous-batching generation over a `CausalLM`.

    `submit()` from any thread; drive the loop either explicitly
    (`run_until_idle()`, tests/bench) or as a background thread
    (`start()`/`stop()`, serving).  `warmup()` compiles the decode step
    and every prefill bucket up front so live traffic never waits on
    XLA."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 block_size: int = 16, max_context: int = 512,
                 num_blocks: Optional[int] = None,
                 prefill_buckets: Optional[Seq[int]] = None,
                 prefill_token_budget: int = 2048,
                 cache_dtype=jnp.float32, registry=None, seed: int = 0,
                 max_queue: Optional[int] = None,
                 kv_quantization: Optional[str] = None,
                 decode_attention: str = "paged",
                 slo_shed_min_queue: Optional[int] = None,
                 prefix_caching: bool = False,
                 chunked_prefill: bool = False,
                 tensor_parallel: int = 0,
                 speculative_decoding: bool = False,
                 speculative_k: int = 4, kv_host_tier=0):
        if model.max_position_len < max_context:
            raise ValueError(
                f"model.max_position_len {model.max_position_len} < "
                f"max_context {max_context}")
        self.model = model
        #: analytic FLOPs model for MFU accounting — the dispatch
        #: ledger combines these with the fenced walls below; None when
        #: the model doesn't carry the CausalLM dims (a stand-in model
        #: in tests) or says its blocks are not CausalLM's (a model
        #: with a `kv_geometry` of its own: grouped heads, experts —
        #: counting it as 4d^2 + 2d*ff a block would put wrong MFU
        #: gauges on /dispatch), which simply zeroes the MFU gauges
        try:
            self._flops = (None if hasattr(model, "kv_geometry") else
                           profiling.CausalLMFlops.from_model(model))
        except (AttributeError, TypeError):
            self._flops = None
        #: tensor-parallel decode (serving/distributed/tp.py); 0 (the
        #: default) keeps the legacy single-device placement bitwise
        #: untouched
        self.tensor_parallel = int(tensor_parallel or 0)
        if self.tensor_parallel < 0:
            raise ValueError("tensor_parallel must be >= 0 (0 = off)")
        #: features a model cannot serve are refused here, by name and
        #: with the model's reason, before anything is placed
        #: (decoder.py and hybrid.py say which and why)
        refused = getattr(model, "unsupported_features", lambda: ())()
        asked = dict(
            tensor_parallel=self.tensor_parallel > 1 and tensor_parallel,
            kv_quantization=kv_quantization == "int8" and "int8",
            prefix_caching=prefix_caching, chunked_prefill=chunked_prefill,
            speculative_decoding=speculative_decoding,
            kv_host_tier=kv_host_tier)
        for feature in refused:
            # (an empty HostKVTier is falsy, and asked for all the same)
            if asked.get(feature) not in (None, False, 0):
                reason = (refused[feature] if isinstance(refused, dict)
                          else None)
                raise NotImplementedError(
                    f"{type(model).__name__} cannot be served with "
                    f"{feature}={asked[feature]!r}"
                    + (f": {reason}" if reason else ""))
        if self.tensor_parallel > 1:
            from analytics_zoo_tpu.serving.distributed.tp import (
                TensorParallelPlacement)
            self._tp = TensorParallelPlacement.build(
                self.tensor_parallel, model)
            self.params = self._tp.put_params(params)
        else:
            self._tp = None
            self.params = jax.device_put(params)
        self.max_slots = max_slots
        self.max_context = max_context
        if decode_attention not in ("paged", "concat"):
            raise ValueError(
                f"decode_attention must be 'paged' or 'concat', got "
                f"{decode_attention!r}")
        #: "paged" (default) routes the decode step through
        #: ops.attention.paged_decode_attention (block-table gather
        #: inside the kernel on TPU); "concat" keeps the legacy
        #: gather+concat-attend path (the parity oracle)
        self.decode_attention = decode_attention
        self.kv_quantization = kv_quantization
        self._quantized = kv_quantization == "int8"
        #: radix-tree prompt-prefix reuse (prefix_cache.py); off (the
        #: default) keeps the engine bitwise-identical to the
        #: pre-cache behavior
        self.prefix_caching = bool(prefix_caching)
        #: chunked prefill: on, long prompts prefill in
        #: token-budget-bounded chunks with decode steps for the other
        #: lanes in between
        self.chunked_prefill = bool(chunked_prefill)
        #: either feature routes prefill through the chunk step (the
        #: ctx-aware prefill program); both off keeps the legacy
        #: whole-prompt prefill path untouched
        self._use_chunks = self.prefix_caching or self.chunked_prefill
        #: draft-free speculative decoding (speculation.py); off (the
        #: default) keeps the decode loop bitwise untouched
        self.speculative_decoding = bool(speculative_decoding)
        self.speculation = (Speculator(int(speculative_k))
                            if self.speculative_decoding else None)
        if num_blocks is None:
            # comfortable default: every lane can hold a full context
            num_blocks = max_slots * (-(-max_context // block_size)) + 1
        n_layers, kv_heads, head_dim = pool_geometry(model)
        self.cache = PagedKVCache(
            n_layers, num_blocks, block_size, kv_heads, head_dim,
            dtype=cache_dtype, quantization=kv_quantization,
            rows=pool_rows(model))
        #: functional scale state fed to the jitted steps alongside
        #: `cache.kv` — a 1-element placeholder when quantization is
        #: off (the steps return it untouched)
        self._kv_scale = (self.cache.kv_scale if self._quantized
                          else jnp.zeros((1,), jnp.float32))
        if self._tp is not None:
            # head-shard the pool, replicate the per-token scales —
            # every committed step input now lives on the mesh, so the
            # compiled steps see one stable input layout
            self.cache.kv = self._tp.put_kv(self.cache.kv)
            self._kv_scale = self._tp.put_replicated(self._kv_scale)
            if self._quantized:
                self.cache.kv_scale = self._kv_scale
        if prefill_buckets is None:
            prefill_buckets = []
            b = min(16, max_context)
            while b < max_context:
                prefill_buckets.append(b)
                b *= 2
            prefill_buckets.append(max_context)
        elif max(prefill_buckets) < max_context:
            # a preempted sequence re-prefills at up to max_context
            # tokens; the top bucket must cover it
            raise ValueError(
                f"largest prefill bucket {max(prefill_buckets)} < "
                f"max_context {max_context}")
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        #: host-RAM KV offload tier (host_tier.py); 0 (the default)
        #: keeps the eviction path bitwise untouched.  Accepts a byte
        #: capacity OR an existing HostKVTier (the router shares ONE
        #: tier across replicas for disaggregation).  Needs the prefix
        #: cache; disabled under tensor parallelism (a head-sharded
        #: pool has no single-host slab to spill).
        if isinstance(kv_host_tier, HostKVTier):
            host_tier = kv_host_tier
        else:
            cap = int(kv_host_tier or 0)
            if cap < 0:
                raise ValueError("kv_host_tier must be >= 0 (0 = off)")
            host_tier = (HostKVTier(cap, registry=reg) if cap > 0
                         else None)
        self.host_tier = (host_tier if self.prefix_caching
                          and self._tp is None else None)
        self.prefix_cache = (PrefixCache(self.cache, registry=reg,
                                         host_tier=self.host_tier)
                             if self.prefix_caching else None)
        if self.prefix_cache is not None and self.host_tier is not None:
            self.prefix_cache.owner = self
            self.prefix_cache.restore_writer = self._host_restore_write
        self.scheduler = SlotScheduler(
            self.cache, max_slots, max_context, prefill_buckets,
            prefill_token_budget, prefix_cache=self.prefix_cache,
            chunk_mode=self._use_chunks)
        #: chunked-prefill chunk size cap: the LARGEST prefill bucket
        #: that fits the per-round token budget (at least the smallest
        #: bucket), so every chunk maps onto one warmed bucket program
        fitting = [b for b in self.scheduler.prefill_buckets
                   if b <= prefill_token_budget]
        self._chunk_cap = (max(fitting) if fitting
                           else self.scheduler.prefill_buckets[0])
        #: admission policy — the unified AdmissionCore
        #: (serving/control_plane/admission.py): queue bound
        #: (`max_queue`; None = unbounded, servers should bound it),
        #: SLO-aware shedding past `slo_shed_min_queue` waiting
        #: (default: one queued request per decode lane), and the
        #: per-tenant quota gate.  `max_queue`/`slo_shed_min_queue`
        #: remain attributes of the engine via properties below.
        self.admission = AdmissionCore(
            max_queue=max_queue,
            slo_shed_min_queue=(max_slots if slo_shed_min_queue is None
                                else int(slo_shed_min_queue)),
            retry_after=self.retry_after_s)
        #: registry label ("model@version") stamped on this engine's
        #: request-log records; None outside a ModelRegistry
        self.model_label: Optional[str] = None
        #: the second kind of state — what a model's state layers carry
        #: a lane, found by its slot (kv_cache.RecurrentStatePool) — or
        #: None for a model all of whose state is keys and values
        recurrent = state_geometry(model)
        self.state_pool = (RecurrentStatePool(recurrent, max_slots)
                           if recurrent is not None else None)
        #: lane rows + sampling key (+ that pool), resident where the
        #: steps run
        self._lanes = lane_state.LaneState(
            self.scheduler, seed,
            lane_state.placement(self.params, self._tp), reg,
            recurrent=(self.state_pool.zeros()
                       if self.state_pool is not None else None))
        #: dispatches enqueued and not collected, in dispatch order
        #: (`_Prefill`, `_Decode`): between two `step()`s the decode
        #: round the last one enqueued, inside one also its prefills
        #: and its own decode round
        self._in_flight: Deque = deque()
        #: when the last of them was collected (`_lap`)
        self._collected_at = 0.0
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: telemetry-spool identity for this engine's serving loop;
        #: the replica router renames it to the replica name so each
        #: replica's snapshot lands in its own fleet-harvestable slot
        self.spool_name = "engine"

        self._c_tokens = reg.counter(
            "generation_tokens_total",
            help="tokens sampled (prefill first-tokens + decode)")
        self._c_prefill_tokens = reg.counter(
            "generation_prefill_tokens_total",
            help="prompt tokens prefilled (bucket-padded tokens excluded)")
        self._c_requests = reg.counter(
            "generation_requests_total", help="generation requests")
        self._h_prefill = reg.histogram(
            "generation_prefill_seconds",
            help="per-sequence prefill latency (records = real tokens)")
        self._h_decode = reg.histogram(
            "generation_decode_seconds",
            help="per-step decode latency (records = active lanes)")
        self._c_ahead = reg.counter(
            "generation_rounds_ahead_total",
            help="decode rounds enqueued while the one before was "
                 "still uncollected (over dispatch_decode_calls_total: "
                 "the share of rounds whose host part ran beside the "
                 "device)")
        self._c_drains = {
            reason: reg.counter(
                f"generation_pipeline_drains_total_{reason}",
                help="times what was in flight was collected before "
                     f"the next round was enqueued: {reason}")
            for reason in DRAINS}
        self._c_sampled = {
            path: reg.counter(
                f"generation_sample_rounds_total_{path}",
                help="decode rounds and prefills whose sampler ran, by "
                     f"what the host's lanes asked of it: {path}")
            for path in SAMPLE_PATHS}
        self._c_pool_writes = {
            form: reg.counter(
                f"generation_prefill_pool_writes_total_{form}",
                help="prefills dispatched, by how their program writes "
                     f"the prompt's keys and values into the pool: {form}")
            for form in POOL_WRITES}
        reg.gauge("generation_cache_occupancy",
                  fn=self.cache.allocator.occupancy,
                  help="fraction of KV blocks held by live sequences")
        reg.gauge("generation_active_slots",
                  fn=lambda: len(self.scheduler.running()),
                  help="decode lanes occupied")
        reg.gauge("generation_queue_depth",
                  fn=lambda: len(self.scheduler.waiting),
                  help="requests waiting for a lane")
        reg.gauge("generation_preemptions",
                  fn=lambda: self.scheduler.n_preemptions,
                  help="sequences preempted under cache pressure")
        reg.gauge("generation_kv_row_bytes",
                  help="bytes a cached token holds over all layers "
                       "(logical: unquantized, a latent row's padding "
                       "left out)").set(self.cache.token_nbytes)
        reg.gauge("generation_kv_rows_per_token",
                  help="rows a cached token holds in a layer: 2 (a key "
                       "and a value) or 1 (a latent row)"
                  ).set(self.cache.rows)
        reg.gauge("generation_loop_steps",
                  help="times the model's layer stack runs for a token: "
                       "1, or a looped decoder's total_ut_steps"
                  ).set(getattr(model, "total_ut_steps", 1))
        reg.gauge("generation_kv_layer_slots",
                  help="pool slots a cached token holds rows in: one a "
                       "layer, or one a layer a step of a looped decoder"
                  ).set(self.cache.n_layers)
        if self.state_pool is not None:
            reg.gauge("generation_state_slots_in_use",
                      fn=lambda: len(self.scheduler.slotted()),
                      help="lanes whose recurrent state is live")
            reg.gauge("generation_state_bytes",
                      fn=lambda: self.state_pool.nbytes,
                      help="bytes of the recurrent-state pool (every "
                           "lane's, in use or not)")
            self._c_state_resets = reg.counter(
                "generation_state_resets_total",
                help="admissions: a prefill that replaced a slot's "
                     "recurrent state by the one after its prompt")
            self._c_state_rebuilds = reg.counter(
                "generation_state_rebuilds_total",
                help="of those, resumes of a preempted lane: a state "
                     "recomputed over prompt and generated tokens")
        #: the expert layers' counters, for a model that hands back
        #: counts (decoder.py), else None
        self._moe = ExpertCounters.of(model, reg)
        self._c_cow = (reg.counter(
            "prefix_cache_cow_copies_total",
            help="shared blocks copy-on-write un-shared before a "
                 "decode write (0 in normal operation — see "
                 "prefix_cache.py)") if self.prefix_caching else None)
        if self.speculation is not None:
            self._c_spec_proposed = reg.counter(
                "speculation_proposed_total",
                help="drafted tokens fed to the spec-verify step")
            self._c_spec_accepted = reg.counter(
                "speculation_accepted_total",
                help="drafted tokens accepted (argmax-matched); the "
                     "free bonus tokens are NOT counted here")
            self._c_spec_rounds = reg.counter(
                "speculation_rounds_total",
                help="per-lane verify rounds (one lane scored once)")
            reg.gauge(
                "speculation_acceptance_rate",
                fn=lambda: (self._c_spec_accepted.value
                            / self._c_spec_proposed.value
                            if self._c_spec_proposed.value else 0.0),
                help="accepted / proposed drafted tokens, lifetime")
            self._h_spec_accepted = reg.histogram(
                "speculation_accepted_length",
                help="accepted draft length per lane verify round "
                     "(one record per round; 0 = fully rejected)")
        #: KV-pool occupancy rides the memory-telemetry track too, so
        #: the timeline draws cache pressure under the request slices
        memory.register_provider("kv_pool", self._kv_pool_stats)
        #: goodput decomposition of the two hot loops.  Both fence
        #: naturally (prefill fetches the sampled token, decode fetches
        #: the token vector), so every iteration is fully accounted: a
        #: round's record holds the laps of one `step()` (`stage` and
        #: `dispatch` of the round it enqueues, `fetch`, `account` and
        #: `emit` of the round it collects), a prefill's its enqueue
        #: and, once the round's decode step is enqueued, its collection
        self._clock_prefill = step_clock("generation_prefill")
        self._clock_decode = step_clock("generation_decode")
        #: the stepping thread's CPU by phase, read and marked in one
        #: round of every few while a profiler session records (`step`)
        self._cpu = tracing.LoopClock("generation.", CPU_BUCKETS,
                                      CPU_PHASE, CPU_UNDER)
        #: speculative verify rounds get their own goodput track, so
        #: the Perfetto timeline shows them as distinct slices next to
        #: generation_decode (docs/observability.md)
        self._clock_spec = (step_clock("generation_spec_verify")
                            if self.speculation is not None else None)
        #: stall watchdog (opt-in via OrcaContext.watchdog_deadline_s):
        #: armed while the engine has work, beaten once per scheduling
        #: round — a wedged decode dispatch dumps a flight bundle
        self.watchdog = maybe_watchdog("generation")
        #: which compiled entry points have dispatched at least once —
        #: a cold dispatch's wall time lands in the goodput "compile"
        #: bucket instead of polluting warm decode latency
        self._goodput_warm: set = set()
        #: the compiled programs (steps.py), under the names the tests,
        #: the smoke and the benchmark's `decode_compile_count` read
        (self._prefill_jit, self._chunk_jit, self._decode_jit,
         self._spec_jit, self._copy_block_jit,
         self._restore_block_jit) = build_steps(
            model, block_size=block_size, n_head=kv_heads,
            quantized=self._quantized,
            paged=decode_attention == "paged", width=self._lanes.width,
            counted=self._moe is not None,
            stateful=self.state_pool is not None, tp=self._tp,
            prefill_variants=self.scheduler.expected_prefill_variants(),
            verify_variants=(self.speculation.expected_verify_variants()
                             if self.speculation is not None else None))

    def _kv_pool_stats(self):
        alloc = self.cache.allocator
        used = alloc.capacity - alloc.available()
        nb = self.cache.num_blocks
        # logical = bytes the cached tokens represent dequantized at
        # the cache dtype; physical = bytes actually resident (int8
        # values + scale vectors).  Both ride the memory_kv_pool_*
        # gauge family so the quantization residency win is a live
        # number, not a datasheet claim (docs/observability.md).
        logical = self.cache.logical_nbytes
        physical = self.cache.physical_nbytes
        # shared = blocks with >1 live reference (prefix-cache tree +
        # sequences); exclusive = singly-owned.  The split is the live
        # residency win of prompt reuse: shared bytes serve N readers
        # for one block's worth of HBM (docs/observability.md).
        n_shared = alloc.n_shared()
        return {
            "blocks_used": used,
            "blocks_capacity": alloc.capacity,
            "blocks_shared": n_shared,
            "blocks_cached": (self.prefix_cache.n_blocks
                              if self.prefix_cache is not None else 0),
            "pool_bytes": physical,
            "used_bytes": physical * used // nb,
            "shared_bytes": physical * n_shared // nb,
            "exclusive_bytes": physical * (used - n_shared) // nb,
            "pool_bytes_logical": logical,
            "pool_bytes_physical": physical,
            "used_bytes_logical": logical * used // nb,
            "used_bytes_physical": physical * used // nb,
        }

    def recurrent_state(self, slot: int):
        """What the state layers hold for lane `slot` as the device
        has it once everything enqueued has run (a fetch): {"ssm": one
        [*state shape] array a state layer (none where the layers have
        no scan), "conv": one [rows, channels] a layer}, or None for a
        model without state layers.
        A finished lane's stays until its slot is admitted again: the
        state after its prompt and all but the last of its tokens."""
        if self.state_pool is None:
            return None
        pool = self._lanes.state["recurrent"]
        return {"ssm": [np.asarray(h[slot]) for h in pool["ssm"]],
                "conv": [np.asarray(c[:, slot]) for c in pool["conv"]]}

    def _store_kv_state(self, kv, kv_scale) -> None:
        self.cache.kv = kv
        self._kv_scale = kv_scale
        if self._quantized:
            self.cache.kv_scale = kv_scale

    @property
    def decode_compile_count(self) -> int:
        """Compiled variants of the decode step (1 after warmup and
        forever after — the zero-recompile guarantee; -1 when the jit
        cache API is unavailable)."""
        size = getattr(self._decode_jit, "_cache_size", None)
        return size() if size is not None else -1

    @property
    def spec_verify_compile_count(self) -> int:
        """Compiled variants of the speculative verify step — one per
        pow2 k-bucket, all warmed in `warmup()`, fixed forever after
        (the speculation half of the zero-recompile guarantee; 0 with
        speculation off, -1 when the jit cache API is unavailable)."""
        if self.speculation is None:
            return 0
        size = getattr(self._spec_jit, "_cache_size", None)
        return size() if size is not None else -1

    def warmup(self) -> None:
        """Compile the decode step and every prefill bucket — of the
        chunk-prefill program when prefix caching / chunked prefill is
        on, of the legacy whole-prompt program otherwise — on dummy
        inputs (all writes land in the null block, no lane is active;
        the key is put back as it was and every row resent after)."""
        with self._lock:
            MB = self.scheduler.max_blocks_per_seq
            one = jnp.zeros(1, jnp.float32)
            onek = jnp.zeros(1, jnp.int32)
            lanes = self._lanes
            key = lanes.key()
            chunk_buckets = [
                b for b in self.scheduler.prefill_buckets
                if not self.chunked_prefill or b <= self._chunk_cap]
            for b in self.scheduler.prefill_buckets:
                if self._use_chunks:
                    if b not in chunk_buckets:
                        continue
                    kv, scl, _, _, lanes.state["rng"], *_ = \
                        self._chunk_jit(
                            self.params, self.cache.kv, self._kv_scale,
                            jnp.zeros((1, b), jnp.int32), jnp.int32(0),
                            jnp.int32(1), jnp.zeros(MB, jnp.int32),
                            one, onek, lanes.state["rng"])
                else:
                    kv, scl, _, _, lanes.state, *_ = self._prefill_jit(
                        self.params, self.cache.kv, self._kv_scale,
                        lanes.state, lanes.warm_request(b))
                self._store_kv_state(kv, scl)
            if self.prefix_cache is not None:
                # the COW copy program (src=dst=null block: harmless)
                kv, scl = self._copy_block_jit(
                    self.cache.kv, self._kv_scale, jnp.int32(0),
                    jnp.int32(0))
                self._store_kv_state(kv, scl)
                self._goodput_warm.add("copy")
            if self.host_tier is not None \
                    and self._restore_block_jit is not None:
                # the host-restore program (dst=null block: harmless)
                slab = self.cache.slab_shape
                rows = jnp.zeros(slab, self.cache.kv.dtype)
                srows = (jnp.zeros(slab[:3], jnp.float32)
                         if self._quantized
                         else jnp.zeros((1,), jnp.float32))
                kv, scl = self._restore_block_jit(
                    self.cache.kv, self._kv_scale, jnp.int32(0),
                    rows, srows)
                self._store_kv_state(kv, scl)
                self._goodput_warm.add("host_restore")
            S = self.max_slots
            kv, scl, _, _, lanes.state, *_ = self._decode_jit(
                self.params, self.cache.kv, self._kv_scale,
                lanes.state, lanes.idle_patch())
            self._store_kv_state(kv, scl)
            lanes.restore(key)
            if self.speculation is not None:
                # every verify k-bucket compiles here too (inactive
                # grid: all writes land in the null block)
                for b in self.speculation.buckets:
                    kv, scl, *_ = self._spec_jit(
                        self.params, self.cache.kv, self._kv_scale,
                        jnp.zeros((S, 1 + b), jnp.int32),
                        jnp.zeros((S, MB), jnp.int32),
                        jnp.zeros(S, jnp.int32),
                        jnp.zeros(S, jnp.int32), jnp.zeros(S, bool))
                    self._store_kv_state(kv, scl)
                    self._goodput_warm.add(("spec", b))
            # everything above compiled here: live traffic is warm
            self._goodput_warm.add("decode")
            if self._use_chunks:
                self._goodput_warm.update(
                    ("chunk_prefill", b) for b in chunk_buckets)
            else:
                self._goodput_warm.update(
                    ("prefill", b)
                    for b in self.scheduler.prefill_buckets)

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------

    def retry_after_s(self) -> float:
        """Comeback hint attached to shed (503) responses: the queue's
        estimated drain time from the measured decode cadence — depth
        x mean decode-step wall — clamped to [0.05s, 10s] (0.5s before
        any decode has been measured)."""
        depth = len(self.scheduler.waiting)
        if self._h_decode.calls:
            mean = self._h_decode.total / self._h_decode.calls
            return float(min(10.0, max(0.05, (depth + 1) * mean)))
        return 0.5

    # queue-bound knobs live on the AdmissionCore (the single door
    # policy); these properties keep `engine.max_queue = N` working
    @property
    def max_queue(self) -> Optional[int]:
        return self.admission.max_queue

    @max_queue.setter
    def max_queue(self, value: Optional[int]) -> None:
        self.admission.max_queue = value

    @property
    def slo_shed_min_queue(self) -> int:
        return self.admission.slo_shed_min_queue

    @slo_shed_min_queue.setter
    def slo_shed_min_queue(self, value: int) -> None:
        self.admission.slo_shed_min_queue = int(value)

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0,
               eos_id: Optional[int] = None,
               stream_timeout: float = 120.0,
               request_id: Optional[str] = None,
               tenant: Optional[str] = None,
               request_class: str = "interactive",
               blame_seed: Optional[Dict[str, float]] = None
               ) -> GenerationStream:
        """Queue one request; returns its token stream.  Raises up
        front when the request can never run: ValueError for malformed
        prompts, `RequestTooLarge` (a ValueError; HTTP 413) when the
        prompt + max_new_tokens exceed max_context or the whole block
        pool, `QueueFull` (HTTP 503) / `TenantQuotaExceeded` (HTTP
        429) from the AdmissionCore's queue/SLO/quota gates.

        `request_id` keys the per-request lifecycle log (request_log);
        one is generated when absent and is readable from the returned
        stream's `.request_id`.  `tenant` attributes the request to a
        quota bucket (`OrcaContext.tenant_quotas`); `request_class`
        ("interactive" | "batch" | "shadow") sets its scheduler
        priority — lower classes admit first and preempt last.
        `blame_seed` ({phase: seconds}) records wait the request
        already served BEFORE this submit — a quota-throttled retry
        loop ("quota_throttle") or a replica-death requeue
        ("requeue") — so the blame ledger's e2e decomposition covers
        the client's whole wait, not just this engine's share."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < self.model.vocab for t in prompt):
            raise ValueError("prompt token out of vocab range")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_context:
            raise RequestTooLarge(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_context "
                f"{self.max_context}")
        if self.cache.blocks_for(total) > self.cache.allocator.capacity:
            raise RequestTooLarge(
                f"request needs {self.cache.blocks_for(total)} KV "
                f"blocks, pool holds {self.cache.allocator.capacity}")
        priority = self.admission.admit(
            len(self.scheduler.waiting), tenant=tenant,
            request_class=request_class)
        rid = request_log.start(request_id, prompt_len=len(prompt),
                                max_new_tokens=int(max_new_tokens),
                                model=self.model_label, tenant=tenant,
                                request_class=request_class,
                                blame_seed=blame_seed)
        seq = Sequence(prompt, max_new_tokens=max_new_tokens,
                       temperature=temperature, top_k=top_k,
                       eos_id=eos_id, request_id=rid,
                       priority=priority)
        seq.stream = GenerationStream(seq, timeout=stream_timeout)
        with self._lock:
            self.scheduler.submit(seq)
            self._c_requests.inc()
        self._wake.set()
        return seq.stream

    def generate(self, prompt, **kw) -> List[int]:
        """Blocking one-shot convenience: submit and drain.  Drives the
        loop inline when no background thread is running."""
        stream = self.submit(prompt, **kw)
        if self._thread is None:
            self.run_until_idle()
        return stream.tokens()

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------

    def _finish(self, seq: Sequence, reason: str) -> None:
        if (self.prefix_cache is not None and seq.slot is not None
                and reason in ("length", "eos")):
            # commit the GENERATED suffix too (ROADMAP item 1
            # remainder): decode wrote KV for every context token
            # except the newest sampled one, so the fully-covered
            # whole blocks of prompt+generated are publishable — a
            # multi-turn conversation's next request hits on this
            # turn's output, not just its prompt
            tokens = (seq.prompt + seq.generated)[:seq.context_len - 1]
            if len(tokens) >= self.cache.block_size:
                seq.block_table = self.prefix_cache.commit(
                    tokens, seq.block_table)
        self.scheduler.release(seq, reason)
        if seq.stream is not None:
            seq.stream._close()

    def _emit(self, seq: Sequence, token: int) -> None:
        seq.generated.append(int(token))
        self._c_tokens.inc()
        request_log.token(seq.request_id)
        if seq.stream is not None:
            seq.stream._put(token)
        reason = seq.should_finish()
        if reason:
            self._finish(seq, reason)

    def _sampled(self, drawing, sorting) -> None:
        """Count the sampler path of a program just enqueued, as the
        host knows its lanes: some of them with a temperature
        (`drawing`), some of those with `top_k` too (`sorting`).  The
        device decides from its own rows (sampling.py) and can be a
        round ahead of this: a lane the step itself stopped, its last
        token not collected yet, still counts here."""
        path = "argmax" if not drawing else "sort" if sorting else "draw"
        self._c_sampled[path].inc()

    def _end_step(self, rec) -> None:
        """Close a step record: the goodput commit (counters, the
        timeline ring, the memory sampler) is accounting like the rest,
        and shows as such in a profiler trace."""
        with tracing.phase("generation.account"):
            rec.end()

    def _lap(self, t0: float) -> float:
        """Seconds the dispatch now collected took the engine: since it
        was enqueued at `t0`, or since the collection before it where
        that came later — a round enqueued ahead is measured between
        consecutive collections, which is the round's length (what
        `retry_after_s()` reads) and lets the requests' phase ledgers
        add up."""
        t = now()
        dur = t - max(t0, self._collected_at)
        self._collected_at = t
        return dur

    def _account_prefill(self, seq: Sequence, family: str, bucket: int,
                         tokens: int, t0: float, moe,
                         start: Optional[int] = None) -> None:
        """What a whole-prompt prefill and a chunk account alike, once
        the sampled token is fetched: `tokens` real tokens through
        `family`'s `bucket` program, dispatched at `t0`; `start`: a
        chunk's first position (None: a whole prompt)."""
        if moe:
            self._moe.add(moe[0], "prefill")
        self._goodput_warm.add((family, bucket))
        dur = self._lap(t0)
        self._h_prefill.record(dur, tokens)
        profiling.record_work(
            family, dur, tokens=tokens,
            flops=(self._flops.prefill(tokens, ctx_start=start or 0)
                   if self._flops else 0.0))
        self._c_prefill_tokens.inc(tokens)
        request_log.attribute(seq.request_id, "prefill_compute", dur)
        where = {} if start is None else {"start": start}
        request_log.event(seq.request_id, "prefill", bucket=bucket,
                          tokens=tokens, **where, dur_s=round(dur, 6),
                          resumed=seq.n_preempted > 0)

    @contextmanager
    def _guard(self):
        """Around a dispatch that takes the lane state: a failure
        leaves the device's rows unknown, so all of them are sent
        again (`LaneState.guard`) — whole, which the host can only
        build once it is exact: what is in flight is collected
        first."""
        try:
            with self._lanes.guard():
                yield
        except BaseException as e:
            self._abandon("evict" if isinstance(e, PoisonedRequestError)
                          else "error")
            raise

    def _prefill_seq(self, seq: Sequence) -> None:
        """Enqueue `seq`'s whole-prompt prefill.  Its first token is
        collected once the round's decode step is enqueued too
        (`_collect_prefill`)."""
        rec = self._clock_prefill.begin(force_fence=True)
        lanes = self._lanes
        with tracing.phase("generation.prefill"), self._guard():
            with rec.phase("generation.stage", "host_input"):
                ctx = seq.prompt + seq.generated
                L = len(ctx)
                bucket = self.scheduler.bucket_for(L)
                # one upload: the prompt and the lane's row, which the
                # program leaves in the resident state itself
                request, head = lanes.prefill_request(seq, ctx, bucket)
            t0 = now()
            rec.cold = ("prefill", bucket) not in self._goodput_warm
            with rec.phase("generation.dispatch"):
                kv, scl, nxt, _, lanes.state, *moe = self._prefill_jit(
                    self.params, self.cache.kv, self._kv_scale,
                    lanes.state, request)
                self._store_kv_state(kv, scl)
                self._c_pool_writes["block"].inc()
                self._sampled(seq.temperature > 0, seq.top_k > 0)
                if self.state_pool is not None:
                    self._c_state_resets.inc()
                    if seq.n_preempted:
                        self._c_state_rebuilds.inc()
                self._enqueued(_Prefill(seq, head, bucket, L, t0, nxt,
                                        moe, rec))

    def _enqueued(self, item) -> None:
        """`item` is in flight: its results start for the host now,
        its sequences are that many tokens behind the device."""
        for out in (item.nxt, *item.moe):
            out.copy_to_host_async()
        for seq in ([item.seq] if isinstance(item, _Prefill)
                    else item.lanes.values()):
            seq.in_flight += 1
        self._in_flight.append(item)

    def _collect_prefill(self, item: _Prefill) -> None:
        seq, head, bucket, L, t0, nxt, moe, rec = item
        # the span a prefill's enqueue has: what the host does for a
        # prefill, here or there, is found under the one name
        with tracing.phase("generation.prefill"):
            rec.resume()
            with rec.phase("generation.fetch", "device_compute"):
                nxt = int(nxt)            # token fetch = device fence
                moe = jax.device_get(moe)
            with rec.phase("generation.account"):
                self._lanes.landed(seq.slot, head, nxt)
                seq.in_flight -= 1
                self._account_prefill(seq, "prefill", bucket, L, t0, moe)
            with rec.phase("generation.emit"):
                self._emit(seq, nxt)
            self._end_step(rec)

    # ------------------------------------------------------------------
    # chunked / prefix-cached prefill (the chunk-step path)
    # ------------------------------------------------------------------

    def _prefill_round(self) -> Tuple[bool, int]:
        """Spend this round's prefill token budget on the lanes still
        prefilling (admit order).  Non-chunked mode covers a lane's
        whole remaining tail in one chunk; chunked mode caps chunks at
        `_chunk_cap` tokens so a long prompt yields to the decode step
        between chunks.  The head chunk always proceeds (no
        starvation), budget charges at bucket granularity like
        admission always has.  Returns (did work, leftover budget) —
        the leftover is what the speculation round may spend on verify
        tokens (same per-round account)."""
        did = False
        budget = self.scheduler.prefill_token_budget
        first = True
        for seq in self.scheduler.prefilling():
            while seq.status == "prefilling":
                remaining = seq.context_len - seq.prefill_pos
                cap = (min(remaining, self._chunk_cap)
                       if self.chunked_prefill else remaining)
                bucket = self.scheduler.bucket_for(cap)
                if not first and bucket > budget:
                    return did, 0
                self._prefill_chunk(seq, bucket)
                did = True
                first = False
                budget -= bucket
                if budget <= 0 and seq.status == "prefilling":
                    return did, 0
        return did, max(0, budget)

    def _prefill_chunk(self, seq: Sequence, bucket: int) -> None:
        """Run one chunk-prefill step: write KV for the next
        `min(bucket, remaining)` context tokens; the final chunk
        commits the prompt's full blocks to the prefix cache, samples
        the first new token and flips the lane to running."""
        rec = self._clock_prefill.begin(force_fence=True)
        with tracing.phase("generation.prefill"):
            with rec.phase("generation.stage", "host_input"):
                ctx = seq.prompt + seq.generated
                L = seq.context_len
                start = seq.prefill_pos
                real = min(bucket, L - start)
                MB = self.scheduler.max_blocks_per_seq
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :real] = ctx[start:start + real]
                table = np.zeros(MB, np.int32)
                table[:len(seq.block_table)] = seq.block_table
            t0 = now()
            rec.cold = ("chunk_prefill", bucket) \
                not in self._goodput_warm
            with rec.phase("generation.dispatch"), self._guard():
                state = self._lanes.state
                kv, scl, nxt, _, state["rng"], *moe = self._chunk_jit(
                    self.params, self.cache.kv, self._kv_scale,
                    jnp.asarray(tokens), jnp.int32(start),
                    jnp.int32(real), jnp.asarray(table),
                    jnp.full(1, seq.temperature, jnp.float32),
                    jnp.full(1, seq.top_k, jnp.int32), state["rng"])
                self._store_kv_state(kv, scl)
                self._c_pool_writes["row"].inc()
                self._sampled(seq.temperature > 0, seq.top_k > 0)
            with rec.phase("generation.fetch", "device_compute"):
                nxt = int(nxt)            # token fetch = device fence
                moe = jax.device_get(moe)
            with rec.phase("generation.account"):
                self._account_prefill(seq, "chunk_prefill", bucket, real,
                                      t0, moe, start=start)
                seq.prefill_pos = start + real
            with rec.phase("generation.emit"):
                if seq.prefill_pos >= L:
                    if self.prefix_cache is not None:
                        # the prompt's KV is now fully written: publish
                        # its full blocks for reuse (deduping against
                        # identical prefixes committed since this
                        # lane's lookup)
                        seq.block_table = self.prefix_cache.commit(
                            seq.prompt, seq.block_table)
                    seq.status = "running"
                    self.scheduler.touched.add(seq.slot)
                    self._emit(seq, nxt)
            self._end_step(rec)

    # ------------------------------------------------------------------
    # host-tier restore (the device half — prefix_cache.restore calls
    # back through `restore_writer`)
    # ------------------------------------------------------------------

    def _host_restore_write(self, block: int, entry) -> bool:
        """Land one host-tier entry's KV rows in pool block `block`.
        Uses the slab staged by `_stage_host_restores` when the race
        was won (the device_put already overlapped the previous decode
        round), falling back to a synchronous transfer otherwise.
        Returns False on any mismatch — the caller recomputes."""
        if self._restore_block_jit is None:
            return False
        t0 = now()
        rows = entry.staged_kv
        if rows is None:
            rows = jnp.asarray(entry.kv)
        if self._quantized:
            srows = entry.staged_scale
            if srows is None:
                if entry.scale is None:
                    return False
                srows = jnp.asarray(entry.scale)
        else:
            srows = jnp.zeros((1,), jnp.float32)
        kv, scl = self._restore_block_jit(
            self.cache.kv, self._kv_scale, jnp.int32(block), rows,
            srows)
        self._store_kv_state(kv, scl)
        entry.staged_kv = None
        entry.staged_scale = None
        dur = now() - t0
        record_dma("host_restore", dur, entry.nbytes,
                   self.spool_name)
        profiling.record_work("host_restore", dur)
        # blame attribution: the scheduler threads the beneficiary's
        # request id through the prefix cache while restore runs
        request_log.attribute(
            getattr(self.prefix_cache, "restoring_for", None),
            "host_restore", dur)
        return True

    def _stage_host_restores(self) -> None:
        """Double-buffer half of the host tier: start the async
        `device_put` of host-resident prefix extensions for the
        waiting heads BEFORE admission, so the host→device DMA hides
        inside the decode dispatch already in flight.  A staged entry
        that loses the race to an eviction is refetched as a miss
        (lossless recompute)."""
        tier = self.host_tier
        if tier is None or not self.scheduler.waiting \
                or self.prefix_cache is None:
            return
        device = None
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        if getattr(leaf, "committed", False):
            device = next(iter(leaf.devices()))
        for seq in list(self.scheduler.waiting)[:4]:
            ctx = seq.prompt + seq.generated
            try:
                tier.stage_prefix(ctx, self.prefix_cache.peek(ctx),
                                  device=device)
            except Exception:
                return   # advisory: staging must never block a round

    def _apply_cow(self) -> None:
        """Materialize the scheduler's copy-on-write decisions: copy
        each shared source block into the fresh exclusive block the
        table now points at (the device-side half of
        `SlotScheduler.resolve_write_conflicts`)."""
        for _seq, _idx, src, dst in \
                self.scheduler.resolve_write_conflicts():
            t0 = now()
            kv, scl = self._copy_block_jit(
                self.cache.kv, self._kv_scale, jnp.int32(src),
                jnp.int32(dst))
            self._store_kv_state(kv, scl)
            profiling.record_work("copy_block", now() - t0)
            if self._c_cow is not None:
                self._c_cow.inc()

    def _spec_round(self, budget: int) -> set:
        """One speculative-decoding pass over the running lanes: draft
        (greedy lanes, cooldown elapsed, n-gram match found), grow each
        drafted lane's block table to cover its draft, score all
        drafted lanes in ONE spec-verify dispatch, emit each lane's
        accepted prefix plus the bonus token, and rewind (rollback the
        over-allocated blocks).  Verify tokens charge `budget` (the
        prefill round's leftover token budget) at bucket granularity.

        Every OTHER greedy running lane rides the same dispatch as a
        length-1 row — its position-0 argmax IS its decode token (the
        block for that write exists: `ensure_decode_capacity` ran), so
        a verify round REPLACES the decode round for greedy lanes
        instead of adding a second dispatch to it.  That 1:1
        substitution is what bounds the adversarial case: a round
        whose every draft gets rejected costs one slightly wider
        dispatch, not two dispatches (the bench's <= 1.1x gate).

        Returns the lanes that already advanced this round — `step()`
        excludes them from the decode step (sampling lanes never ride:
        verify is argmax-only)."""
        done: set = set()
        spec = self.speculation
        drafted = []                  # (seq, state, draft)
        for seq in self.scheduler.running():
            if seq.temperature > 0:
                continue              # greedy lanes only
            st = spec.state(seq)
            if st.cooldown > 0:
                st.cooldown -= 1
                continue
            draft = spec.draft_for(seq)
            if not draft:
                continue
            bucket = spec.bucket_for(len(draft))
            if 1 + bucket > budget:
                continue              # out of this round's budget
            if not self.scheduler.grow_for_speculation(
                    seq, seq.context_len - 1 + len(draft)):
                continue              # pool too tight: decode normally
            budget -= 1 + bucket
            drafted.append((seq, st, draft))
        if not drafted:
            return done
        in_grid = {seq for seq, _st, _d in drafted}
        riders = [seq for seq in self.scheduler.running()
                  if seq.temperature <= 0 and seq not in in_grid]
        rec = self._clock_spec.begin(force_fence=True)
        with tracing.phase("generation.spec_verify"):
            with rec.phase("generation.stage", "host_input"):
                S = self.max_slots
                MB = self.scheduler.max_blocks_per_seq
                W = 1 + spec.bucket_for(
                    max(len(d) for _, _, d in drafted))
                tokens = np.zeros((S, W), np.int32)
                tables = np.zeros((S, MB), np.int32)
                start = np.zeros(S, np.int32)
                length = np.zeros(S, np.int32)
                active = np.zeros(S, bool)
                for seq, _st, draft in drafted:
                    i = seq.slot
                    tokens[i, 0] = seq.generated[-1] if seq.generated \
                        else seq.prompt[-1]
                    tokens[i, 1:1 + len(draft)] = draft
                    tables[i, :len(seq.block_table)] = seq.block_table
                    start[i] = seq.context_len - 1
                    length[i] = 1 + len(draft)
                    active[i] = True
                for seq in riders:    # length-1 rows: draft-free decode
                    i = seq.slot
                    tokens[i, 0] = seq.generated[-1] if seq.generated \
                        else seq.prompt[-1]
                    tables[i, :len(seq.block_table)] = seq.block_table
                    start[i] = seq.context_len - 1
                    length[i] = 1
                    active[i] = True
            try:
                # fault site: an injected raise costs exactly one
                # round's speculation — nothing was emitted or written
                # yet, so the drafted lanes just rejoin the normal
                # decode step (after rewinding the blocks grown above);
                # nothing is evicted
                fault_point("generation.spec_verify",
                            request_ids=[s.request_id
                                         for s, _, _ in drafted]
                            + [s.request_id for s in riders])
            except FaultInjected:
                for seq, _st, _draft in drafted:
                    self.scheduler.rollback_speculation(seq)
                self._end_step(rec)
                return done
            t0 = now()
            rec.cold = ("spec", W - 1) not in self._goodput_warm
            with rec.phase("generation.dispatch"):
                kv, scl, greedy, *moe = self._spec_jit(
                    self.params, self.cache.kv, self._kv_scale,
                    jnp.asarray(tokens), jnp.asarray(tables),
                    jnp.asarray(start), jnp.asarray(length),
                    jnp.asarray(active))
                self._store_kv_state(kv, scl)
            with rec.phase("generation.fetch", "device_compute"):
                greedy = np.asarray(greedy)  # token fetch = device fence
                moe = jax.device_get(moe)
            # accounting for every lane, then emission for every lane:
            # each request's log keeps its order (decode round, token,
            # finish), and a trace shows two spans, not two a lane
            accepted = []
            with rec.phase("generation.account"):
                if moe:
                    self._moe.add(moe[0], "decode")
                self._goodput_warm.add(("spec", W - 1))
                dur = now() - t0
                self._h_decode.record(dur, len(drafted) + len(riders))
                n_rows = len(drafted) + len(riders)
                ctx_mean = (float(np.sum(start[active])) / n_rows
                            if n_rows else 0.0)
                profiling.record_work(
                    "spec_verify", dur,
                    tokens=int(np.sum(length[active])),
                    flops=(self._flops.verify(n_rows, W, ctx_mean)
                           if self._flops else 0.0))
                for seq in riders:
                    # a rider's row is an ordinary decode in verify
                    # clothing: it charges no speculation budget, ticks
                    # no speculation counters, and needs no rollback —
                    # position 0's argmax is the round's one token
                    request_log.decode_round(seq.request_id)
                    request_log.attribute(seq.request_id,
                                          "decode_active", dur)
                    done.add(seq)
                for seq, st, draft in drafted:
                    i = seq.slot
                    m = 0
                    while m < len(draft) and draft[m] == greedy[i, m]:
                        m += 1
                    accepted.append(m)
                    st.record(len(draft), m)
                    self._c_spec_rounds.inc()
                    self._c_spec_proposed.inc(len(draft))
                    self._c_spec_accepted.inc(m)
                    self._h_spec_accepted.record(m)
                    n = st.rounds
                    if n & (n - 1) == 0:  # pow2-sampled, like decode
                        request_log.event(seq.request_id,
                                          "spec_propose", round=n,
                                          proposed=len(draft))
                        request_log.event(seq.request_id, "spec_accept",
                                          round=n, accepted=m)
                    request_log.decode_round(seq.request_id, spec=True)
                    # blame split of the verify round's wall: the
                    # accepted prefix + bonus token are useful decode
                    # ((m+1) of the (k+1) scored positions); the
                    # rejected remainder is speculation overhead.  The
                    # two shares sum to `dur`, so ledger additivity
                    # survives any acceptance rate.
                    k1 = 1 + len(draft)
                    request_log.attribute(seq.request_id,
                                          "decode_active",
                                          dur * (m + 1) / k1)
                    request_log.attribute(seq.request_id,
                                          "spec_verify_overhead",
                                          dur * (len(draft) - m) / k1)
                    done.add(seq)
            with rec.phase("generation.emit"):
                # advanced outside the decode step: their rows go up
                # again (sync() keeps them out of this round's decode)
                self.scheduler.touched.update(s.slot for s in done)
                for seq in riders:
                    self._emit(seq, int(greedy[seq.slot, 0]))
                for (seq, _st, _draft), m in zip(drafted, accepted):
                    # emit the accepted prefix + the bonus token —
                    # exactly the tokens greedy single-step decode
                    # would have produced — stopping at eos/length like
                    # the decode loop would
                    for j in range(m + 1):
                        self._emit(seq, int(greedy[seq.slot, j]))
                        if seq.status == "finished":
                            break
                    if seq.status != "finished":
                        # the free-list rewind: drop table blocks past
                        # the next write position (rejected slots
                        # decref here)
                        self.scheduler.rollback_speculation(seq)
            self._end_step(rec)
        return done

    def _decode_all(self, lanes: Dict[int, Sequence], skip) -> bool:
        """The round's decode step enqueued for `lanes` (none: no
        step), then everything that was in flight before it collected:
        the decode round the previous `step()` enqueued, this round's
        prefills.  `skip`: the lanes that already advanced via verify.
        Whether anything ran or was collected."""
        if not lanes and not self._in_flight:
            return False
        rec = self._clock_decode.begin(force_fence=True)
        if not lanes:
            self._collect(rec)
            self._end_step(rec)
            return True
        # the two counts ride in the span's name: a reader of the trace
        # keeps an event's name and drops its other fields
        with tracing.phase(
                f"generation.decode[l={len(lanes)},"
                f"w={min(len(self.scheduler.waiting), 99)}]"):
            with self._guard():
                with rec.phase("generation.stage", "host_input"):
                    # the rows the scheduler changed since the last
                    # round, as one upload — or none (lane_state.py)
                    patch = self._lanes.sync(skip)
                # fault-injection site: "poison_request" raises
                # PoisonedRequestError BEFORE dispatch (no KV change
                # happened; the guard collects what is in flight and
                # has every lane's row resent, so surviving lanes
                # replay this round untouched); "stall" wedges the
                # loop for the watchdog
                fault_point("generation.decode",
                            request_ids=[s.request_id
                                         for s in lanes.values()])
                t0 = now()
                rec.cold = "decode" not in self._goodput_warm
                with rec.phase("generation.dispatch"):
                    kv, scl, nxt, _, self._lanes.state, *moe = \
                        self._decode_jit(
                            self.params, self.cache.kv, self._kv_scale,
                            self._lanes.state, patch)
                    self._store_kv_state(kv, scl)
                    self._sampled(self.scheduler.n_drawing,
                                  self.scheduler.n_sorting)
                    # the oldest in flight: the previous round, if any
                    if self._in_flight \
                            and isinstance(self._in_flight[0], _Decode):
                        self._c_ahead.inc()
                    self._enqueued(_Decode(lanes, t0, nxt, moe))
            self._collect(rec, keep=1)
            self._end_step(rec)
        return True

    def _collect(self, rec=None, keep: int = 0) -> None:
        """Fetch, account and emit what is in flight, in dispatch
        order, but for the `keep` newest dispatches.  `rec`: the
        goodput record a decode round's laps go to (None: one of its
        own)."""
        while len(self._in_flight) > keep:
            item = self._in_flight.popleft()
            if isinstance(item, _Prefill):
                self._collect_prefill(item)
                if rec is not None:
                    rec.resume()      # that time was the prefill's
            elif rec is not None:
                self._collect_decode(item, rec)
            else:
                own = self._clock_decode.begin(force_fence=True)
                self._collect_decode(item, own)
                self._end_step(own)

    def _collect_decode(self, item: _Decode, rec) -> None:
        lanes, t0, nxt, moe = item
        with rec.phase("generation.fetch", "device_compute"):
            nxt = np.asarray(nxt)         # token fetch = device fence
            moe = jax.device_get(moe)
        # accounting for every lane, then emission for every lane:
        # each request's log keeps its order (decode round, token,
        # finish), and a trace shows two spans, not two a lane
        with rec.phase("generation.account"):
            if moe:
                self._moe.add(moe[0], "decode")
            self._goodput_warm.add("decode")
            dur = self._lap(t0)
            ctx_sum = self._lanes.ctx_sum()
            live = self._lanes.advance(nxt)
            for seq in lanes.values():
                seq.in_flight -= 1
            # a lane the step had stopped the round before (its last
            # token or its `eos`, collected since) computed nothing
            lanes = {i: seq for i, seq in lanes.items() if live[i]}
            self._h_decode.record(dur, len(lanes))
            ctx_mean = ctx_sum / len(lanes) if lanes else 0.0
            profiling.record_work(
                "decode", dur, tokens=len(lanes),
                flops=(self._flops.decode(len(lanes), ctx_mean)
                       if self._flops else 0.0))
            for seq in lanes.values():
                request_log.decode_round(seq.request_id)
                # per-request wall-clock experience: every riding
                # lane waited out the whole round
                request_log.attribute(seq.request_id,
                                      "decode_active", dur)
        with rec.phase("generation.emit"):
            for i, seq in lanes.items():
                self._emit(seq, nxt[i])

    def _drain(self, reason: str) -> bool:
        """Collect everything in flight, because of `reason` (one of
        `DRAINS`): the host is exact afterwards — every sequence's
        tokens, every finished lane released.  Whether there was
        anything."""
        if not self._in_flight:
            return False
        self._c_drains[reason].inc()
        self._collect()
        return True

    def _abandon(self, reason: str) -> None:
        """`_drain` for a step that failed or an engine that stops:
        what cannot be collected went down with it."""
        try:
            self._drain(reason)
        finally:
            self._in_flight.clear()
            for seq in self.scheduler.slotted():
                seq.in_flight = 0

    def _evict_poisoned(self, e: PoisonedRequestError) -> None:
        """Graceful degradation: a step failure attributable to ONE
        request evicts exactly that request — tagged 503 in the
        lifecycle log, flight bundle dumped — and the engine keeps
        serving everyone else.  Caller holds the lock."""
        victim = None
        for seq in self.scheduler.running():
            if seq.request_id == e.request_id:
                victim = seq
                break
        get_registry().counter(
            "resilience_evictions_total",
            help="requests evicted individually after an attributable "
                 "step failure (engine kept serving)").inc()
        log_event("generation_request_evicted",
                  request_id=e.request_id, error=str(e))
        request_log.event(e.request_id, "evicted", code=503,
                          error=str(e))
        flight_recorder.dump(
            "generation_request_evicted",
            extra={"request_id": e.request_id, "error": str(e)})
        if victim is not None:
            self._finish(victim, f"error: evicted ({e})")

    def _exact_round(self) -> Optional[str]:
        """Why the round about to run needs the host exact from its
        first line (the `DRAINS` reason), or None.  A host-tier restore
        and a prefix match read the waiting prompts' whole context, a
        chunk its lane's progress, the copy-on-write guard the block a
        lane writes next, a draft the lane's own tokens: an engine
        with one of them on collects every round before the next."""
        if self.host_tier is not None and self.scheduler.waiting:
            return "host_restore"
        if self._use_chunks:
            chunks = self.scheduler.waiting or self.scheduler.prefilling()
            return ("chunk" if chunks or self.prefix_cache is None
                    else "cow")
        if self.speculation is not None:
            return "verify"
        return None

    def step(self) -> bool:
        """One scheduling round: admit → prefill (whole prompts
        enqueued on the legacy path; budget-bounded chunks with prefix
        reuse on the chunk path) → grow/preempt for decode capacity
        (+ copy-on-write un-sharing) → one decode step enqueued → and
        only then the collection of what was in flight before it, in
        dispatch order (the decode round the PREVIOUS `step()`
        enqueued, this round's prefills' first tokens): fetch,
        account, emit, finish — while the device works on this round.
        Returns whether any device work ran or was collected.

        So the tokens of the decode round a `step()` enqueues are
        visible after the next `step()`, or after a drain: whatever
        needs the host exact collects what is in flight first
        (`_drain`), and `run_until_idle()` and `generate()` return
        with nothing in flight.  Per request the order of emissions is
        what it always was: the first token, then one a round.

        While a profiler session records, in one round of every few
        (`LoopClock.EVERY`) the calling thread's CPU clock is read
        where the round's phases change hands, and the sums leave as
        one mark after it (`CPU_PHASE`)."""
        recording = self._cpu.arm()
        try:
            return self._round()
        finally:
            if recording:
                self._cpu.mark("cpu.loop")

    def _round(self) -> bool:
        with self._lock, tracing.phase("generation.round"):
            exact = self._exact_round()
            did = exact is not None and self._drain(exact)
            spec_budget = self.scheduler.prefill_token_budget
            with tracing.phase("generation.admit"):
                if self.host_tier is not None:
                    self._stage_host_restores()
                admitted = self.scheduler.admit()
            if self._use_chunks:
                chunked, spec_budget = self._prefill_round()
                did = chunked or did
            else:
                for seq in admitted:
                    self._prefill_seq(seq)
            with tracing.phase("generation.capacity"):
                if self._in_flight \
                        and self.scheduler.decode_blocks_short() > 0:
                    # an allocation is about to fail: the eviction or
                    # preemption it leads to reads exact sequences
                    did = self._drain("preempt")
                self.scheduler.ensure_decode_capacity()
                if self.prefix_cache is not None:
                    self._apply_cow()
            advanced: set = set()
            if self.speculation is not None \
                    and self.scheduler.running():
                # a draft is made of the lane's own tokens, the first
                # one, of a prefill this round enqueued, among them
                did = self._drain("verify") or did
                advanced = self._spec_round(spec_budget)
            # a lane whose last token is in flight is stopped by the
            # step itself: it is in no further round
            lanes = {seq.slot: seq for seq in self.scheduler.running()
                     if seq not in advanced and not seq.spent}
            try:
                did = self._decode_all(lanes, advanced) or did
            except PoisonedRequestError as e:
                self._evict_poisoned(e)
                did = True
            if self.watchdog is not None:
                self.watchdog.beat()
            return bool(did or admitted or advanced)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """`step()` until the scheduler has no work; returns with
        nothing in flight."""
        if self.watchdog is not None:
            self.watchdog.arm()
        try:
            for _ in range(max_steps):
                if not self.scheduler.has_work():
                    with self._lock:
                        self._drain("idle")
                    return
                if not self.step():
                    stuck_ids = [s.request_id
                                 for s in self.scheduler.waiting]
                    for rid in stuck_ids:
                        request_log.event(rid, "stuck")
                    log_event("generation_stuck",
                              waiting=len(stuck_ids),
                              request_ids=stuck_ids)
                    flight_recorder.dump(
                        "generation_stuck",
                        extra={"waiting": len(self.scheduler.waiting),
                               "request_ids": stuck_ids})
                    raise RuntimeError(
                        "generation engine stuck: waiting requests but "
                        "no schedulable work (block pool too small?)")
            raise RuntimeError(f"still busy after {max_steps} steps")
        finally:
            if self.watchdog is not None:
                self.watchdog.disarm()

    # ------------------------------------------------------------------
    # background serving
    # ------------------------------------------------------------------

    def ensure_started(self) -> "GenerationEngine":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True)
            self._thread.start()
        return self

    def _loop(self) -> None:
        stuck_rounds = 0
        while not self._stop.is_set():
            # durable telemetry: snapshot this loop's registry so a
            # replica SIGKILL'd mid-decode still leaves its counters
            # for the fleet harvest (no-op while observability_dir is
            # unset; time-gated otherwise)
            with tracing.phase("generation.housekeeping"):
                maybe_spool(self.spool_name, (self.registry,))
                # metrics history: time-series samples for burn-rate
                # alerting + replay (disarmed unless
                # metrics_history_interval_s is set)
                maybe_record((self.registry,))
            if not self.scheduler.has_work():
                with self._lock:
                    # a round enqueued for lanes that had all sampled
                    # their `eos`: nobody waits for it
                    self._drain("idle")
                if self.watchdog is not None:
                    # idle is not a stall: disarm until work arrives
                    self.watchdog.disarm()
                with tracing.phase("generation.wait"):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            if self.watchdog is not None:
                self.watchdog.arm()
            try:
                did = self.step()
                if did:
                    # the other threads' turn, the engine's own lock
                    # free for the requests they submit
                    with tracing.phase("generation.wait"):
                        time.sleep(TURN_S)
                with self._lock, \
                        tracing.phase("generation.housekeeping"):
                    if did or not self.scheduler.waiting:
                        stuck_rounds = 0
                    else:
                        # waiting requests, no lanes running, nothing
                        # admittable: the head can never be scheduled.
                        # Reject it (tagged in the request log and
                        # log_event so the failure is findable in a
                        # bundle) instead of busy-spinning forever.
                        stuck_rounds += 1
                        if stuck_rounds >= 3:
                            stuck_rounds = 0
                            head = self.scheduler.waiting.popleft()
                            log_event("generation_stuck",
                                      request_ids=[head.request_id],
                                      waiting=len(
                                          self.scheduler.waiting) + 1)
                            request_log.event(head.request_id, "stuck")
                            flight_recorder.dump(
                                "generation_stuck",
                                extra={"request_ids":
                                       [head.request_id]})
                            self._finish(
                                head, "error: engine stuck (request "
                                "cannot be scheduled)")
            except Exception as e:   # fail loudly but keep serving
                affected = [s.request_id
                            for s in self.scheduler.slotted()]
                log_event("generation_step_error",
                          error=f"{type(e).__name__}: {e}",
                          request_ids=affected)
                flight_recorder.dump("generation_step_error", exc=e,
                                     extra={"request_ids": affected})
                with self._lock:
                    self._abandon("error")
                    self._lanes.invalidate()
                    for seq in list(self.scheduler.slotted()):
                        self._finish(seq, f"error: {e}")

    def consume_stream(self, stream, out_stream=None, **kw):
        """Attach this engine to a durable stream as a consumer-group
        member: each leased record's prompt is submitted under the
        stable id ``strm-<stream>-<record_id>``, the finished tokens
        land in `out_stream`, and only then is the record acked — a
        replica dying mid-record leaves the lease to expire and the
        record replays elsewhere under the same id
        (docs/streaming.md).  Returns the started `StreamConsumer`."""
        from analytics_zoo_tpu.serving.streaming.consumer import (
            generation_consumer,
        )
        return generation_consumer(stream, self,
                                   out_stream=out_stream, **kw)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # unblock consumers of requests that will never run, once the
        # tokens already computed for them are out
        with self._lock:
            self._abandon("idle")
            for seq in list(self.scheduler.slotted()):
                self._finish(seq, "error: engine stopped")
            while self.scheduler.waiting:
                self._finish(self.scheduler.waiting.popleft(),
                             "error: engine stopped")
