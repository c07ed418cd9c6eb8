"""Profiling plane: dispatch ledger, compile forensics, MFU accounting.

Every PR since the continuous-batching engine enforces the hot-path
performance contract with one blunt instrument — ``decode_compile_count
== 1``.  When that pin breaks in production, or when tokens/s regresses
with compiles still pinned, nothing in the stack can say *which*
compiled program ate the wall, *what* argument signature triggered a
recompile, or how far measured throughput sits from the model's
analytic FLOP ceiling.  This module produces those three signals:

**Dispatch ledger.**  Every named jitted program family registers at
jit-wrap time via `instrument(family, fn)` — the registered family
names are the `DISPATCH_FAMILIES` tuple, linted in both directions
against docs/observability.md's family table by
`scripts/check_compiled_families.py`.  Each call records count + arg
bytes (derived per signature, so the hot path never re-walks buffer
sizes); the surrounding loop reports its FENCED wall + token/FLOP
work via `record_work(family, dur_s, ...)` (warm dispatches return
before the device finishes, so only the caller's fence-to-fence wall
is honest).  Per-family wall/work lands in bounded reservoirs,
exported as the ``dispatch_*`` metric family, a per-family Perfetto
track (timeline pid 8) and the ``GET /dispatch`` server block — "where
did the step go" decomposes by *program*, not just by goodput bucket.

**Compile forensics.**  The wrapper derives each call's abstract
signature (leaf path, shape, dtype; static leaves by value).  A
signature never seen by the family is a compile: the call's wall is
the compile cost (jit compiles synchronously inside the dispatch), a
`compile event` is appended to a bounded log — family, signature,
compile seconds, callsite — and, on any compile after the family's
first, a differ names the exact leaf that forked the cache entry
(path, old shape/dtype → new shape/dtype).  Events embed in flight
bundles and tick ``compile_events_total`` / ``compile_seconds_total``,
which the built-in ``recompile_storm`` alert rule watches over the
metrics history plane.

**MFU / roofline accounting.**  `CausalLMFlops` is the analytic FLOPs
model for prefill/decode/verify (matmul + attention terms from the
model dims); the SPMD estimator uses the standard ``6·P`` train /
``2·P`` eval FLOPs-per-token approximation.  Analytic FLOPs combine
with the ledger's measured wall into ``mfu_ratio`` / ``mfu_decode`` /
``mfu_prefill`` gauges and the ``model_flops_total`` counter — peak is
``OrcaContext.hardware_peak_flops`` (default `DEFAULT_PEAK_FLOPS`).
The benchmark (`benchmarks/run.py`) reads ``compile_seconds_total``
and counts its own FLOPs (`benchmarks/harness/flops.py`).
"""

from __future__ import annotations

import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from analytics_zoo_tpu.observability.registry import get_registry, now

#: Registered dispatch-ledger family names — the closed set
#: `instrument()` / `record_work()` accept.  The
#: scripts/check_compiled_families.py lint anchors on this tuple and
#: keeps it in sync (both directions) with the family table in
#: docs/observability.md.
DISPATCH_FAMILIES = (
    "prefill",        # whole-prompt prefill, one signature per bucket
    "chunk_prefill",  # chunked/prefix-cached prefill chunk step
    "decode",         # the one-signature batched decode step
    "spec_verify",    # speculative verify, one signature per k-bucket
    "copy_block",     # prefix-cache copy-on-write block copy
    "host_restore",   # host-KV-tier restore writer
    "train_step",     # SPMDEngine training step
    "eval_step",      # SPMDEngine evaluation step
)

#: Hardware peak used for MFU when `OrcaContext.hardware_peak_flops`
#: is unset: 1 TFLOP/s — a deliberately round placeholder so CPU CI
#: MFU numbers are comparable across rounds, not a real roofline.
DEFAULT_PEAK_FLOPS = 1.0e12

#: bounded per-family call reservoir (timeline + percentiles)
RESERVOIR = 256

#: bounded compile-event log
MAX_COMPILE_EVENTS = 256


def peak_flops() -> float:
    """The configured hardware peak (FLOP/s) MFU is computed against."""
    try:
        from analytics_zoo_tpu.common.context import OrcaContext
        v = OrcaContext.hardware_peak_flops
        if v:
            return float(v)
    except Exception:
        pass
    return DEFAULT_PEAK_FLOPS


# ----------------------------------------------------------------------
# abstract signatures + the differ
# ----------------------------------------------------------------------

def _leaf_abstract(leaf: Any) -> Tuple[Any, ...]:
    """Hashable abstract view of one argument leaf.  Arrays by
    shape/dtype (the jit cache key); python numbers by weak type only
    (changing VALUES of weak-typed scalars does not recompile); other
    statics by repr (changing them does)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return ("array", tuple(shape), str(dtype))
    if isinstance(leaf, bool):
        return ("py", "bool")
    if isinstance(leaf, (int, float, complex)):
        return ("py", type(leaf).__name__)
    return ("static", repr(leaf))


def _leaf_str(abstract: Tuple[Any, ...]) -> str:
    """Render one abstract leaf the way the forensics log prints it:
    ``int32[4,16]`` for arrays, ``py:int`` / ``static:...`` else."""
    if abstract[0] == "array":
        return "%s[%s]" % (abstract[2],
                           ",".join(str(d) for d in abstract[1]))
    return ":".join(str(p) for p in abstract)


def abstract_signature(args: Sequence[Any],
                       argnames: Optional[Sequence[str]] = None
                       ) -> Tuple[Tuple[str, Tuple[Any, ...]], ...]:
    """The abstract signature of a positional-arg tuple: one
    ``(path, abstract-leaf)`` per pytree leaf, paths rooted at the
    argument name when `argnames` is given (else the position)."""
    import jax

    out: List[Tuple[str, Tuple[Any, ...]]] = []
    for i, arg in enumerate(args):
        root = (argnames[i] if argnames is not None
                and i < len(argnames) else f"arg{i}")
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in leaves:
            sub = jax.tree_util.keystr(path)
            out.append((root + sub, _leaf_abstract(leaf)))
    return tuple(out)


def diff_signatures(old, new) -> List[Dict[str, Optional[str]]]:
    """Name the exact leaves that forked a jit cache entry: changed
    leaves as ``{path, old, new}`` (shape/dtype strings), added/removed
    leaves with the missing side None."""
    old_map = dict(old)
    new_map = dict(new)
    diffs: List[Dict[str, Optional[str]]] = []
    for path, ab in new_map.items():
        prev = old_map.get(path)
        if prev is None:
            diffs.append({"path": path, "old": None,
                          "new": _leaf_str(ab)})
        elif prev != ab:
            diffs.append({"path": path, "old": _leaf_str(prev),
                          "new": _leaf_str(ab)})
    for path, ab in old_map.items():
        if path not in new_map:
            diffs.append({"path": path, "old": _leaf_str(ab),
                          "new": None})
    diffs.sort(key=lambda d: d["path"])
    return diffs


def _signature_bytes(sig) -> int:
    """Total argument bytes of one signature (arrays only) — computed
    once per signature, reused for every call carrying it."""
    import numpy as np

    total = 0
    for _path, ab in sig:
        if ab[0] == "array":
            n = 1
            for d in ab[1]:
                n *= int(d)
            try:
                total += n * np.dtype(ab[2]).itemsize
            except TypeError:
                total += n
    return total


# ----------------------------------------------------------------------
# the ledger
# ----------------------------------------------------------------------

class _Family:
    """Per-family accumulators + bounded call reservoir."""

    __slots__ = ("name", "calls", "wall_s", "bytes_total",
                 "flops_total", "tokens_total", "work_calls",
                 "signatures", "compile_count", "compile_seconds",
                 "reservoir", "last_event", "expected")

    def __init__(self, name: str):
        self.name = name
        #: declared compile budget (None = undeclared): the number of
        #: program variants the call-site geometry implies — prefill's
        #: bucket count, decode's 1 — so the ledger can flag a family
        #: that compiled MORE programs than its geometry allows
        self.expected: Optional[int] = None
        self.calls = 0
        self.wall_s = 0.0
        self.bytes_total = 0
        self.flops_total = 0.0
        self.tokens_total = 0
        self.work_calls = 0
        #: signature -> arg bytes (insertion-ordered ≈ compile order)
        self.signatures: Dict[Tuple, int] = {}
        self.compile_count = 0
        self.compile_seconds = 0.0
        #: (wall ts at record, fenced dur_s, tokens) — newest kept
        self.reservoir: "deque[Tuple[float, float, int]]" = deque(
            maxlen=RESERVOIR)
        self.last_event: Optional[Dict[str, Any]] = None

    def mfu(self) -> float:
        if self.wall_s <= 0.0 or self.flops_total <= 0.0:
            return 0.0
        return self.flops_total / self.wall_s / peak_flops()

    def snapshot(self) -> Dict[str, Any]:
        res = list(self.reservoir)
        durs = sorted(d for _t, d, _n in res)
        mid = durs[len(durs) // 2] if durs else 0.0
        p99 = durs[min(len(durs) - 1,
                       int(0.99 * len(durs)))] if durs else 0.0
        out = {
            "calls": self.calls,
            "work_calls": self.work_calls,
            "wall_s": round(self.wall_s, 6),
            "mean_ms": round(self.wall_s / self.work_calls * 1e3, 3)
            if self.work_calls else 0.0,
            "p50_ms": round(mid * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3),
            "bytes_total": int(self.bytes_total),
            "tokens_total": int(self.tokens_total),
            "model_flops_total": float(self.flops_total),
            "mfu": round(self.mfu(), 6),
            "signatures": len(self.signatures),
            "compile_count": self.compile_count,
            "compile_seconds": round(self.compile_seconds, 6),
        }
        if self.expected is not None:
            out["expected_variants"] = self.expected
            out["over_budget"] = self.compile_count > self.expected
        if self.last_event is not None:
            out["last_compile"] = self.last_event
        return out


_lock = threading.Lock()
_families: Dict[str, _Family] = {}
_compile_events: "deque[Dict[str, Any]]" = deque(
    maxlen=MAX_COMPILE_EVENTS)
_metrics_installed = False


def _install_metrics() -> None:
    """Register the fn-backed gauges once (counters are ticked at
    record time; gauges read the ledger live)."""
    global _metrics_installed
    if _metrics_installed:
        return
    _metrics_installed = True
    reg = get_registry()
    reg.gauge("mfu_ratio", fn=lambda: _mfu_over(None),
              help="measured model FLOP/s over the configured "
                   "hardware peak, all ledger families combined")
    reg.gauge("mfu_decode", fn=lambda: _mfu_over(("decode",)),
              help="decode-step MFU: analytic decode FLOPs over "
                   "fenced decode wall, vs hardware peak")
    reg.gauge("mfu_prefill",
              fn=lambda: _mfu_over(("prefill", "chunk_prefill")),
              help="prefill MFU over both prefill program families")


def _mfu_over(names: Optional[Tuple[str, ...]]) -> float:
    with _lock:
        fams = [f for f in _families.values()
                if names is None or f.name in names]
        flops = sum(f.flops_total for f in fams)
        wall = sum(f.wall_s for f in fams if f.flops_total > 0.0)
    if wall <= 0.0 or flops <= 0.0:
        return 0.0
    return flops / wall / peak_flops()


def _family(name: str) -> _Family:
    if name not in DISPATCH_FAMILIES:
        raise ValueError(
            f"unknown dispatch family {name!r} — add it to "
            "profiling.DISPATCH_FAMILIES and the docs/observability.md "
            "family table (scripts/check_compiled_families.py)")
    with _lock:
        fam = _families.get(name)
        if fam is None:
            fam = _families[name] = _Family(name)
    _install_metrics()
    return fam


def _callsite() -> str:
    """First stack frame outside this module — where the compiling
    dispatch came from.  Compared by exact path: a suffix match would
    also swallow frames of files merely NAMED like this one (the test
    file tests/test_profiling.py, for instance)."""
    for fr in reversed(traceback.extract_stack(limit=12)):
        if fr.filename != __file__:
            return f"{fr.filename}:{fr.lineno}"
    return "?"


class LedgeredFunction:
    """The jit-wrap hook: forwards calls to the wrapped (jitted)
    callable, counts them, and records a compile event for every
    signature the family has not dispatched before.  The abstract
    signature is derived only for a call that compiled — the wrapped
    function's own ``_cache_size()`` grew across it, or the family has
    no signature yet — never on a warm call: that walk over every
    argument leaf (150 for a decode step) would describe again what the
    jit cache already knows.  A callable without ``_cache_size`` (not a
    jit) is described on every call, as there is nothing cheaper to ask.
    Forwards ``_cache_size`` so the engines' ``decode_compile_count``
    pin keeps reading the REAL jit cache."""

    def __init__(self, family: str, fn: Callable,
                 argnames: Optional[Sequence[str]] = None):
        self.family = family
        self.fn = fn
        self.argnames = tuple(argnames) if argnames else None
        self._fam = _family(family)
        #: argument bytes of the variant this wrapper compiled last:
        #: what a warm call adds to the family's `bytes_total`
        self._arg_bytes = 0
        inner = getattr(fn, "_cache_size", None)
        if inner is not None:
            self._cache_size = inner

    def __call__(self, *args):
        fam = self._fam
        size = getattr(self, "_cache_size", None)
        before = size() if size is not None else -1
        t0 = now()
        out = self.fn(*args)
        dur = now() - t0
        if size is None or size() != before or not fam.signatures:
            sig = abstract_signature(args, self.argnames)
            with _lock:
                known = sig in fam.signatures
            if not known:
                _record_compile(fam, sig, dur, _callsite())
            self._arg_bytes = fam.signatures[sig]
        reg = get_registry()
        with _lock:
            fam.calls += 1
            fam.bytes_total += self._arg_bytes
        reg.counter(
            "dispatch_calls_total",
            help="ledgered jit dispatches, all families").inc()
        reg.counter(
            f"dispatch_{fam.name}_calls_total",
            help=f"{fam.name} program dispatches").inc()
        return out


def _record_compile(fam: _Family, sig, dur_s: float,
                    callsite: str) -> None:
    """Append one compile event (with the signature diff when this is
    not the family's first program) and tick the forensics metrics."""
    with _lock:
        prev = (next(reversed(fam.signatures))
                if fam.signatures else None)
        fam.signatures[sig] = _signature_bytes(sig)
        fam.compile_count += 1
        fam.compile_seconds += dur_s
        event: Dict[str, Any] = {
            "ts": round(time.time(), 6),
            "family": fam.name,
            "n": fam.compile_count,
            "compile_s": round(dur_s, 6),
            "callsite": callsite,
            "signature": [(p, _leaf_str(ab)) for p, ab in sig],
        }
        if prev is not None:
            event["diff"] = diff_signatures(prev, sig)
        fam.last_event = {k: v for k, v in event.items()
                          if k != "signature"}
        _compile_events.append(event)
    reg = get_registry()
    reg.counter("compile_events_total",
                help="jit compile events across all ledgered "
                     "dispatch families (recompile_storm input)").inc()
    reg.counter("compile_seconds_total",
                help="wall seconds spent inside compiling "
                     "dispatches").inc(max(0.0, dur_s))
    if fam.compile_count > 1:
        # a second program for a family is exactly what the forensics
        # exist for — leave a breadcrumb on the flight ring too
        try:
            from analytics_zoo_tpu.observability import flight_recorder
            first = (event.get("diff") or [{}])[0]
            flight_recorder.record(
                "compile", family=fam.name, n=fam.compile_count,
                compile_s=event["compile_s"],
                path=str(first.get("path", "")),
                old=str(first.get("old", "")),
                new=str(first.get("new", "")))
        except Exception:
            pass


def instrument(family: str, fn: Callable,
               argnames: Optional[Sequence[str]] = None
               ) -> LedgeredFunction:
    """Register `fn` (a jitted callable) under a dispatch-ledger
    family.  The wrapper is transparent to the zero-recompile pin
    (``_cache_size`` forwards) and derives a signature only for a
    call that compiled."""
    return LedgeredFunction(family, fn, argnames)


def declare_expected(family: str, n_variants: int) -> None:
    """Declare a family's compile budget — how many program variants
    its call-site geometry implies (the scheduler's prefill bucket
    count, speculation's verify k-bucket count, decode's 1).  Snapshot
    rows then carry ``expected_variants`` / ``over_budget`` so a
    recompile storm is visible as a budget breach, not just a rate."""
    fam = _family(family)
    with _lock:
        fam.expected = int(n_variants)


def record_work(family: str, dur_s: float, tokens: int = 0,
                flops: float = 0.0) -> None:
    """Report one fenced unit of work for a family: the surrounding
    loop's measured wall (dispatch → device fence) plus the analytic
    token/FLOP content.  This is the wall MFU divides by — wrapper
    dispatch times are async for warm calls and would overstate MFU."""
    fam = _family(family)
    with _lock:
        fam.work_calls += 1
        fam.wall_s += max(0.0, dur_s)
        fam.tokens_total += int(tokens)
        fam.flops_total += float(flops)
        fam.reservoir.append((time.time(), max(0.0, dur_s),
                              int(tokens)))
    reg = get_registry()
    reg.counter(
        f"dispatch_{family}_wall_seconds_total",
        help=f"fenced wall seconds attributed to the {family} "
             "program family").inc(max(0.0, dur_s))
    if flops:
        reg.counter(
            "model_flops_total",
            help="analytic model FLOPs executed (CausalLMFlops / "
                 "estimator 6P·tokens accounting)").inc(float(flops))


# ----------------------------------------------------------------------
# snapshots (server block, flight bundles, timeline)
# ----------------------------------------------------------------------

def ledger_snapshot() -> Dict[str, Any]:
    """The ``GET /dispatch`` payload: per-family ledger rows, the MFU
    block, and the compile-event tail."""
    with _lock:
        fams = {name: fam.snapshot()
                for name, fam in _families.items()}
        events = list(_compile_events)
    return {
        "families": fams,
        "peak_flops": peak_flops(),
        "mfu": {"overall": round(_mfu_over(None), 6),
                "decode": round(_mfu_over(("decode",)), 6),
                "prefill": round(
                    _mfu_over(("prefill", "chunk_prefill")), 6)},
        "compile_events_total": sum(
            f["compile_count"] for f in fams.values()),
        "compile_seconds_total": round(sum(
            f["compile_seconds"] for f in fams.values()), 6),
        "compile_events": events[-64:],
    }


def compile_events(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The compile-event log, oldest first (bounded)."""
    with _lock:
        events = list(_compile_events)
    return events[-int(n):] if n is not None else events


def recent_calls(n: Optional[int] = None
                 ) -> List[Tuple[str, float, float, int]]:
    """(family, wall_ts, dur_s, tokens) across all family reservoirs,
    oldest first — the timeline's pid-8 feed."""
    with _lock:
        rows = [(fam.name, ts, dur, tok)
                for fam in _families.values()
                for ts, dur, tok in fam.reservoir]
    rows.sort(key=lambda r: r[1])
    return rows[-int(n):] if n is not None else rows


def registered_families() -> Tuple[str, ...]:
    """Families that have actually registered (subset of
    `DISPATCH_FAMILIES`), registration order."""
    with _lock:
        return tuple(_families)


def reset_profiling() -> None:
    """Drop all ledger/forensics state (tests).  Metric registrations
    persist — the fn-backed gauges simply read an empty ledger."""
    with _lock:
        _families.clear()
        _compile_events.clear()


# ----------------------------------------------------------------------
# analytic FLOPs models
# ----------------------------------------------------------------------

class CausalLMFlops:
    """Analytic per-token FLOPs for the serving `CausalLM`: the
    standard decomposition into a context-independent matmul term
    (QKV/proj/MLP/head, 2·m·n per m×n matmul) and a context-linear
    attention term (QKᵀ + weighted-V ≈ 4·ctx·hidden per layer).
    Embedding lookups and LayerNorms are dropped (≪1%)."""

    def __init__(self, vocab: int, hidden_size: int, n_block: int,
                 intermediate_size: int):
        self.vocab = int(vocab)
        self.hidden = int(hidden_size)
        self.n_block = int(n_block)
        self.intermediate = int(intermediate_size)
        H, I = self.hidden, self.intermediate
        #: per-token matmul FLOPs: qkv (H→3H) + proj (H→H) + fc1/fc2
        #: (H→I→H) per block, + the lm head (H→vocab)
        self.matmul_per_token = (
            self.n_block * (2 * H * 3 * H + 2 * H * H
                            + 2 * H * I + 2 * I * H)
            + 2 * H * self.vocab)

    @classmethod
    def from_model(cls, model: Any) -> "CausalLMFlops":
        return cls(model.vocab, model.hidden_size, model.n_block,
                   model.intermediate_size)

    def _attention(self, ctx: float) -> float:
        return self.n_block * 4.0 * max(0.0, float(ctx)) * self.hidden

    def prefill(self, n_tokens: int, ctx_start: int = 0) -> float:
        """FLOPs of prefilling `n_tokens` positions starting at
        context offset `ctx_start` (chunked prefill passes the chunk's
        start).  Attention sums over each position's causal context."""
        n = int(n_tokens)
        if n <= 0:
            return 0.0
        # sum_{i=0}^{n-1} (ctx_start + i + 1)
        ctx_sum = n * (int(ctx_start) + 1) + n * (n - 1) // 2
        return n * self.matmul_per_token + self._attention(ctx_sum)

    def decode(self, n_lanes: int, ctx_mean: float) -> float:
        """One batched decode step: `n_lanes` single-token rows each
        attending over ~`ctx_mean` context tokens."""
        n = int(n_lanes)
        if n <= 0:
            return 0.0
        return n * (self.matmul_per_token + self._attention(ctx_mean))

    def verify(self, n_rows: int, width: int, ctx_mean: float
               ) -> float:
        """One speculative verify step: `n_rows` lanes × `width`
        positions (draft + pending token), each attending over the
        lane context plus its preceding in-row positions."""
        tokens = int(n_rows) * int(width)
        if tokens <= 0:
            return 0.0
        return (tokens * self.matmul_per_token
                + self._attention(tokens * max(0.0, float(ctx_mean))
                                  + int(n_rows)
                                  * int(width) * (int(width) - 1) / 2))


def train_step_flops(n_params: int, batch_tokens: int,
                     train: bool = True) -> float:
    """The standard dense-model approximation the Estimator uses:
    forward ≈ 2·P FLOPs per token, backward ≈ 4·P — 6·P per trained
    token, 2·P per evaluated one."""
    factor = 6.0 if train else 2.0
    return factor * float(n_params) * float(batch_tokens)
