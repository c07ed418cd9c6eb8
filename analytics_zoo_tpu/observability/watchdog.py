"""Watchdog — stall detection and nonfinite localization.

Two failure modes metrics alone cannot catch in time:

* **stalls** — a hung collective, a wedged device dispatch, a deadlocked
  queue: the process is alive, every gauge is frozen, and nothing
  fires.  `Watchdog` is a daemon thread fed heartbeats (`beat()`) by
  the hot loops (one per training step / decode iteration); when no
  progress lands for `deadline_s` it increments
  ``watchdog_stall_total``, writes a flight-recorder bundle (the stack
  of every thread shows WHERE it is stuck) and keeps watching — one
  dump per stall episode, re-armed by the next beat.

* **nonfinite values** — the SPMD train step already folds a cheap
  `isfinite` all-reduce over loss+grads into the jitted program (its
  ``_nan_steps`` stat; no recompile is involved in reading it).  The
  opt-in sentinel (`OrcaContext.nonfinite_watchdog`) makes the host
  CHECK that stat per step and, on trip, run `localize_nonfinite` — a
  host-side per-tensor pass that names the first nonfinite leaf — and
  dump a bundle.  Off (default) the step program, its dispatch pattern
  and its zero-recompile guarantees are byte-identical.

`localize_nonfinite` is also a standalone tool: point it at any pytree
(params, grads, activations) and it returns the offending leaf paths —
what finally localizes the `test_pipeline_fsdp_composition` NaN flake
instead of re-triaging a bare "loss is NaN".
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

from analytics_zoo_tpu.observability import flight_recorder
from analytics_zoo_tpu.observability.registry import get_registry, now


class Watchdog:
    """Stall detector for one hot loop.

    name: label for metrics/bundles (e.g. "estimator_fit").
    deadline_s: max seconds between beats before a stall fires.
    on_stall: optional callback(run_seconds_since_last_beat).
    dump: write a flight-recorder bundle on stall (default True).

    Use as a context manager (arms on enter, disarms on exit) or call
    `arm()`/`disarm()` explicitly; `beat()` from the observed loop.
    The watcher thread is started lazily on first arm and polls at
    deadline/4 (min 50 ms) — idle cost is one sleeping daemon thread.
    """

    def __init__(self, name: str, deadline_s: float,
                 on_stall: Optional[Callable[[float], None]] = None,
                 dump: bool = True):
        if deadline_s <= 0:
            raise ValueError("deadline_s must be > 0")
        self.name = name
        self.deadline_s = float(deadline_s)
        self.on_stall = on_stall
        self._dump = dump
        self._lock = threading.Lock()
        self._last_beat = now()
        self._armed = False
        self._fired = False      # one dump per stall episode
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._c_stalls = get_registry().counter(
            "watchdog_stall_total",
            help="stall episodes detected by watchdogs")
        self.stalls = 0

    # ------------------------------------------------------------------

    def beat(self) -> None:
        """Progress heartbeat: call once per step/iteration."""
        with self._lock:
            self._last_beat = now()
            self._fired = False

    def arm(self) -> "Watchdog":
        with self._lock:
            self._last_beat = now()
            self._fired = False
            self._armed = True
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._watch, name=f"watchdog-{self.name}",
                daemon=True)
            self._thread.start()
        return self

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    def stop(self) -> None:
        self.disarm()
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
        self._stop.clear()

    def __enter__(self) -> "Watchdog":
        return self.arm()

    def __exit__(self, *exc) -> bool:
        self.disarm()
        return False

    # ------------------------------------------------------------------

    def _watch(self) -> None:
        poll = max(0.05, self.deadline_s / 4.0)
        while not self._stop.wait(poll):
            with self._lock:
                if not self._armed or self._fired:
                    continue
                stalled = now() - self._last_beat
                if stalled < self.deadline_s:
                    continue
                self._fired = True
            self._trip(stalled)

    def _trip(self, stalled: float) -> None:
        self.stalls += 1
        self._c_stalls.inc()
        flight_recorder.record("watchdog_stall", watchdog=self.name,
                               stalled_s=round(stalled, 3),
                               deadline_s=self.deadline_s)
        if self._dump:
            flight_recorder.dump(
                "watchdog_stall",
                extra={"watchdog": self.name,
                       "stalled_s": round(stalled, 3),
                       "deadline_s": self.deadline_s})
        if self.on_stall is not None:
            try:
                self.on_stall(stalled)
            except Exception:
                pass


def maybe_watchdog(name: str,
                   deadline_s: Optional[float] = None
                   ) -> Optional[Watchdog]:
    """Build a Watchdog when a deadline is configured: explicit
    `deadline_s` wins, else `OrcaContext.watchdog_deadline_s`, else
    None (watchdog off — the default)."""
    if deadline_s is None:
        from analytics_zoo_tpu.common.context import OrcaContext
        deadline_s = OrcaContext.watchdog_deadline_s
    if deadline_s is None:
        return None
    return Watchdog(name, deadline_s)


# ----------------------------------------------------------------------
# nonfinite localization
# ----------------------------------------------------------------------

def nonfinite_leaves(tree: Any, max_leaves: int = 8,
                     prefix: str = "") -> List[Dict[str, Any]]:
    """Host-side per-tensor pass over a pytree: the path, shape, dtype
    and nonfinite counts (nan/inf) of up to `max_leaves` offending
    leaves, in tree order — so [0] is "the first nonfinite leaf".

    Device arrays are fetched leaf-by-leaf (this runs on the cold
    post-mortem path, not the hot loop)."""
    import numpy as np
    import jax

    out: List[Dict[str, Any]] = []
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        if len(out) >= max_leaves:
            break
        try:
            a = np.asarray(leaf)
        except Exception:
            continue
        if a.dtype.kind not in "fc":
            continue
        finite = np.isfinite(a)
        if finite.all():
            continue
        n_nan = int(np.isnan(a).sum())
        n_bad = int(a.size - finite.sum())
        out.append({
            "path": prefix + jax.tree_util.keystr(path),
            "shape": list(a.shape),
            "dtype": str(a.dtype),
            "nonfinite": n_bad,
            "nan": n_nan,
            "inf": n_bad - n_nan,
        })
    return out


def localize_nonfinite(trees: Dict[str, Any],
                       max_leaves: int = 8) -> List[Dict[str, Any]]:
    """Scan several labeled pytrees ({"params": ..., "grads": ...}) in
    the given order and return the offending leaves across all of them
    (first entry = first nonfinite leaf of the first dirty tree)."""
    found: List[Dict[str, Any]] = []
    for label, tree in trees.items():
        if len(found) >= max_leaves:
            break
        found.extend(nonfinite_leaves(
            tree, max_leaves=max_leaves - len(found),
            prefix=label + ":"))
    return found
