"""Flight recorder / goodput / watchdog end-to-end: an induced stall
and an injected NaN in small jitted steps must leave a post-mortem
bundle (thread stacks, ring contents, localized leaf name) under
observability_dir; a killed child must leave evidence; and the default
(sentinel-off) train step and the decode hot loop must keep their
zero-recompile guarantees with the watchdog enabled."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from analytics_zoo_tpu import init_orca_context
from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability import (
    Watchdog,
    flight_recorder,
    get_registry,
    goodput_tables,
    localize_nonfinite,
    nonfinite_leaves,
)


@pytest.fixture()
def obs_dir(tmp_path):
    """Configured observability dir + restores every knob this suite
    touches (sentinel, watchdog deadline, excepthook/faulthandler)."""
    d = str(tmp_path / "obs")
    prev = OrcaContext.observability_dir
    OrcaContext.observability_dir = d
    yield d
    OrcaContext.observability_dir = prev
    OrcaContext.nonfinite_watchdog = False
    OrcaContext.watchdog_deadline_s = None
    flight_recorder.uninstall()


def _tiny_estimator():
    import flax.linen as nn

    from analytics_zoo_tpu.orca.learn import Estimator

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    return Estimator.from_flax(Tiny(), loss="mse", optimizer="sgd",
                               learning_rate=1e-2)


# ---------------------------------------------------------------------------
# ring + dump basics
# ---------------------------------------------------------------------------

def test_ring_bounded_and_dump_contents(obs_dir):
    flight_recorder.clear_ring()
    for i in range(flight_recorder.RING_SIZE + 40):
        flight_recorder.record("unit_fill", i=i)
    ring = flight_recorder.ring_contents()
    assert len(ring) == flight_recorder.RING_SIZE
    assert ring[-1]["i"] == flight_recorder.RING_SIZE + 39
    path = flight_recorder.dump(
        "unit_test",
        extra={"api_key": "hunter2", "note": "Bearer abc.def.ghi"})
    assert path is not None and os.path.exists(path)
    bundle = json.load(open(path))
    assert bundle["reason"] == "unit_test"
    assert bundle["thread_stacks"]          # every live thread's stack
    assert any("test_ring_bounded" in "".join(frames)
               for frames in bundle["thread_stacks"].values())
    assert any(r["kind"] == "unit_fill" for r in bundle["ring"])
    assert "metrics" in bundle and "goodput" in bundle
    # the metrics-history plane rides every bundle: with no recorder
    # armed both fields are present and empty, never missing
    assert bundle["history_tail"] == []
    assert bundle["alerts_active"] == {}
    # secrets never reach disk
    assert bundle["extra"]["api_key"] == "<redacted>"
    assert "Bearer abc" not in bundle["extra"]["note"]


def test_dump_without_dir_is_noop_but_counted():
    prev = OrcaContext.observability_dir
    OrcaContext.observability_dir = None
    try:
        before = get_registry().counter(
            "flight_recorder_dumps_total").value
        assert flight_recorder.dump("nowhere") is None
        assert get_registry().counter(
            "flight_recorder_dumps_total").value == before + 1
    finally:
        OrcaContext.observability_dir = prev


# ---------------------------------------------------------------------------
# induced stall
# ---------------------------------------------------------------------------

def test_induced_stall_dumps_bundle(obs_dir):
    """A batch iterator that wedges mid-epoch must trip the watchdog:
    stall counter, ring marker, and a bundle whose thread stacks show
    where the loop sat."""
    init_orca_context(cluster_mode="local")
    est = _tiny_estimator()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    # engine exists after the first (fast) fit; then drive run_epoch
    # directly with a wedging iterator under a tight watchdog
    est.fit({"x": x, "y": y}, epochs=1, batch_size=8)
    eng = est._engine

    def wedging_batches():
        mask = np.ones(8, np.float32)
        yield {"features": (x[:8],), "labels": (y[:8],), "mask": mask}
        time.sleep(0.9)                      # the "hang"
        yield {"features": (x[8:16],), "labels": (y[8:16],),
               "mask": mask}

    before = get_registry().counter("watchdog_stall_total").value
    wd = Watchdog("unit_stall", deadline_s=0.25)
    eng.watchdog = wd
    try:
        with wd:
            eng.run_epoch(wedging_batches(), train=True)
    finally:
        eng.watchdog = None
        wd.stop()
    assert wd.stalls >= 1
    assert get_registry().counter("watchdog_stall_total").value > before
    bundles = flight_recorder.find_bundles(obs_dir)
    assert bundles, "stall left no bundle"
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "watchdog_stall"
    assert bundle["extra"]["watchdog"] == "unit_stall"
    assert bundle["thread_stacks"]
    # the ring carries the steps that DID happen before the wedge
    assert any(r["kind"] == "spmd_step" for r in bundle["ring"])


# ---------------------------------------------------------------------------
# injected NaN + sentinel localization
# ---------------------------------------------------------------------------

def test_injected_nan_localized_and_dumped(obs_dir):
    init_orca_context(cluster_mode="local")
    OrcaContext.nonfinite_watchdog = True
    est = _tiny_estimator()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    x[19, 1] = np.inf                        # poisons batch 3 of 4
    est.fit({"x": x, "y": y}, epochs=1, batch_size=8, shuffle=False)
    # the on-device guard skipped the poisoned step...
    assert est.train_summary[-1]["nan_steps"] == 1
    # ...and the sentinel wrote a bundle naming the first bad leaf
    bundles = flight_recorder.find_bundles(obs_dir)
    assert bundles, "sentinel left no bundle"
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "nonfinite_step"
    leaves = bundle["extra"]["leaves"]
    assert leaves, "no leaf localized"
    first = leaves[0]
    # params stayed finite (guarded); the forward is the first dirty
    # tree, so the named leaf is the predictions tensor
    assert first["path"].startswith("predictions")
    assert first["inf"] >= 1
    paths = [l["path"] for l in leaves]
    assert not any(p.startswith("params") for p in paths)


def test_localize_nonfinite_orders_and_counts():
    leaves = nonfinite_leaves(
        {"a": np.ones(3, np.float32),
         "b": np.array([1.0, np.nan, np.inf], np.float32),
         "c": np.array([np.nan], np.float32)})
    assert [l["nan"] for l in leaves] == [1, 1]
    assert leaves[0]["nonfinite"] == 2 and leaves[0]["inf"] == 1
    found = localize_nonfinite(
        {"clean": {"x": np.zeros(2, np.float32)},
         "dirty": {"y": np.array([np.inf], np.float32)}})
    assert len(found) == 1
    assert found[0]["path"].startswith("dirty:")
    # integer trees never count as nonfinite
    assert nonfinite_leaves({"i": np.array([1, 2])}) == []


# ---------------------------------------------------------------------------
# zero-recompile guarantees with the watchdog armed
# ---------------------------------------------------------------------------

def test_default_train_step_compiles_once_with_watchdog(obs_dir):
    """The watchdog (stall detection armed) and the sentinel being OFF
    must leave the default step byte-identical: exactly one compiled
    variant of the jitted train step across a multi-epoch fit."""
    init_orca_context(cluster_mode="local")
    assert OrcaContext.nonfinite_watchdog is False     # the default
    OrcaContext.watchdog_deadline_s = 60.0
    est = _tiny_estimator()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    est.fit({"x": x, "y": y}, epochs=3, batch_size=8)
    size = est._engine._train_step._cache_size
    assert size() == 1, f"train step recompiled: {size()} variants"


def test_decode_hot_loop_zero_recompile_with_watchdog(obs_dir):
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.generation import (CausalLM,
                                                      GenerationEngine)

    init_orca_context(cluster_mode="local")
    OrcaContext.watchdog_deadline_s = 60.0
    model = CausalLM(vocab=32, hidden_size=16, n_head=2, n_block=1,
                     intermediate_size=32, max_position_len=64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    eng = GenerationEngine(model, params, max_slots=2, block_size=8,
                           max_context=32)
    assert eng.watchdog is not None          # knob was picked up
    eng.warmup()
    flight_recorder.clear_ring()
    for prompt in ([1, 2, 3], [4, 5], [6]):
        assert eng.generate(prompt, max_new_tokens=4)
    assert eng.decode_compile_count == 1
    # the scheduler's per-lane decisions reached the flight ring
    kinds = {r["kind"] for r in flight_recorder.ring_contents()}
    assert {"sched_admit", "sched_release"} <= kinds
    # and the goodput clocks decomposed the loops, buckets summing to
    # the fenced wall (the invariant the step clock is built on)
    for name in ("generation_prefill", "generation_decode"):
        t = goodput_tables()[name]
        assert t["fenced_steps"] > 0
        ssum = sum(t["buckets_s"].values())
        assert ssum == pytest.approx(t["fenced_wall_s"], rel=0.05)


# ---------------------------------------------------------------------------
# killed child leaves evidence (the multichip-dryrun recipe)
# ---------------------------------------------------------------------------

def test_killed_child_leaves_evidence(tmp_path):
    """A child armed like the multichip dryrun's stage children must
    leave evidence when killed: SIGTERM (Python handler runs) gets a
    full json bundle; the faulthandler stacks file exists for the
    hard-abort class that never reaches Python."""
    d = str(tmp_path / "diag")
    code = (
        "import os, signal, time\n"
        "from analytics_zoo_tpu.common.context import OrcaContext\n"
        f"OrcaContext.observability_dir = {d!r}\n"
        "from analytics_zoo_tpu.observability import flight_recorder\n"
        "flight_recorder.install()\n"
        "flight_recorder.record('child_progress', step=7)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(10)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=60)
    assert proc.returncode == -signal.SIGTERM
    bundles = flight_recorder.find_bundles(d)
    assert bundles, "killed child left no bundle"
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "signal_SIGTERM"
    assert any(r["kind"] == "child_progress" for r in bundle["ring"])
    # the faulthandler stacks file (hard-crash insurance) was created
    assert any(fn.endswith(".stacks") for fn in os.listdir(d))


def test_multichip_flake_classifier():
    """The per-stage attempt records' signature classifier: the known
    XLA:CPU rendezvous-timeout SIGABRT is told apart from a real
    signal death and a deterministic exit."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import __graft_entry__ as g
    finally:
        sys.path.pop(0)
    assert g._classify_failure(
        -6, "Termination timeout for `collective permute Rendezvous"
    ) == "sigabrt_rendezvous_timeout"
    assert g._classify_failure(-6, "") == "signal_6"
    assert g._classify_failure(-9, "") == "signal_9"
    assert g._classify_failure(1, "Traceback ...") == "exit_rc1"
