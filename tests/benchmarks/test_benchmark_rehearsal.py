"""The drivers at toy widths on the suite's CPU devices: a whole run
without the look for a chip, the control that has to come out as not
correct, and the timed path broken underneath.

Nothing here is a speed number; the sizes are the toy's, the limits
the toy's own (set as the chip's are: above what sound runs read over
these seeds, under what the control reads)."""

import argparse
import json

import jax
import numpy as np
import pytest

from _bench_toy import SERVE, TRAIN, bench_run, toy
from benchmarks.harness import device

#: toy limits.  Serving: sound runs read 0 over four seeds of some 430
#: served tokens, the fp8 control 0.0084 to 0.0285 (which requests a
#: window of threads finishes varies from run to run).  Training, the
#: chip's six numbers over seeds 5, 6 and 8 (the toy computes in float32
#: on the CPU, so its losses agree to the last digit): the medians of
#: the leaves' differences read 6e-5 and 0.005 to 0.006 sound, 0.028 to
#: 0.037 and 0.096 to 0.147 under the fp8 control; the worst leaf's
#: gaps swing from seed to seed (0.0016 to 0.012 sound) and only catch
#: the planted faults; the change of a state left as it was reads 1.
SERVE_LIMITS = {"served_logit_gap_max": {"limit": 0.001}}
TRAIN_LIMITS = {"loss_gap_step2": {"limit": 2e-5},
                "loss_gap_step3": {"limit": 2e-5},
                "grad_norm_gap_max": {"limit": 0.1},
                "update_norm_gap_max": {"limit": 0.2},
                "grad_diff_median": {"limit": 0.003},
                "update_diff_median": {"limit": 0.03}}
LIMITS = {SERVE: SERVE_LIMITS, TRAIN: TRAIN_LIMITS}


def one_run(cell, seed=7, seconds=1.0, trace=0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    peaks = device.load_peaks()["TPU v5 lite"]
    return bench_run.run(args, jax.devices()[:1], peaks,
                         files=toy(cell, LIMITS[cell]))


@pytest.mark.parametrize("cell,seed", [
    (SERVE, 7), (TRAIN, 7),
    # the driver's seeds pass 2**31
    (SERVE, 2**31 + 1017), (TRAIN, 2**31 + 1017)])
def test_rehearsal_of_a_whole_run(cell, seed):
    line = json.loads(json.dumps(one_run(cell, seed=seed)))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]
    assert "setup_s" in line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["value"] > 0 and metric["unit"], name
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]


@pytest.mark.parametrize("cell", [SERVE, TRAIN])
def test_rehearsal_of_a_traced_run(cell):
    """`--trace 1`: the line carries the cell's per-layer metrics that
    found something to read and no other; on the CPU no device plane
    is in the trace, so every reader of the trace returns nothing and
    no share reads 0."""
    line = json.loads(json.dumps(one_run(cell, seed=9, trace=1)))
    manifest = toy(cell)[0]
    mine = {m["name"] for m in bench_run.reported(manifest["per_layer"],
                                                  cell)}
    assert line["metrics"] and set(line["metrics"]) <= mine
    assert not any("roofline" in name or "idle" in name
                   or "device_ms" in name for name in line["metrics"])
    assert line["device"]["busy_s"] == 0.0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "compared" and line["correct"] is True


def test_serve_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails the
    limit the sound run keeps; in float32 it reads exactly 0."""
    driver = _serve_driver(seed=11)
    sample = driver.sample()
    assert driver.gaps(sample)[0] <= SERVE_LIMITS[
        "served_logit_gap_max"]["limit"]
    control, compared = driver.gaps(sample, "fp8")
    assert compared >= 100
    assert control > SERVE_LIMITS["served_logit_gap_max"]["limit"]


def _serve_driver(seed):
    _, _, config, traffic, _ = toy(SERVE)
    module = bench_run.load_module("drivers", traffic["driver"])
    driver = module.Driver(config, traffic, jax.devices()[:1], seed)
    driver.setup()
    driver.window(1.5, None)
    driver.release()
    return driver


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    """Every fifth token changed as the engine hands it to its stream:
    the run goes through and `correct` comes out false."""
    from analytics_zoo_tpu.serving.generation.engine import GenerationStream
    put, count = GenerationStream._put, [0]

    def altered(self, token):
        count[0] += 1
        put(self, int(token) ^ 1 if count[0] % 5 == 0 else token)

    monkeypatch.setattr(GenerationStream, "_put", altered)
    line = one_run(SERVE, seed=13)
    assert line["attempted"] > 0
    assert line["correct"] is False
    assert line["compared"]["served_logit_gap_max"]["value"] > \
        SERVE_LIMITS["served_logit_gap_max"]["limit"]


def _train_numbers(seed=5):
    _, _, config, traffic, _ = toy(TRAIN)
    module = bench_run.load_module("drivers", traffic["driver"])
    driver = module.Driver(config, traffic, jax.devices()[:1], seed)
    driver.build()
    driver.first_steps()
    driver.release()
    return driver, driver.compare(driver.first, driver.reference())


def _fails(numbers):
    return [k for k, v in TRAIN_LIMITS.items() if not numbers[k] <= v["limit"]]


def test_train_control_and_faults():
    """Sound run inside the toy limits; the reference in fp8, put in
    the program's place, outside them; so is the reference with half of
    the batch left out (the mean over the rest)."""
    driver, sound = _train_numbers()
    assert _fails(sound) == [], sound
    want = driver.reference()
    control = dict(zip(("losses", "grad", "params"),
                       driver.reference(mode="fp8")))
    assert _fails(driver.compare(control, want)), "the fp8 control passed"
    half = dict(zip(("losses", "grad", "params"),
                    driver.reference(rows=slice(0, 8))))
    assert _fails(driver.compare(half, want)), "half a batch passed"


def _break_step(monkeypatch, broken):
    """The program's train step replaced, before the engine jits it."""
    from analytics_zoo_tpu.orca.learn.spmd import SPMDEngine
    sound = SPMDEngine._train_step_impl
    monkeypatch.setattr(
        SPMDEngine, "_train_step_impl",
        lambda self, state, batch, guard=True: broken(
            lambda s, b: sound(self, s, b, guard), state, batch))


def _unchanged(step, state, batch):
    """A step that returns its state as it got it."""
    return state, step(state, batch)[1]


def _halved(step, state, batch):
    """The step's mask drops the second half of every batch, so its
    mean is over the rest."""
    mask = batch["mask"]
    return step(state, dict(batch,
                            mask=mask.at[mask.shape[0] // 2:].set(0)))


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    _break_step(monkeypatch, _unchanged)
    _, numbers = _train_numbers()
    assert numbers["update_norm_gap_max"] == pytest.approx(1.0, abs=1e-6)
    assert "update_norm_gap_max" in _fails(numbers)


def test_train_half_of_the_batch_left_out_in_the_program(monkeypatch):
    _break_step(monkeypatch, _halved)
    _, numbers = _train_numbers()
    assert _fails(numbers), numbers


@pytest.mark.parametrize("fault", [_unchanged, _halved])
def test_a_whole_train_run_with_the_step_broken_is_not_correct(
        monkeypatch, fault):
    """The harness's look for a chip skipped, the rest of a run driven
    with the timed path broken underneath."""
    _break_step(monkeypatch, fault)
    line = one_run(TRAIN)
    assert line["attempted"] > 0 and line["correct"] is False
