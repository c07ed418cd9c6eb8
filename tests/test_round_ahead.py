"""A serving round collected one round late (serving/generation/
engine.py): the engine enqueues the next decode step from the resident
lane state before it has fetched the last one's tokens, the step stops
a lane that is done, and whatever needs the host exact collects what is
in flight first.  Greedy requests get the tokens the parent served,
request by request; a lane that sampled its `eos` computes nothing
further; a block appended with a round in flight moves neither the
lane's pending token nor its position; the two counters say how often
the mechanism engaged and why it did not."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import _lane_cases as lane_cases
import _round_cases as cases
from analytics_zoo_tpu.observability import MetricsRegistry
from analytics_zoo_tpu.serving.generation import (
    GenerationEngine,
    lane_state,
)
from analytics_zoo_tpu.serving.generation.engine import DRAINS
from analytics_zoo_tpu.serving.generation.scheduler import Sequence

PARENT = os.path.join(os.path.dirname(__file__), "data",
                      "round_ahead_parent_tokens.json")
#: scenario -> the reasons its engine may drain for besides `idle`,
#: the first of which it has to show (none: a plain engine)
EXACT = {"length": (), "eos": (), "join": (), "readmit": (),
         "preempt": ("preempt",), "prefix_cache": ("chunk", "cow"),
         "chunked": ("chunk",), "speculation": ("verify",),
         "host_tier": ("host_restore", "chunk", "cow")}


@pytest.fixture(scope="module")
def models():
    return {"causal": lane_cases.causal_lm(),
            "decoder": lane_cases.decoder_lm()}


@pytest.fixture(scope="module")
def parent_tokens():
    with open(PARENT) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(models):
    """Each scenario run once on each decoder; the tests look at it."""
    runs = {}

    def run(kind, name):
        if (kind, name) not in runs:
            engines = []
            out = cases.serve(kind, name, models, engines.append)
            runs[kind, name] = (out, engines[0])
        return runs[kind, name]
    return run


def counts(engine):
    """(decode rounds, rounds enqueued ahead, drains by reason)."""
    value = engine.registry.counter
    return (engine._h_decode.calls,
            value("generation_rounds_ahead_total").value,
            {r: value(f"generation_pipeline_drains_total_{r}").value
             for r in DRAINS})


@pytest.mark.parametrize("scenario", list(cases.SCENARIOS))
@pytest.mark.parametrize("kind", list(cases.MODELS))
def test_greedy_requests_get_the_parents_tokens(kind, scenario, served,
                                                parent_tokens):
    """Length stop, `eos` stop (the prefill's own token among them), a
    request joining mid-stream, a lane freed and taken again, pool
    pressure that preempts, and each default-off feature on: request
    by request what the parent served, with its preemptions and one
    compiled decode step."""
    out, _ = served(kind, scenario)
    assert out == parent_tokens[kind][scenario]


@pytest.mark.parametrize("scenario", list(cases.SCENARIOS))
@pytest.mark.parametrize("kind", list(cases.MODELS))
def test_rounds_run_ahead_unless_the_round_needs_the_host_exact(
        kind, scenario, served):
    """A plain engine enqueues nine rounds in ten while the one before
    is uncollected, and drains for nothing but the end of its work; a
    preemption is a drain; an engine with a default-off feature on
    never runs ahead and names the feature each time it collects."""
    _, engine = served(kind, scenario)
    rounds, ahead, drains = counts(engine)
    reasons = EXACT[scenario]
    assert rounds >= 10
    assert not engine._in_flight
    assert {r for r, n in drains.items() if n} - {"idle"} <= set(reasons)
    if not reasons:
        assert ahead / rounds > 0.9
    elif reasons == ("preempt",):
        assert ahead / rounds > 0.7
        assert drains["preempt"] >= engine.scheduler.n_preemptions >= 1
    else:
        assert ahead == 0
        assert drains[reasons[0]] >= 1
        assert sum(drains[r] for r in reasons) >= 10


def tapped_decode(engine, on_dispatch):
    """Wrap the decode program: `on_dispatch(args, out)` a dispatch."""
    decode = engine._decode_jit

    def tapped(*args):
        out = decode(*args)
        on_dispatch(args, out)
        return out

    tapped._cache_size = decode._cache_size
    engine._decode_jit = tapped


def plain_engine(models, kind="causal", **options):
    model, params = models[kind]
    engine = GenerationEngine(model, params, registry=MetricsRegistry(),
                              seed=3, **dict(cases.GRID, **options))
    engine.warmup()
    return engine


@pytest.mark.parametrize("kind", list(cases.MODELS))
def test_a_lane_that_samples_its_eos_is_stopped_by_the_step(
        kind, models, parent_tokens):
    """The round in which a lane samples its `eos` hands the lane back
    inactive, before the host has seen the token; the round after it,
    enqueued with the lane's request still running on the host, leaves
    every pool row of the lane's blocks as it was and the lane's row
    where it stopped."""
    free, other = parent_tokens[kind]["eos"]["tokens"][:2]
    first, second = cases.requests(2, cases.MODELS[kind], (24, 24))
    # a token the request first samples in a decode round
    stop_at = next(k for k in range(3, len(free))
                   if free[k] not in free[:k])
    engine = plain_engine(models, kind)
    seen = []                             # (rows out, pool out, blocks)
    try:
        stream = engine.submit(**first, eos_id=free[stop_at])
        beside = engine.submit(**second)
        tapped_decode(engine, lambda args, out: seen.append(
            (np.asarray(out[4]["rows"]), out[0],
             list(stream.seq.block_table), stream.seq.status)))
        engine.run_until_idle()
        assert stream.tokens() == free[:stop_at + 1]
        assert stream.finish_reason == "eos"
        assert beside.tokens() == other
    finally:
        engine.stop()
    lane = 0
    # decode round n samples token n + 1 of the lane
    rows, pool, blocks, _ = seen[stop_at - 1]
    assert rows[lane, lane_state.ACTIVE] == 0
    assert rows[lane, lane_state.CTX] == 0     # and reads no block
    assert rows[lane, lane_state.TOKEN] == free[stop_at]
    assert seen[stop_at - 2][0][lane, lane_state.ACTIVE] == 1
    after_rows, after_pool, _, status = seen[stop_at]
    assert status == "running"            # the host had not seen it
    np.testing.assert_array_equal(
        after_rows[lane, :lane_state.EOS], rows[lane, :lane_state.EOS])
    bs = cases.GRID["block_size"]
    for block in blocks:
        np.testing.assert_array_equal(
            np.asarray(pool[:, :, block * bs:(block + 1) * bs]),
            np.asarray(after_pool[:, :, block * bs:(block + 1) * bs]))
    # and the pool did move where the neighbour wrote
    assert not np.array_equal(np.asarray(pool), np.asarray(after_pool))


def test_a_block_appended_with_a_round_in_flight(models):
    """Every fourth round a lane needs a block while the round before
    is uncollected: the patch carries the scheduler's columns alone,
    the table on the device grows, and the pending token and position
    stay the step's — a round ahead of the lane's `Sequence`."""
    engine = plain_engine(models)
    owned = []

    def on_dispatch(args, out):
        rows, patch = np.asarray(args[3]["rows"]), np.asarray(args[4])
        if patch[0, 0] == lane_state.OWNED:
            applied = np.asarray(lane_state.patched(
                jnp.asarray(rows), jnp.asarray(patch)))
            owned.append((rows[0], applied[0],
                          list(stream.seq.block_table),
                          stream.seq.context_len))

    try:
        request, = cases.requests(11, 61, (30,), 6, 7)
        stream = engine.submit(**request)
        tapped_decode(engine, on_dispatch)
        engine.run_until_idle()
        tokens = stream.tokens()
    finally:
        engine.stop()
    assert len(owned) >= 6
    for before, after, table, host_len in owned:
        head = slice(0, lane_state.EOS)
        np.testing.assert_array_equal(after[head], before[head])
        # the device is a position past the lane's Sequence
        assert before[lane_state.CTX] == host_len
        held = after[lane_state.TABLE:lane_state.TABLE + len(table)]
        np.testing.assert_array_equal(held, table)
        grown = before[lane_state.TABLE:lane_state.TABLE + len(table)]
        assert grown[-1] == 0 and held[-1] != 0
        # the write this round makes lands in the new block
        assert before[lane_state.CTX] // cases.GRID["block_size"] \
            == len(table) - 1
    other = plain_engine(models, prefix_caching=True)   # collects each
    try:
        assert other.generate(**request) == tokens
    finally:
        other.stop()


@pytest.mark.parametrize("seed", range(4))
def test_the_mirror_steps_as_the_device_does(seed):
    """`advanced`/`admitted` on the device and `advance()`/`landed()`
    on the mirror are one arithmetic: live lanes take the token, move
    on and count down, and stop at their last token or their `eos`;
    a stopped lane keeps no context; the scheduler's columns and the
    dead lanes do not move."""
    rng = np.random.default_rng(seed)
    lanes, width = 16, lane_state.TABLE + 4
    rows = rng.integers(0, 50, (lanes, width)).astype(np.int32)
    rows[:, lane_state.ACTIVE] = rng.integers(0, 2, lanes)
    rows[:, lane_state.LEFT] = rng.integers(1, 4, lanes)
    rows[:, lane_state.EOS] = rng.choice([-1, 7], lanes)
    nxt = rng.choice([7, 9], lanes).astype(np.int32)
    want = rows.copy()
    for i in range(lanes):
        if rows[i, lane_state.ACTIVE]:
            last = rows[i, lane_state.LEFT] == 1 \
                or nxt[i] == rows[i, lane_state.EOS]
            want[i, :lane_state.EOS] = (
                nxt[i], 0 if last else rows[i, lane_state.CTX] + 1,
                0 if last else 1, rows[i, lane_state.LEFT] - 1)
    device = np.asarray(lane_state.advanced(jnp.asarray(rows),
                                            jnp.asarray(nxt)))
    np.testing.assert_array_equal(device, want)

    class Scheduler:
        max_slots, max_blocks_per_seq, touched = lanes, 4, set()

    state = lane_state.LaneState(Scheduler, 0, jnp.asarray,
                                 MetricsRegistry())
    state.mirror[:] = rows
    live = state.advance(nxt)
    np.testing.assert_array_equal(state.mirror, want)
    np.testing.assert_array_equal(live, rows[:, lane_state.ACTIVE] != 0)
    # a prefill's row: stepped by its token, but for the position
    row = rows[0].copy()
    row[lane_state.ACTIVE] = 1
    placed = np.asarray(lane_state.admitted(
        jnp.asarray(rows), jnp.int32(3), jnp.asarray(row), nxt[0]))
    state.mirror[3, lane_state.EOS:] = row[lane_state.EOS:]
    state.landed(3, row[:lane_state.EOS], int(nxt[0]))
    np.testing.assert_array_equal(placed[3], state.mirror[3])
    last = row[lane_state.LEFT] == 1 or nxt[0] == row[lane_state.EOS]
    assert list(placed[3, :lane_state.EOS]) == [
        nxt[0], 0 if last else row[lane_state.CTX], 0 if last else 1,
        row[lane_state.LEFT] - 1]


@pytest.mark.parametrize("generated,in_flight,spent",
                         [(0, 0, False), (3, 0, False), (3, 1, True),
                          (2, 1, False), (2, 2, True), (4, 0, True)])
def test_a_sequence_is_spent_once_its_last_token_is_in_flight(
        generated, in_flight, spent):
    seq = Sequence([1, 2, 3], max_new_tokens=4)
    seq.generated = [5] * generated
    seq.in_flight = in_flight
    assert seq.spent is spent


def test_capacity_is_grown_for_where_the_device_is(models):
    """`ensure_decode_capacity` and `decode_blocks_short` count a
    lane's position from its `Sequence` plus what is in flight, and
    leave a spent lane alone."""
    engine = plain_engine(models, num_blocks=9)     # 8 allocatable
    try:
        sched = engine.scheduler
        stream = engine.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=9)
        engine.step()         # its first token, and a round in flight
        seq = stream.seq
        assert seq.in_flight == 1 and seq.context_len == 8
        assert len(seq.block_table) == 2        # positions 0..7
        assert sched.decode_blocks_short() == 1 - 6
        sched.ensure_decode_capacity()          # the write at 8
        assert len(seq.block_table) == 3
        seq.in_flight = 8                       # its last in flight
        assert seq.spent
        assert sched.decode_blocks_short() == -5
        sched.ensure_decode_capacity()
        assert len(seq.block_table) == 3
        seq.in_flight = 1
        assert len(engine.generate([9, 8, 7], max_new_tokens=2)) == 2
        assert len(stream.tokens()) == 9
    finally:
        engine.stop()
