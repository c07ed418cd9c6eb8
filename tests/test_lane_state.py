"""The lane state resident on the device (serving/generation/
lane_state.py): a seeded engine serves what it served before the state
moved there, the device's rows equal the host mirror and the
scheduler's truth after every round (the scheduler's columns as of the
round enqueued, the step's as of the round collected), the steps split
the key where the host used to, a steady round uploads nothing, and a
failed round loses nothing."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _lane_cases as cases
from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.observability import MetricsRegistry
from analytics_zoo_tpu.resilience.faults import FaultInjected
from analytics_zoo_tpu.serving.generation import (
    GenerationEngine,
    lane_state,
    sample_tokens,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
PARENT = os.path.join(DATA, "lane_state_parent_tokens.json")
#: what the engine serves since a round is collected one round late
#: (tests/_lane_cases.py says how it was made and where it differs)
RECORDED = os.path.join(DATA, "round_ahead_tokens.json")


@pytest.fixture(scope="module")
def models():
    return {"causal": cases.causal_lm(), "decoder": cases.decoder_lm()}


@pytest.fixture(scope="module")
def parent_tokens():
    with open(PARENT) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded_tokens():
    with open(RECORDED) as f:
        return json.load(f)


def expected_rows(engine):
    """Every lane's row as the scheduler's sequences say it should be,
    written without the module under test."""
    sched = engine.scheduler
    rows = np.zeros((sched.max_slots, 7 + sched.max_blocks_per_seq),
                    np.int32)
    for seq in sched.running():
        row = rows[seq.slot]
        row[0] = (seq.generated or seq.prompt)[-1]
        row[1] = seq.context_len - 1
        row[2] = 1
        row[3] = seq.max_new_tokens - len(seq.generated)
        row[4] = -1 if seq.eos_id is None else seq.eos_id
        row[5] = np.array(seq.temperature, np.float32).view(np.int32)
        row[6] = seq.top_k
        row[7:7 + len(seq.block_table)] = seq.block_table
    return rows


#: a row's first column that is the scheduler's
OWNED = lane_state.EOS


class Tap:
    """Wraps the three programs that consume a key and records, for
    each dispatch in order, what it sampled from and what it sampled;
    after every round compares the device's rows with the mirror and
    with the scheduler: the scheduler's columns always, the step's
    where nothing of the lane's is in flight but the round just
    enqueued."""

    def __init__(self):
        #: a dispatch: (logits [n, vocab], temperature [n], top_k [n],
        #: sampled [n], live [n]) over the n lanes it sampled for
        self.dispatches = []
        self.faults = []
        self.rounds = 0

    def on_engine(self, engine):
        self.engine = engine
        self.key = jnp.asarray(engine._lanes.key())
        width = engine._lanes.width
        prefill, decode, chunk = (engine._prefill_jit, engine._decode_jit,
                                  engine._chunk_jit)

        def on_prefill(params, kv, scale, state, request):
            out = prefill(params, kv, scale, state, request)
            _, row, _ = lane_state.split_request(np.asarray(request),
                                                 width)
            _, _, _, temperature, top_k, _ = lane_state.fields(row)
            self.dispatches.append(
                (out[3][None], temperature[None], top_k[None],
                 out[2][None], np.ones(1, bool)))
            return out

        def on_decode(params, kv, scale, state, patch):
            rows = lane_state.patched(state["rows"], patch)
            _, _, live, temperature, top_k, _ = lane_state.fields(rows)
            out = decode(params, kv, scale, state, patch)
            self.dispatches.append((out[3], temperature, top_k, out[2],
                                    np.asarray(live)))
            return out

        def on_chunk(*args):
            out = chunk(*args)
            self.dispatches.append((out[3][None], args[7], args[8],
                                    out[2][None], np.ones(1, bool)))
            return out

        on_decode._cache_size = decode._cache_size
        engine._prefill_jit, engine._decode_jit, engine._chunk_jit = \
            on_prefill, on_decode, on_chunk

    def after_round(self, engine):
        self.rounds += 1
        device = np.asarray(engine._lanes.state["rows"])
        mirror = engine._lanes.mirror
        ahead = bool(engine._in_flight)
        at = slice(OWNED, None) if ahead else slice(None)
        if not np.array_equal(device[:, at], mirror[:, at]):
            self.faults.append((self.rounds, "device != mirror", ahead,
                                np.argwhere(device != mirror)[:4]))
        settled = [i for i in range(len(mirror))
                   if i not in engine.scheduler.touched]
        want = expected_rows(engine)
        if not np.array_equal(mirror[settled], want[settled]):
            self.faults.append((self.rounds, "mirror != scheduler",
                                mirror[settled], want[settled]))


@pytest.fixture(scope="module")
def served(models):
    """Each case run once, tapped; the tests below look at the run."""
    runs = {}

    def run(name):
        if name not in runs:
            tap = Tap()
            stop = cases.tp2_context() if name == "tp2" else None
            try:
                out = cases.serve(name, models, tap.on_engine,
                                  tap.after_round)
            finally:
                if stop is not None:
                    stop()
            runs[name] = (out, tap)
        return runs[name]
    return run


@pytest.mark.parametrize("case", list(cases.CASES))
def test_serves_the_tokens_the_parent_served(case, served, parent_tokens,
                                             recorded_tokens):
    """Token for token: greedy and temperature/top-k lanes mixed,
    lanes joining and leaving in waves, a block boundary every fourth
    round, and with them a preemption and resume, the int8 pool, the
    concat oracle, prefix-cache reuse, chunked prefill, speculation,
    `DecoderLM`, a tp=2 placement.  Every greedy request gets the
    tokens the commit before the device-resident lane state served;
    so does every sampled one where a default-off feature holds the
    engine to collecting each round before the next.  In the other
    cases a freed lane is admitted into a round later than it was, so
    the key is split in another order and the sampled requests'
    tokens are the ones recorded since (as long, every one)."""
    out, _ = served(case)
    want, parent = recorded_tokens[case], parent_tokens[case]
    assert out["tokens"] == want["tokens"]
    assert out["preemptions"] == parent["preemptions"]
    assert out["decode_compile_count"] == 1
    _, requests, options = cases.CASES[case]
    exact = set(options) & {"prefix_caching", "chunked_prefill",
                            "speculative_decoding"}
    for request, got, was in zip(requests, out["tokens"],
                                 parent["tokens"]):
        if exact or request["temperature"] == 0:
            assert got == was
        assert len(got) == len(was)


@pytest.mark.parametrize("case", list(cases.CASES))
def test_device_rows_equal_the_mirror_and_the_scheduler(case, served):
    """After every round of the run: the rows on the device are the
    host mirror's, and every lane the scheduler has not touched since
    reads as its sequence says — a change that reached neither the
    step nor `touched` would show here."""
    _, tap = served(case)
    assert tap.rounds > 10
    assert tap.faults == []
    # the run's end collected everything: the two tenses are one
    lanes = tap.engine._lanes
    assert not tap.engine._in_flight
    np.testing.assert_array_equal(np.asarray(lanes.state["rows"]),
                                  lanes.mirror)


@pytest.mark.parametrize("case", list(cases.CASES))
def test_steps_split_the_key_where_the_host_used_to(case, served):
    """One `jax.random.split` a prefill, chunk and decode dispatch, in
    dispatch order, the second half to `sample_tokens`: replayed here
    with eager splits from the engine's seed over the logits each
    dispatch handed back."""
    _, tap = served(case)
    key = tap.key
    assert tap.dispatches
    for n, (logits, temperature, top_k, got, live) in enumerate(
            tap.dispatches):
        key, sub = jax.random.split(key)
        want = sample_tokens(logits, sub, temperature, top_k)
        np.testing.assert_array_equal(
            np.asarray(got)[live], np.asarray(want)[live],
            err_msg=f"dispatch {n}")
    np.testing.assert_array_equal(
        np.asarray(tap.engine._lanes.state["rng"]), np.asarray(key))


def test_most_rounds_send_few_rows_and_some_send_none(served):
    """The two counters: rows uploaded, and rounds that uploaded any,
    against the decode rounds.  Four lanes at block size 4 cross a
    boundary each every fourth round, so about one row a round and
    well under the four a full rebuild would send."""
    _, tap = served("mixed")
    registry = tap.engine.registry
    rows = registry.counter("generation_lane_rows_sent_total").value
    syncs = registry.counter("generation_lane_sync_rounds_total").value
    decodes = tap.engine._h_decode.calls
    assert 0 < syncs < decodes
    assert rows < 2 * decodes


def small_engine(models, block_size=16):
    model, params = models["causal"]
    engine = GenerationEngine(model, params, registry=MetricsRegistry(),
                              seed=cases.SEED, max_slots=4,
                              block_size=block_size, max_context=64)
    engine.warmup()
    return engine


def test_a_steady_round_uploads_nothing_and_a_dirty_one_one_array(models):
    """Three lanes mid-block, greedy and sampled: a round in which the
    scheduler changed nothing runs with every host-to-device transfer
    forbidden; a round after an admission and one after a block
    boundary upload one array each, through the lane state's `_put`."""
    engine = small_engine(models)
    uploads = []
    put = engine._lanes._put
    engine._lanes._put = lambda x: uploads.append(x.shape) or put(x)
    try:
        streams = [engine.submit([1, 2, 3, 4, 5], max_new_tokens=20,
                                 temperature=t) for t in (0.0, 0.9, 0.0)]
        engine.step()                 # three prefills, the first decode
        uploads.clear()
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            for _ in range(4):
                engine.step()
        assert uploads == []
        # positions 5..15 lie in the first block; the write at 16 (the
        # round that makes the context 18 long) needs a second one:
        # every lane's table grows in that round
        with jax.transfer_guard_host_to_device("disallow"):
            while engine.scheduler.running()[0].context_len < 18:
                engine.step()
        width = engine._lanes.width
        assert uploads == [(4, 1 + width)]
        uploads.clear()
        # an admission: the prompt and its row go up as one array, and
        # the decode of the same round has nothing left to send
        late = engine.submit([7, 8, 9], max_new_tokens=3)
        with jax.transfer_guard_host_to_device("disallow"):
            engine.step()
        bucket = engine.scheduler.bucket_for(3)
        assert uploads == [(1 + width + bucket,)]
        engine.run_until_idle()
        assert [len(s.tokens()) for s in streams + [late]] == [20] * 3 + [3]
        assert engine.decode_compile_count == 1
    finally:
        engine.stop()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_copy_on_write_swap_reaches_the_device(models, temperature):
    """A block in a lane's write path shared by a fork: the scheduler
    swaps the table entry, the swapped row goes up before the step
    writes, and the lane serves what it serves with no fork."""
    model, params = models["causal"]

    def run(fork):
        engine = GenerationEngine(
            model, params, registry=MetricsRegistry(), seed=cases.SEED,
            max_slots=2, block_size=8, max_context=64,
            prefix_caching=True)
        engine.warmup()
        try:
            stream = engine.submit(list(range(3, 15)), max_new_tokens=10,
                                   temperature=temperature)
            engine.step()               # the prompt's chunk, a decode
            seq = stream.seq
            index = (seq.context_len - 1) // 8
            block = seq.block_table[index]
            if fork:
                engine.cache.allocator.share([block])
            engine.step()
            if fork:
                assert engine._c_cow.value == 1
                assert seq.block_table[index] != block
                engine.cache.allocator.free([block])
            assert not engine.scheduler.touched
            engine._drain("idle")       # the round just enqueued
            np.testing.assert_array_equal(
                np.asarray(engine._lanes.state["rows"]),
                expected_rows(engine))
            engine.run_until_idle()
            return stream.tokens()
        finally:
            engine.stop()

    assert run(fork=True) == run(fork=False)


@pytest.mark.parametrize("action", ["raise", "poison_request"])
def test_a_failed_round_loses_no_lane(models, action):
    """An injected error at the decode dispatch, and a `poison_request`
    fault that evicts one lane: the patch the failed round had built
    never reached the device, so every row is sent again and the
    surviving lanes serve what they serve with no fault."""
    prompts = {f"lane-{j}": [3 + j, 9, 27, 20, 11, 6][:4 + j]
               for j in range(3)}

    def run(fault):
        engine = small_engine(models, block_size=4)
        OrcaContext.fault_plan = fault and {"faults": [dict(
            fault, site="generation.decode", at=3)]}
        try:
            streams = {rid: engine.submit(p, max_new_tokens=9,
                                          request_id=f"{rid}-{action}")
                       for rid, p in prompts.items()}
            errors = 0
            while engine.scheduler.has_work():
                try:
                    engine.step()
                except FaultInjected:
                    errors += 1
            out = {rid: s.tokens() for rid, s in streams.items()}
            assert engine.decode_compile_count == 1
            np.testing.assert_array_equal(
                np.asarray(engine._lanes.state["rows"]),
                engine._lanes.mirror)
            return out, errors
        finally:
            OrcaContext.fault_plan = None
            engine.stop()

    sound, _ = run(None)
    if action == "raise":
        got, errors = run(dict(action="raise"))
        assert errors == 1 and got == sound
    else:
        got, errors = run(dict(action="poison_request",
                               request_id=f"lane-1-{action}"))
        assert errors == 0             # evicted inside step()
        assert len(got["lane-1"]) < 9
        assert got["lane-0"] == sound["lane-0"]
        assert got["lane-2"] == sound["lane-2"]


def test_loop_serves_on_after_a_step_error(models):
    """The background loop's own handler: the lanes of the failed round
    are finished with the error, every row is marked for resending, and
    the next requests are served as a fresh engine serves them."""
    engine = small_engine(models)
    try:
        want = engine.generate([5, 6, 7, 8], max_new_tokens=6)
        OrcaContext.fault_plan = {"faults": [dict(
            site="generation.decode", at=2, action="raise")]}
        engine.ensure_started()
        hit = engine.submit([9, 10, 11], max_new_tokens=8)
        hit.tokens()
        assert hit.finish_reason.startswith("error")
        OrcaContext.fault_plan = None
        after = engine.submit([5, 6, 7, 8], max_new_tokens=6)
        assert after.tokens() == want
        assert engine.decode_compile_count == 1
    finally:
        OrcaContext.fault_plan = None
        engine.stop()
