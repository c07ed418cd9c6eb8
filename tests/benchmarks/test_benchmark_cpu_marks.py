"""The program's CPU marks reduced to the eleven metrics that read
them (`harness/cpu_marks.py`), on a cut made by hand.

`data/cpu_marks_cut.json`: one device line of four operations and one
`python3` host line with the engine loop's spans of four rounds.  The
first (200-800 µs) is the round the session caught half-way, with no
mark; then two whole rounds, each with its ``azt:cpu.loop[...]`` mark
behind it; then a round the trace's end cut, with none.  The first mark
(at 7,050 µs, `wall` 6,050) covers its own round from 1,000 µs on, the
second (at 11,000 µs, `wall` 3,950) reaches back to the first: the
marks' stretches are 1,000-7,050 and 7,050-11,000 µs and the two other
rounds lie outside them.  Across the rounds lie two handlers' spans with a ``cpu.handler``
mark each and one ``cpu.client`` mark.  Every number below is worked
out from the file by hand."""

import json
import os

import pytest

from _bench_toy import ROOT, bench_run  # noqa: F401  (the repo on the path)
from benchmarks.harness import cpu_marks, span_metrics, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HAND = os.path.join(DATA, "cpu_marks_cut.json")
#: programs from before the marks: the hand-made span cut, the chip's
#: first trace with spans (PR 28), and PR 27's program without any
NO_MARKS = ["span_cut.json", "span_cut_chip_decode.json",
            "serve_trace_cut.json"]
CPU = ["loop_cpu_ms." + p for p in cpu_marks.CPU_PHASES]
READERS = (["loop_round_ms"] + CPU + ["loop_off_cpu_ms",
           "handler_cpu_us_per_token", "client_cpu_us_per_token"])
OLDER = (["serve_idle." + p for p in cpu_marks.CPU_PHASES]
         + ["device_idle_share.serve", "decode_lanes_mean",
            "queue_depth_mean", "prefill_time_share"])


def ctx_of(path):
    return {"trace": trace_reduce.Trace.from_json(path)}


def read(name, ctx):
    return bench_run.load_module("readers", name).read(ctx)


def test_every_new_metric_has_its_reader_and_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    serving = [w["name"] for w in manifest["workloads"]
               if w["name"] != "bert_base_finetune"]
    assert len(READERS) == 11
    for name in READERS:
        assert entries[name]["workloads"] == serving, name
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["better"] == "lower"


def test_the_marks_are_parsed_in_their_order():
    trace = trace_reduce.Trace.from_json(HAND)
    loop = cpu_marks.marks(trace, "loop")
    assert [start for start, _ in loop] == [7050000, 11000000]
    assert list(loop[0][1]) == ["wall", *cpu_marks.CPU_PHASES]
    assert [f["tokens"] for _, f in cpu_marks.marks(trace, "handler")] \
        == [120, 80]
    assert cpu_marks.marks(trace, "nobody") == []


def test_a_round_by_hand():
    """Marks: wall 6,050 + 3,950 µs over two rounds is 5.0 ms a round;
    CPU by phase (400+280, 900+0, 1000+600, 50+40, 380+560, 390+580,
    30+140 µs) halved."""
    ctx = ctx_of(HAND)
    assert read("loop_round_ms", ctx) == pytest.approx(5.0)
    want = {"schedule": 0.34, "prefill_host": 0.45, "dispatch": 0.8,
            "fetch": 0.045, "account": 0.47, "emit": 0.485,
            "off_round": 0.085}
    for phase, ms in want.items():
        assert read("loop_cpu_ms." + phase, ctx) == pytest.approx(ms), phase
    assert sum(want.values()) == pytest.approx((3150 + 2200) / 2 / 1e3)
    assert cpu_marks.loop(ctx["trace"])["rounds"] == 2


def test_off_cpu_by_hand():
    """Wall by phase from the spans inside 1,000-11,000 µs, in µs.

    round 1: schedule = the round's own 10 + 80 + 50 + 100 + 100, admit
    100, capacity 50 = 490; prefill_host = 800 (the enqueue) + 600 (the
    collection inside the decode span) = 1,400; dispatch = the decode
    span's own 50 + 100 + 100 + 100, stage 250, and the dispatch span,
    2,000 wide with 500 µs of the phase's 1,000 of CPU = 2,600; fetch
    700; account 300 + 100 = 400; emit 400.  They add up to the round's
    5,990.
    round 2: schedule 10 + 50 + 50 + 100 + 40 + 50 = 300; dispatch 20 +
    100 + 200 + 80 + 1,000 = 1,400; fetch 500; account 600; emit 600:
    3,400.

    A round: schedule 0.395 ms of wall less 0.34 of CPU, prefill_host
    0.7 - 0.45, dispatch 2.0 - 0.8, account 0.5 - 0.47, emit 0.5 -
    0.485: 1.55 ms.  `fetch` (0.6 of wall, 0.045 of CPU) waits for the
    device and is left out; the half round before the stretch (an
    `emit` of 400) and the cut round behind it (a `dispatch` of 300)
    are not in it."""
    ctx = ctx_of(HAND)
    loop = cpu_marks.loop(ctx["trace"])
    walls = {"schedule": 0.395, "prefill_host": 0.7, "dispatch": 2.0,
             "fetch": 0.6, "account": 0.5, "emit": 0.5}
    for phase, ms in walls.items():
        assert loop["wall." + phase] == pytest.approx(ms), phase
    assert read("loop_off_cpu_ms", ctx) == pytest.approx(1.55)
    assert "fetch" not in cpu_marks.RUNNABLE


def test_stretches_that_lie_apart_leave_out_what_lies_between():
    """The program clocks one round of every few: a mark reaches back
    to the end of the round before its own, not to the mark before it.
    The second mark's `wall` cut to 3,400 µs, its stretch begins at
    7,600: the round's first 10 µs, its `admit` of 40 and the 50 behind
    that are now between the stretches, 100 µs of `schedule` less over
    two rounds, and a round is (6,050 + 3,400) / 2 µs."""
    with open(HAND) as f:
        cut = json.load(f)
    host = cut["planes"]["/host:CPU"]
    host["python3"] = [[e[0].replace("wall=3950", "wall=3400"), *e[1:]]
                       for e in host["python3"]]
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in cut["planes"].items()}
    ctx = {"trace": trace_reduce.Trace(planes, 1, cut["window_s"])}
    loop = cpu_marks.loop(ctx["trace"])
    assert read("loop_round_ms", ctx) == pytest.approx(4.725)
    assert loop["wall.schedule"] == pytest.approx(0.345)
    assert loop["wall.dispatch"] == pytest.approx(2.0)
    assert read("loop_off_cpu_ms", ctx) == pytest.approx(1.5)


def test_cpu_a_token_is_a_sum_over_a_sum():
    """Handlers: (9,600 + 4,400) µs over 120 + 80 tokens = 70 (the
    mean of 80 and 55 would be 67.5); the one client 6,000 / 120."""
    ctx = ctx_of(HAND)
    assert read("handler_cpu_us_per_token", ctx) == pytest.approx(70.0)
    assert read("client_cpu_us_per_token", ctx) == pytest.approx(50.0)


def test_loop_marks_alone_read_no_request():
    with open(HAND) as f:
        cut = json.load(f)
    host = cut["planes"]["/host:CPU"]
    host["python3"] = [e for e in host["python3"]
                       if not e[0].startswith(("azt:cpu.handler",
                                               "azt:cpu.client"))]
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in cut["planes"].items()}
    ctx = {"trace": trace_reduce.Trace(planes, 1, cut["window_s"])}
    assert read("handler_cpu_us_per_token", ctx) is None
    assert read("client_cpu_us_per_token", ctx) is None
    assert read("loop_round_ms", ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("cut", NO_MARKS)
@pytest.mark.parametrize("name", READERS)
def test_a_program_without_marks_reads_nothing(name, cut):
    """The parent's program under this benchmark: no reader raises,
    none reads 0."""
    assert read(name, ctx_of(os.path.join(DATA, cut))) is None


@pytest.mark.parametrize("name", OLDER)
def test_the_older_readers_do_not_see_the_marks(name):
    with open(HAND) as f:
        cut = json.load(f)
    host = cut["planes"]["/host:CPU"]
    host["python3"] = [e for e in host["python3"]
                       if not e[0].startswith(cpu_marks.MARKS)]
    assert len(host["python3"]) == 44 - 5
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in cut["planes"].items()}
    bare = {"trace": trace_reduce.Trace(planes, 1, cut["window_s"])}
    value = read(name, ctx_of(HAND))
    assert value is not None and value == read(name, bare)
    assert not [s for s in span_metrics.engine_spans(ctx_of(HAND)["trace"])
                if s.name.startswith("cpu")]
