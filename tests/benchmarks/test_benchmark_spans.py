"""The device's idle time put down to the program's spans
(`harness/span_metrics.py`), on two cuts.

`data/span_cut.json` is made by hand: one device line of four
operations, one `python3` host line that holds the engine loop's nested
``azt:generation.*`` spans of two rounds, the ``azt:serving.*`` spans of
two handler threads lying across them (the reader merges every Python
thread into that one line), three events of the runtime, and a fit's
``azt:spmd.*`` spans.  Every number below is worked out from it by hand.

`data/span_cut_chip_decode.json` is two rounds of the decode cell's
first traced window on the chip that had spans (PR 28; 85 ms, 32 lanes,
no prefill), `data/span_cut_chip_prefill.json` one round of the prefill
cell's (109 ms: two prefills and a decode round of 25 lanes), both cut
by `Trace.cut`; there the partition is checked against a plain sweep
over every boundary, never by the code under test."""

import json
import os

import pytest

from _bench_toy import ROOT, bench_run  # noqa: F401  (the repo on the path)
from benchmarks.harness import span_metrics, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HAND = os.path.join(DATA, "span_cut.json")
#: cut -> (decode rounds, their lanes, prefills)
CHIP = {"span_cut_chip_decode.json": (2, 32.0, 0),
        "span_cut_chip_prefill.json": (1, 25.0, 2)}
NO_SPANS = os.path.join(DATA, "serve_trace_cut.json")   # PR 27's program
IDLE = ["serve_idle." + p for p in span_metrics.PHASES + ("off_round",)]
SPAN_READERS = IDLE + ["decode_lanes_mean", "queue_depth_mean",
                       "prefill_time_share", "train_input_wait_ms"]


def ctx_of(path):
    return {"trace": trace_reduce.Trace.from_json(path)}


def read(name, ctx):
    return bench_run.load_module("readers", name).read(ctx)


def test_the_partition_by_hand():
    """Operations at 100-400, 500-900, 1000-1600, 2000-2300 ns of a
    2500 ns window: busy 1600, idle 36%.  The three gaps between them:

    400-500   prefill's fetch, account, emit, account and own time to
              480 (80 prefill_host); the round's own 480-485, capacity
              485-495, the round's own 495-496 (16 schedule); the
              decode span's own 496-498 and its stage 498-500
              (4 dispatch)
    900-1000  fetch to 940 (40), account to 960 (20), emit (40)
    1600-2000 fetch to 1610 (10), account to 1630 (20), emit to 1680
              (50), account to 1688 (8), the decode span's own to 1690
              (2 dispatch), the round's own to 1700 (10 schedule), then
              housekeeping and the wait for work (300, no round)

    and 300 ns before the first and after the last operation."""
    ctx = ctx_of(HAND)
    assert ctx["trace"].idle_share == pytest.approx(36.0)
    want = {"schedule": 26, "prefill_host": 80, "dispatch": 6,
            "fetch": 50, "account": 48, "emit": 90, "off_round": 600}
    for phase, ns in want.items():
        assert read("serve_idle." + phase, ctx) == pytest.approx(
            100.0 * ns / 2500, abs=1e-12), phase
    assert sum(read(name, ctx) for name in IDLE) == pytest.approx(
        read("device_idle_share.serve", ctx), abs=1e-9)


def test_a_gap_is_cut_not_given_whole():
    """900-1000 lies mostly under nothing in particular (40, 20, 40):
    winner-takes-all would hand `fetch` or `emit` the 100."""
    trace = trace_reduce.Trace.from_json(HAND)
    segments = span_metrics.phase_segments(span_metrics.engine_spans(trace))
    got = span_metrics.overlap_by_phase([(900, 1000)], segments)
    assert got == {"schedule": 0, "prefill_host": 0, "dispatch": 0,
                   "fetch": 40, "account": 20, "emit": 40}


def test_handlers_spans_do_not_count_as_the_loops():
    trace = trace_reduce.Trace.from_json(HAND)
    assert len(span_metrics.host_events(trace, "azt:serving.")) == 3
    names = {s.name for s in span_metrics.engine_spans(trace)}
    assert not any(n.startswith("serving") for n in names)
    assert {s.parents for s in span_metrics.engine_spans(trace)
            if s.name == "emit"} == {("round", "prefill"),
                                     ("round", "decode")}


def test_the_counts_ride_in_the_decode_spans_names():
    ctx = ctx_of(HAND)                    # decode[l=3,w=1], decode[l=2,w=0]
    assert read("decode_lanes_mean", ctx) == 2.5
    assert read("queue_depth_mean", ctx) == 0.5
    assert span_metrics.short("azt:generation.decode[l=32,w=99]") == "decode"


def test_prefill_time_share_by_hand():
    # one prefill of 330 ns; rounds of 1004 and 600 ns
    assert read("prefill_time_share", ctx_of(HAND)) == pytest.approx(
        100.0 * 330 / 1604)


def test_train_input_wait_by_hand():
    # pops of 10, 4 and 1 ns (the last finds the epoch at its end), 2 steps
    assert read("train_input_wait_ms", ctx_of(HAND)) == pytest.approx(
        15 / 2 / 1e6)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_trace_without_spans_reads_nothing(name):
    """The parent's program under this benchmark: no reader raises, no
    share reads 0."""
    assert read(name, ctx_of(NO_SPANS)) is None


def test_spans_without_a_device_plane_read_no_idle_share():
    with open(HAND) as f:
        cut = json.load(f)
    del cut["planes"]["/device:TPU:0"]
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in cut["planes"].items()}
    ctx = {"trace": trace_reduce.Trace(planes, 1, cut["window_s"])}
    assert all(read(name, ctx) is None for name in IDLE)
    assert read("decode_lanes_mean", ctx) == 2.5


# --- the recorded cut ----------------------------------------------------

def by_sweep(cut):
    """Idle nanoseconds by the innermost open `azt:generation.*` span's
    name, over every elementary interval between two boundaries."""
    device = cut["planes"]["/device:TPU:0"]["XLA Ops"]
    spans = [(n, s, s + d) for lines in cut["planes"]["/host:CPU"].values()
             for n, s, d in lines if n.startswith("azt:generation.")]
    first = min(s for _, s, _ in device)
    last = max(s + d for _, s, d in device)
    edges = sorted({first, last}
                   | {t for _, s, d in device for t in (s, s + d)}
                   | {t for _, s, e in spans for t in (s, e)})
    total = {}
    for a, b in zip(edges, edges[1:]):
        if a < first or b > last:
            continue
        if any(s <= a and b <= s + d for _, s, d in device if d > 0):
            continue
        open_ = [(s, n) for n, s, e in spans if s <= a and b <= e]
        # the innermost began last
        name = max(open_)[1] if open_ else None
        path = [n for _, n in sorted(open_)]
        total[(name, any("prefill" in n for n in path))] = total.get(
            (name, any("prefill" in n for n in path)), 0) + b - a
    return total


@pytest.mark.parametrize("name", sorted(CHIP))
def test_a_recorded_cut_adds_up(name):
    path = os.path.join(DATA, name)
    rounds, lanes, prefills = CHIP[name]
    with open(path) as f:
        cut = json.load(f)
    assert os.path.getsize(path) < 500_000
    ctx = ctx_of(path)
    shares = {name: read(name, ctx) for name in IDLE}
    assert all(v is not None and v >= 0 for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(
        read("device_idle_share.serve", ctx), abs=1e-9)

    want = dict.fromkeys(span_metrics.PHASES, 0)
    for (name, in_prefill), ns in by_sweep(cut).items():
        if name is None:
            continue
        leaf = span_metrics.short(name)
        phase = ("prefill_host" if in_prefill
                 else span_metrics.LEAF_PHASE.get(leaf))
        if phase is not None:
            want[phase] += ns
    window_ns = cut["window_s"] * 1e9
    for phase, ns in want.items():
        assert shares["serve_idle." + phase] == pytest.approx(
            100.0 * ns / window_ns, abs=1e-9), phase
    spans = span_metrics.engine_spans(ctx["trace"])
    assert sum(s.name == "decode" for s in spans) == rounds
    assert sum(s.name == "prefill" for s in spans) == prefills
    assert read("decode_lanes_mean", ctx) == lanes
    assert (shares["serve_idle.prefill_host"] > 0) == (prefills > 0)
    assert (read("prefill_time_share", ctx) > 0) == (prefills > 0)
    # most of the chip's wait is under the decode round's dispatch or,
    # where there are prefills, under them
    top = max(span_metrics.PHASES, key=lambda p: shares["serve_idle." + p])
    assert top == ("prefill_host" if prefills else "dispatch")
