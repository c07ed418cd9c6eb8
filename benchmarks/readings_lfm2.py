"""`readings.py` for the cell of `drivers/serve_closed_lfm2.py`: a short
window at the cell's own load, then the program's numbers as `correct`
compares them and the control's — `fp8` (e4m3 matmul operands in the
reference: the logit gaps and the convolution tails) — with every
looked-at position's (routing margin, logit gap) pair kept, so that the
epsilon and the limits can be read off one run, and the tails' gap of
every convolution layer, over all probes and over the steady probe.

    python3 benchmarks/readings_lfm2.py --workload lfm2_serve_decode --seeds 1 [--seconds 30] [--controls fp8] [--out file.jsonl]

One seed a process on the chip: 10.6 GB of weights are not all given
back between two."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readings  # noqa: E402


def serve_readings(driver, seconds: float, controls) -> dict:
    driver.keep_pairs = True
    out = readings.serve_readings(driver, seconds, controls)
    out["state"] = driver.result["state"]
    # probe by convolution layer; the last probe is the steady request
    for mode in ["f32"] + list(controls):
        gaps = driver.tail_gaps(mode)
        out["tail_gap_" + mode] = {
            "clear": max((g for row in gaps
                          for g in row[:driver.clear_state_layers()]),
                         default=0.0),
            "by_layer": [max(col) for col in zip(*gaps)],
            "steady_by_layer": gaps[-1] if gaps else []}
    return out


readings.KINDS["serve_closed_lfm2"] = serve_readings

if __name__ == "__main__":
    readings.main()
