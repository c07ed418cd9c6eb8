"""`readings.py` for the cell of `drivers/serve_closed_nemotron.py`: a
short window at the cell's own load, then the program's numbers as
`correct` compares them and each control's — `fp8` (the logit gaps) and
`bf16_state` (the logit gaps, which it does not move, and the state's
gap, which it does) — with every looked-at position's (routing margin,
logit gap) pair kept, so that the epsilon and the limits can be read
off one run, and the state's gap over the checked requests and over the
steady probe apart.

    python3 benchmarks/readings_nemotron.py --workload nemotron3_serve_decode --seeds 1 [--seconds 30] [--controls fp8,bf16_state] [--out file.jsonl]

One seed a process on the chip: 9 GB of weights are not all given back
between two."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readings  # noqa: E402


def serve_readings(driver, seconds: float, controls) -> dict:
    driver.keep_pairs = True
    # (a bfloat16 state moves no served token's logit gap: its argmax
    # is the float32 reference's; its reading is the state's)
    out = readings.serve_readings(
        driver, seconds, [c for c in controls if c != "bf16_state"])
    out["state"] = driver.result["state"]
    probes = driver.probes
    for name, part in (("state_gap", probes), ("state_gap_served",
                                               probes[:-1]),
                       ("state_gap_steady", probes[-1:])):
        driver.probes = part
        out[name] = {mode: driver.state_gap(mode)[0]
                     for mode in ["f32"] + [c for c in controls
                                            if c == "bf16_state"]}
    driver.probes = probes
    return out


readings.KINDS["serve_closed_nemotron"] = serve_readings

if __name__ == "__main__":
    readings.main()
