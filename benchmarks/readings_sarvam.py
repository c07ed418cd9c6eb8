"""`readings.py` for the cells of `drivers/serve_closed_sarvam.py`: the
same loop (a short window at the cell's own load, the program's numbers
as `correct` compares them, the control's — the cached latent row in
fp8), keyed on the new driver, with every looked-at position's (routing
margin, logit gap) pair kept.

    python3 benchmarks/readings_sarvam.py --workload sarvam_serve_decode --seeds 1 [--seconds 30] [--controls fp8] [--out file.jsonl]

One seed a process on the chip."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readings, readings_exaone  # noqa: E402


readings.KINDS["serve_closed_sarvam"] = readings_exaone.serve_readings

if __name__ == "__main__":
    readings.main()
