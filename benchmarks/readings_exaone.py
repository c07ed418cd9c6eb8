"""`readings.py` for the cells of `drivers/serve_closed_exaone.py`: the
same loop (a short window at the cell's own load, the program's numbers
as `correct` compares them, the fp8 control's), keyed on the new
driver, and with every looked-at position's (routing margin, logit gap)
pair kept, so that the epsilon and both limits can be read off one run.

    python3 benchmarks/readings_exaone.py --workload kexaone_serve_decode --seeds 1 [--seconds 30] [--controls fp8] [--out file.jsonl]

One seed a process on the chip: 12 GB of weights are not all given back
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
    return readings.serve_readings(driver, seconds, controls)


readings.KINDS["serve_closed_exaone"] = serve_readings

if __name__ == "__main__":
    readings.main()
