"""`readings.py` for the cells of `drivers/serve_closed_ouro.py`: the
same loop (a short window at the cell's own load, the program's numbers
as `correct` compares them, the control's — the cached keys and values
of every slot in fp8), keyed on the new driver.

    python3 benchmarks/readings_ouro.py --workload ouro_serve_decode --seeds 1 [--seconds 30] [--controls fp8] [--out file.jsonl]

One seed a process on the chip: the weights and the pool fill 84% of
it, and not all of it is given back between two."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readings  # noqa: E402

readings.KINDS["serve_closed_ouro"] = readings.serve_readings

if __name__ == "__main__":
    readings.main()
