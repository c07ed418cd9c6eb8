"""The readings a cell's limits are set from, many seeds in one process.

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 [--seconds 8] [--controls fp8,int8] [--out file.jsonl]

For each seed: the program's numbers as `correct` compares them (the
lower reading is their largest over a dozen seeds or more), the same
numbers of the control — the plain reference computed in the precision
below the configuration's — and of each planted fault (the upper
reading is the smallest of those).  A serving cell runs a short window
at the cell's own load, long enough to finish its longest requests; a
training cell needs none, its readings come from the first steps.
A training cell takes at most four seeds a process (the device's
memory is not all given back between them).  Needs the chip, like
`run.py`; the limits it leads to are written by
hand into `benchmarks/limits/<cell>.json` with the readings beside
them."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import device  # noqa: E402

def serve_readings(driver, seconds: float, controls) -> dict:
    driver.setup()
    result = driver.window(seconds, None)
    driver.release()
    sample = driver.sample()
    out = dict(attempted=result["attempted"], failed=result["failed"],
               finished=len(result["finished"]), sampled=len(sample))
    out["program"], out["compared"] = driver.gaps(sample)
    for mode in controls:
        out[mode], _ = driver.gaps(sample, mode)
    return out


def train_readings(driver, seconds: float, controls) -> dict:
    driver.build()
    driver.first_steps()
    driver.release()
    want = driver.reference()
    out = dict(losses=driver.first["losses"], reference_losses=want[0],
               program=driver.compare(driver.first, want))
    batch = int(driver.config["estimator"]["batch_size"])
    runs = [(m, dict(mode=m)) for m in controls]
    runs.append(("half_batch", dict(rows=slice(0, batch // 2))))
    runs.append(("state_unchanged", dict(lr=0.0)))
    for name, kw in runs:
        got = dict(zip(("losses", "grad", "params"), driver.reference(**kw)))
        out[name] = driver.compare(got, want)
    return out


KINDS = {"serve_closed": serve_readings, "train_fit": train_readings}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="fp8",
                    help="precisions below the configuration's, by commas")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    device.use_compile_cache()
    from benchmarks.run import load_cell, load_module
    _, cell, config, traffic, _ = load_cell(args.workload)
    devices, _ = device.resolve(int(cell["chips"]))
    import analytics_zoo_tpu  # noqa: F401
    module = load_module("drivers", traffic["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        driver = module.Driver(config, traffic, devices, seed)
        line = dict(workload=args.workload, seed=seed,
                    **KINDS[traffic["driver"]](
                        driver, args.seconds,
                        [c for c in args.controls.split(",") if c]))
        line["seconds"] = time.perf_counter() - t
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")


if __name__ == "__main__":
    main()
