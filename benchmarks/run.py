"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds everything by name:
the cell in `BENCHMARK.json`, its configuration in
`benchmarks/configs/<config>.json`, its traffic in
`benchmarks/traffic/<traffic>.json` (which names its driver,
`benchmarks/drivers/<driver>.py`), its limits in
`benchmarks/limits/<cell>.json`, and each per-layer metric's reader in
`benchmarks/readers/<metric>.py`.  A new cell, configuration, traffic
mix or per-layer metric is new files and new entries; no file that is
here needs an edit.

Order of a run: resolve the device (no TPU with a row in
`harness/peaks.json`, or too few chips: exit non-zero, no result);
set-up (weights from `--seed` on the device, every program of the cell
compiled or read from the compile cache, the traffic brought to a
steady state) — all of it `setup_s`; the window of `--seconds`; the
peak of device memory; the program's state freed; `correct`, by the
plain reference over what the window itself produced.  The last line of
standard output is the result; the numbers compared, each beside its
limit, are its last key and the last lines of standard error.

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` part of the window is traced and the metrics are the
cell's per-layer metrics (end-to-end numbers are never taken from a
traced run)."""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import device  # noqa: E402


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, by path: a metric's name may hold
    a dot, which no import statement could spell."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str):
    """(manifest, cell, configuration, traffic, limits) of cell `name`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        sys.exit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                 f"({sorted(cells)})")
    cell = cells[name]
    return (manifest, cell, load_json("configs", cell["config"] + ".json"),
            load_json("traffic", cell["traffic"] + ".json"),
            load_json("limits", name + ".json"))


def reported(metrics, cell_name: str):
    """The metrics of a list that this cell reports."""
    return [m for m in metrics
            if "workloads" not in m or cell_name in m["workloads"]]


def run(args, devices, peaks, files) -> dict:
    """Everything of a run after the look for a chip, over `files` (what
    `load_cell` returns; a test hands in a cell at toy widths)."""
    manifest, cell, config, traffic, limits = files
    driver = load_module("drivers", traffic["driver"]).Driver(
        config, traffic, devices, args.seed)
    tracer = None
    if args.trace:
        from benchmarks.harness.tracing import Tracer
        tracer = Tracer()
    driver.setup()
    result = driver.window(float(args.seconds), tracer)
    setup_s = result["t_open"] - T0
    memory_peak = device.memory_peak_bytes(devices)
    driver.release()

    out_device = device.stamp(devices, memory_peak)
    metrics, breakdown = {}, None
    if tracer is None:
        values = dict(result["end_to_end"], setup_s=setup_s)
        wanted = reported(manifest["end_to_end"], cell["name"])
    else:
        from benchmarks.harness import trace_reduce
        trace = trace_reduce.reduce_file(
            tracer.path(), len(devices), tracer.t_stop - tracer.t_start)
        tracer.remove()
        out_device["busy_s"] = trace.busy_s
        out_device["window_s"] = trace.window_s
        breakdown = trace.breakdown()
        ctx = dict(trace=trace, traced=(tracer.t_start, tracer.t_stop),
                   window=result, config=config,
                   traffic=traffic, peaks=peaks, chips=len(devices))
        wanted = reported(manifest["per_layer"], cell["name"])
        values = {m["name"]: load_module("readers", m["name"]).read(ctx)
                  for m in wanted}
    for m in wanted:
        value = values.get(m["name"])
        if value is not None:       # a reader with nothing to read
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    checks = driver.check(limits)
    line = {"correct": all(c["ok"] for c in checks),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": out_device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if result.get("detail"):
        line["detail"] = result["detail"]
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"compared {c['name']}: {c['value']!r} (limit {c['limit']!r})"
              f" {'ok' if c['ok'] else 'NOT ok'}", file=sys.stderr)
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    device.use_compile_cache()
    files = load_cell(args.workload)
    devices, peaks = device.resolve(int(files[1]["chips"]))
    # before a word is printed: beside nothing but the benchmark's own
    # files, the run ends here
    import analytics_zoo_tpu  # noqa: F401
    line = run(args, devices, peaks, files)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
