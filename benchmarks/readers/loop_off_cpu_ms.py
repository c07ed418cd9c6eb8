"""Time a round the loop thread ran nothing in phases that wait neither for results nor for work: the interpreter lock, a core, or (where the device is a round behind) an enqueue that blocks. Wall of its phases but `fetch` and `off_round` from their spans, less its CPU in them from the `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "off_cpu")
