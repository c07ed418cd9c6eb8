"""The engine loop thread's own CPU a round under `fetch` (the wait for the device itself costs none), from the traced `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "cpu.fetch")
