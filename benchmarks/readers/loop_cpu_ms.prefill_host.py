"""The engine loop thread's own CPU a round under any part of a `prefill`, from the traced `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "cpu.prefill_host")
