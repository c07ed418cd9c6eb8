"""The engine loop thread's own CPU a round under `stage`, `dispatch` and a decode span's own time, from the traced `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "cpu.dispatch")
