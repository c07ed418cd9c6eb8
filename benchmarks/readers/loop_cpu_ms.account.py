"""The engine loop thread's own CPU a round under `account`: what the observability planes cost the loop, from the traced `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "cpu.account")
