"""The engine loop thread's own CPU a round between rounds (`housekeeping`, the return from the turn it gives the other threads), from the traced `cpu.loop` marks."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "cpu.off_round")
