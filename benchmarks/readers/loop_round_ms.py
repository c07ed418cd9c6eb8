"""Wall of a round the loop clocked and the turn before it, from the end of the round before to its own: the `wall` of the traced `cpu.loop` marks over their count."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "round")
