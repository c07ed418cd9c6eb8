"""Mean device time of one execution of the engine's decode program."""
from benchmarks.harness.layer_metrics import program_ms


def read(ctx):
    return program_ms(ctx, "jit_decode")
