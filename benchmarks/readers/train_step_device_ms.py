"""Mean device time of one execution of SPMDEngine's train step."""
from benchmarks.harness.layer_metrics import program_ms


def read(ctx):
    return program_ms(ctx, "jit__train_step_impl")
