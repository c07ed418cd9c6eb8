"""Share of the traced window in which the device was idle under fetch of a decode round: the tail after the program ended, and the copy back."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "fetch")
