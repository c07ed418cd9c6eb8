"""Share of the traced window in which the device was idle under emit of a decode round: stream puts, the finish checks."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "emit")
