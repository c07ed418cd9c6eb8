"""Share of the traced window in which the device was idle under stage + dispatch of a decode round: the numpy arguments, their copies to the device, the RNG split, the jitted call."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "dispatch")
