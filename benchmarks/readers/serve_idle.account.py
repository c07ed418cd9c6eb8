"""Share of the traced window in which the device was idle under account of a decode round: what the observability planes cost."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "account")
