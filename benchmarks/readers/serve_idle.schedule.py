"""Share of the traced window in which the device was idle under the scheduler's admission, its look for decode capacity and a round's own bookkeeping."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "schedule")
