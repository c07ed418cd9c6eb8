"""Share of the traced window in which the device was idle under the host's part of a prefill (any leaf of a generation.prefill span)."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "prefill_host")
