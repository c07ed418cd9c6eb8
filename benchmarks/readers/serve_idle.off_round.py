"""`device_idle_share.serve` less the six other `serve_idle.*`: the device idle with the engine's loop in no round (no work, housekeeping, the head and the tail of the trace)."""
from benchmarks.harness.span_metrics import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "off_round")
