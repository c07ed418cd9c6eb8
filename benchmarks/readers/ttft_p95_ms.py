"""95th percentile of time to first token over the window, clients' clock: the tail, which a window of some 140 requests cannot hold to a bound."""
from benchmarks.harness.layer_metrics import window_percentile


def read(ctx):
    return window_percentile(ctx, "ttft_ms", 95)
