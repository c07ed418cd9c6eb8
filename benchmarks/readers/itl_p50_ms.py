"""Median gap between tokens over the window, clients' clock (the engine's decode round as a client sees it)."""
from benchmarks.harness.layer_metrics import window_percentile


def read(ctx):
    return window_percentile(ctx, "itl_ms", 50)
