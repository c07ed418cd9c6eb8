"""Mean number of requests waiting when a decode round starts, from the `w=` of the traced `generation.decode[...]` spans (capped at 99 in the name)."""
from benchmarks.harness.span_metrics import decode_counts


def read(ctx):
    return decode_counts(ctx, 1)
