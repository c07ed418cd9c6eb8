"""Mean number of lanes in a decode dispatch, from the `l=` of the traced `generation.decode[...]` spans."""
from benchmarks.harness.span_metrics import decode_counts


def read(ctx):
    return decode_counts(ctx, 0)
