"""CPU the `POST /generate` handler threads took a token they carried, over the traced `cpu.handler` marks: what they ask of the loop's interpreter lock."""
from benchmarks.harness.cpu_marks import read as _read


def read(ctx):
    return _read(ctx, "handler_us_per_token")
