"""Forward operations the prompts and tokens of the traced window need, over window x peak."""
from benchmarks.harness.layer_metrics import serve_mfu as read  # noqa: F401
