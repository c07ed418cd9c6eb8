"""Wall of the traced `generation.prefill` spans over wall of the `generation.round` spans they lie in."""
from benchmarks.harness.span_metrics import prefill_time_share as read  # noqa: F401
