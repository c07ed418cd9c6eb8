"""Host time in `spmd.input_wait` a step of the traced fit: the loop waiting for its next batch."""
from benchmarks.harness.span_metrics import train_input_wait_ms as read  # noqa: F401
