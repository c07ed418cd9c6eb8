"""Mean device time of one execution of the engine's prefill program (every bucket), where the chunked scan lives."""
from benchmarks.harness.layer_metrics_hybrid import prefill_step_device_ms as read  # noqa: F401
