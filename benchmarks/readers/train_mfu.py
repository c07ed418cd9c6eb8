"""6N + 12LHt operations a trained token (recompute not credited), over window x peak."""
from benchmarks.harness.layer_metrics import train_mfu as read  # noqa: F401
