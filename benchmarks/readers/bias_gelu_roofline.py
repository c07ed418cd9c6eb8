"""2*rows*768*3072 operations a call over peak bf16, over the fused bias-GELU kernel's device time (compute-bound)."""
from benchmarks.harness.layer_metrics import bias_gelu_roofline as read  # noqa: F401
