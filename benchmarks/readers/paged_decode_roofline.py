"""KV bytes the live contexts need over peak bandwidth, over the paged-decode kernel's device time (memory-bound)."""
from benchmarks.harness.layer_metrics import paged_decode_roofline as read  # noqa: F401
