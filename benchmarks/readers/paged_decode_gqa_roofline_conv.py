"""KV bytes the live contexts need over the two attention layers (head_dim 64, no window) over peak bandwidth, over the grouped-query paged kernel's device time."""
from benchmarks.harness.layer_metrics_conv import paged_decode_gqa_roofline_conv as read  # noqa: F401
