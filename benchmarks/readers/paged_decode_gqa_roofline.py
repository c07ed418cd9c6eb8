"""KV bytes the live contexts need (a window layer: the positions in sight) over peak bandwidth, over the grouped-query paged kernel's device time."""
from benchmarks.harness.layer_metrics_moe import paged_decode_gqa_roofline as read  # noqa: F401
