"""Keys and values of the live contexts over every cache slot and the two products over them, against the device time of the paged kernel's events under attn.loop."""
from benchmarks.harness.layer_metrics_looped import paged_decode_looped_roofline as read  # noqa: F401
