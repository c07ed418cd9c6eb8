"""Bytes the traced decode rounds must read (weights, routed experts that had a token, KV in sight) over peak bandwidth, over jit_decode's device time (memory-bound)."""
from benchmarks.harness.layer_metrics_moe import decode_hbm_roofline as read  # noqa: F401
