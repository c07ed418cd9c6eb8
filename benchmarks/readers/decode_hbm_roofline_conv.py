"""Bytes the traced decode rounds must move (weights, routed experts that had a token, KV in sight, the live lanes' convolution tails read and written) over peak bandwidth, over jit_decode's device time (memory-bound)."""
from benchmarks.harness.layer_metrics_conv import decode_hbm_roofline_conv as read  # noqa: F401
