"""Bytes the traced decode rounds must move (weights, routed experts that had a token, the live lanes' recurrent state read and written, KV in sight) over peak bandwidth, over jit_decode's device time (memory-bound)."""
from benchmarks.harness.layer_metrics_hybrid import decode_hbm_roofline_hybrid as read  # noqa: F401
