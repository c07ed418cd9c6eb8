"""Bytes the traced decode rounds must read (weights, routed experts that had a token, the live latent rows) over peak bandwidth, over jit_decode's device time."""
from benchmarks.harness.layer_metrics_latent import decode_hbm_roofline_latent as read  # noqa: F401
