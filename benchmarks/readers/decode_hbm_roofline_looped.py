"""Bytes the traced decode rounds had to read (the layers' weights once a loop step, the head, the live contexts' rows over every cache slot) over peak bandwidth, against jit_decode's device time."""
from benchmarks.harness.layer_metrics_looped import decode_hbm_roofline_looped as read  # noqa: F401
