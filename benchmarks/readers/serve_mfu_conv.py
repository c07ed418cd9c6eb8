"""Forward operations the traced prompts and tokens need on this chip (the short convolution's projections and taps, attention by context, the dense FFN, routed experts by the program's count of held assignments, the head), over window x peak."""
from benchmarks.harness.layer_metrics_conv import serve_mfu_conv as read  # noqa: F401
