"""Forward operations the traced prompts and tokens need on this chip (window layers by what is in sight, routed experts by the program's count of held assignments), over window x peak."""
from benchmarks.harness.layer_metrics_moe import serve_mfu_moe as read  # noqa: F401
