"""Forward operations the traced prompts and tokens need on this chip, every layer counted as many times as the program says its stack runs (`generation_loop_steps`), over window x peak."""
from benchmarks.harness.layer_metrics_looped import serve_mfu_looped as read  # noqa: F401
