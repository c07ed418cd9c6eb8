"""Forward operations the traced prompts and tokens need on this chip (the scan as the recurrence, attention by context, routed experts by the program's count of held assignments, the head over the held vocabulary), over window x peak."""
from benchmarks.harness.layer_metrics_hybrid import serve_mfu_hybrid as read  # noqa: F401
