"""The step-indexed cached rows' part of the bytes the window's decode rounds read, by the program's `generation_kv_row_bytes`, `generation_loop_steps` and decode rounds, beside the weights."""
from benchmarks.harness.layer_metrics_looped import loop_kv_bytes_share as read  # noqa: F401
