"""Tokens a held expert computes each time a dispatch reads its weights, from the program's generation_moe_* counters over the window."""
from benchmarks.harness.layer_metrics_moe import moe_tokens_per_expert_mean as read  # noqa: F401
