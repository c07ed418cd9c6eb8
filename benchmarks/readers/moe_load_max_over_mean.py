"""The busiest held expert of a layer over the layer's mean, averaged over the expert layers, from the program's counters over the window."""
from benchmarks.harness.layer_metrics_moe import moe_load_max_over_mean as read  # noqa: F401
