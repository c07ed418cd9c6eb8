"""The cached latent rows' part of the bytes the window's decode rounds read, by the program's `generation_kv_row_bytes`, rounds and expert loads, beside the weights."""
from benchmarks.harness.layer_metrics_latent import latent_bytes_share as read  # noqa: F401
