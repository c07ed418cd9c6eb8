"""Latent rows the live contexts need (1,152 B a token a layer) and the absorbed products over them (139,264 FLOP), over the latent decode kernel's device time."""
from benchmarks.harness.layer_metrics_latent import latent_decode_roofline as read  # noqa: F401
