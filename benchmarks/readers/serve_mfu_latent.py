"""Forward operations the traced prompts (expanded) and tokens (absorbed) need on this chip, routed experts by the program's count of held assignments, over window x peak."""
from benchmarks.harness.layer_metrics_latent import serve_mfu_latent as read  # noqa: F401
