"""The recurrent state's part of the bytes a decode round moves, from the program's `generation_state_bytes`, the lanes a round holds and the expert loads a round."""
from benchmarks.harness.layer_metrics_hybrid import state_bytes_share as read  # noqa: F401
