"""Toy-width copies of the benchmark's cells for the CPU suite: the
same files, the same drivers, only the sizes cut (never used on the
chip, never reported)."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

SERVE, TRAIN = "gpt2s_serve_decode", "bert_base_finetune"


def toy(cell: str, limits=None):
    """`load_cell(cell)` with the widths, batch and traffic cut."""
    manifest, entry, config, traffic, real = bench_run.load_cell(cell)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    if traffic["driver"] == "serve_closed":
        config["model"] = dict(vocab=2048, hidden_size=64, n_head=4,
                               n_block=2, intermediate_size=128,
                               max_position_len=128)
        config["engine"] = dict(max_slots=4, block_size=8, max_context=128,
                                prefill_buckets=[32, 64, 128])
        traffic.update(
            clients=4, deck=16, check_requests=24, trace_lead_s=0.1,
            trace_seconds=0.3,
            prompt_len=dict(dist="log_uniform", low=8, high=60),
            max_new_tokens=dict(dist="uniform", low=12, high=24))
    else:
        config["model"].update(vocab=211, hidden_size=32, n_head=4,
                               n_block=2, intermediate_size=64,
                               max_position_len=32)
        config["estimator"].update(seq_len=16, batch_size=16)
        traffic.update(steps_per_fit=4, reference_microbatch=8)
    return manifest, entry, config, traffic, limits or real
