"""`serve_closed`'s loop over the decoder with latent attention
(`DecoderLM` by `sarvam_mla`'s keys: one cached row a token a layer,
expanded at prefill, absorbed at decode through the latent pool, in
front of sigmoid-routed experts) and its own plain reference
(`reference/sarvam_mla_ref.py`, the expanded form only).

The loop, the clients, the reduction and the sample are
`serve_closed.Driver`'s; `build`, the expert counters over the window
and over its traced part, the two latencies kept under `detail` and
`check` are `serve_closed_exaone.Driver`'s.  What is new here is what
has to be: `gaps` (the new reference; the control lowers the CACHED
LATENT ROW to fp8_e4m3, the precision below the pool's bfloat16, and
nothing else), and the pool's own numbers in the window's record
(`generation_kv_row_bytes`, `generation_kv_rows_per_token`, the pool's
logical and stored bytes, the decode rounds of the window), which the
cache manager's per-layer metric reads.

`correct` compares what `serve_closed_exaone` compares, over what the
window itself served: `served_logit_gap_p99` over the positions clear
of a routing near-tie, `served_logit_gap_mean` over every position,
`routing_near_tie_share`, `served_tokens_compared`,
`moe_dropped_assignments`."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.drivers import serve_closed_exaone

POOL = "generation_kv_"


class Driver(serve_closed_exaone.Driver):
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        # a program without the latent form (the parent of the PR that
        # brought this cell) ends here, at once and non-zero
        from analytics_zoo_tpu.ops.attention import (  # noqa: F401
            latent_decode_attention,
        )
        super().__init__(config, traffic, devices, seed)

    # -- the window ----------------------------------------------------

    def pool_numbers(self) -> Dict[str, float]:
        """What the engine says of its pool: bytes a cached token holds
        over all layers, rows a token, the pool's logical and stored
        bytes."""
        snap = self.engine.registry.snapshot()
        stats = self.engine._kv_pool_stats()
        return dict(
            row_bytes=snap[POOL + "row_bytes"],
            rows_per_token=snap[POOL + "rows_per_token"],
            pool_bytes_logical=stats["pool_bytes_logical"],
            pool_bytes_physical=stats["pool_bytes_physical"])

    def decode_rounds(self) -> int:
        """Decode rounds the engine has collected so far."""
        return int(self.engine.registry.snapshot()[
            "generation_decode_seconds"]["calls"])

    def window(self, seconds: float, tracer) -> Dict:
        pool, rounds = self.pool_numbers(), self.decode_rounds()
        result = super().window(seconds, tracer)
        result["kv"] = dict(pool, rounds=self.decode_rounds() - rounds)
        result["detail"]["kv"] = dict(result["kv"])
        return result

    # -- after the window ----------------------------------------------

    def gaps(self, requests: List[Dict], mode: str = "f32",
             epsilon: float = 0.0) -> Tuple[Dict, int]:
        """`serve_closed_exaone.Driver.gaps` over this model's
        reference: the served tokens' gaps, their 99th percentile over
        the positions clear of a near-tie, their mean over all, the
        near-tie share.  With `mode` "fp8" the token judged is the one
        the reference puts first over latent rows cached in fp8 (the
        control); the margins stay the float32 reference's."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.reference import sarvam_mla_ref as ref
        length = int(self.config["engine"]["max_context"])
        margins, gaps = [], []
        for r in requests:
            tokens = r["tokens"]
            seq = (r["prompt"] + tokens)[:-1]
            padded = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
            first = len(r["prompt"]) - 1
            rows = slice(first, first + len(tokens))
            want, margin, _ = ref.forward(self.params, padded,
                                          self.config, rows=rows)
            if mode == "f32":
                judged = jnp.asarray(tokens, jnp.int32)
            else:
                judged = ref.forward(self.params, padded, self.config,
                                     mode=mode, rows=rows)[0].argmax(-1)
            gaps.append(np.asarray(want.max(-1) - jnp.take_along_axis(
                want, judged[:, None], axis=-1)[:, 0]))
            margins.append(np.asarray(margin[rows]))
        if not gaps:
            return dict(gap=0.0, gap_p99=0.0, gap_all=0.0, gap_mean=0.0,
                        near_tie_share=0.0), 0
        below, margin = np.concatenate(gaps), np.concatenate(margins)
        clear = margin >= epsilon
        out = dict(gap=float(below[clear].max()) if clear.any() else 0.0,
                   gap_p99=(float(np.quantile(below[clear], 0.99))
                            if clear.any() else 0.0),
                   gap_all=float(below.max()),
                   gap_mean=float(below.mean()),
                   near_tie_share=float(1.0 - clear.mean()))
        if self.keep_pairs:
            out["pairs"] = np.stack([margin, below], 1).tolist()
        return out, len(below)
