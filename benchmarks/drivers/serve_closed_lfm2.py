"""`serve_closed`'s loop over the `lfm2_moe` decoder (`HybridLM` by
`lfm2_moe`'s keys: gated short-convolution layers whose two-row tail
lives in the recurrent pool beside the KV pool, grouped-query attention
layers, SwiGLU routed experts) and its own plain reference
(`reference/lfm2_moe_ref.py`).

Everything but what has to differ is `serve_closed_nemotron.Driver`'s:
the loop, the clients, the reduction and the sample (`serve_closed`),
the expert counters and the latencies kept under `detail`
(`serve_closed_exaone`), the build from the configuration's published
keys with the benchmark's weights (`harness/weights_hybrid.py`: the
short convolution's taps by PyTorch's `Conv1d` default), the recurrent
pool's counters and the probes served after the window.  What is new:
`_forward` (the new reference), the state compared — the convolution
tails the probes leave in their slots, there being no scan — and, in a
traced run, `serve_closed_ouro`'s tracer started and stopped with the
device quiet: a closed loop of as many clients as lanes keeps the chip
busy through the rounds' edges, so a round running across an edge of
the profiler's session could count busy time outside the window.

`correct` compares, over what the window itself served, what
`serve_closed_exaone` compares — `served_logit_gap_p99` over the
positions clear of a routing near-tie, `served_logit_gap_mean` over
every position, `routing_near_tie_share`, `served_tokens_compared`,
`moe_dropped_assignments` — and one number more:

  * `conv_tail_gap_worst` — once the window has closed and its
    requests have ended, the checked requests and one steady request
    are served through the same engine (the probes); each leaves, in
    its lane's slot of the recurrent pool, the tails after its prompt
    and all but the last of its tokens, and the reference computes the
    same rows of v in float32.  The number is the largest, over probes
    and the convolution layers in front of the first expert layer, of
    |T - T_ref| / |T_ref| (Frobenius norms of a layer's [2, hidden]
    tail).  A tail one row off, or a bucket's padding shifted into it,
    reads near 1; bfloat16 rows some thousandths.  The layers behind an
    expert layer are left out for the reason `serve_closed_nemotron`
    leaves them out: a routing flip moves them by more than any
    precision does."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.drivers import (serve_closed_exaone, serve_closed_nemotron,
                                serve_closed_ouro)


class Driver(serve_closed_nemotron.Driver):
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        # a program without the short convolution (one older than this
        # cell) ends here, at once and non-zero
        from analytics_zoo_tpu.serving.generation.hybrid import (  # noqa
            ShortConv)
        super().__init__(config, traffic, devices, seed)

    # -- the window ----------------------------------------------------

    def window(self, seconds: float, tracer) -> Dict:
        quiet = (serve_closed_ouro.QuietEdgesTracer(tracer, self.engine)
                 if tracer is not None else None)
        result = super().window(seconds, quiet)
        if quiet is not None:
            quiet.join()
            result["detail"]["trace_edges_held_s"] = quiet.held_s
        return result

    def probe(self, requests: List[Dict]) -> List[Dict]:
        """`serve_closed_nemotron`'s probes, with the convolution tails
        each still-held slot keeps (its scan state is the empty list)."""
        out = super().probe(requests)
        for p in out:
            p["conv"] = (None if p["ssm"] is None else
                         self.engine.recurrent_state(p["slot"])["conv"])
        return out

    # -- after the window ----------------------------------------------

    def _forward(self, tokens: List[int], **kw):
        import jax.numpy as jnp

        from benchmarks.reference import lfm2_moe_ref as ref
        length = int(self.config["engine"]["max_context"])
        padded = jnp.asarray(tokens + [0] * (length - len(tokens)),
                             jnp.int32)
        return ref.forward(self.params, padded, self.config, **kw)

    def clear_state_layers(self) -> int:
        """Convolution layers no routed expert lies in front of: those
        of the dense layers and of the first routed layer's operator."""
        kinds = self.config["layer_types"]
        return kinds[:int(self.config["num_dense_layers"]) + 1].count(
            "conv")

    def tail_gaps(self, mode: str = "f32") -> List[List[float]]:
        """For each probe that still held its slot, the relative gap of
        every convolution layer's tail to the float32 reference's, in
        layer order — the program's own tails (`f32`), or the lowered
        reference's (the control)."""
        import numpy as np
        out = []
        for p in self.probes:
            if p.get("conv") is None:
                continue
            seq = (p["prompt"] + p["tokens"])[:-1]
            none = slice(0, 0)
            want = self._forward(seq, rows=none, length=len(seq))[2]
            got = p["conv"] if mode == "f32" else self._forward(
                seq, rows=none, length=len(seq), mode=mode)[2]
            row = []
            for g, w in zip(got, want):
                g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
                row.append(float(np.sqrt(((g - w) ** 2).sum()
                                         / max((w ** 2).sum(), 1e-60))))
            out.append(row)
        return out

    def state_gap(self, mode: str = "f32") -> Tuple[float, int]:
        """The widest of `tail_gaps` over the layers no routed expert
        lies in front of, and how many tails were compared."""
        clear = [row[:self.clear_state_layers()]
                 for row in self.tail_gaps(mode)]
        return (max((g for row in clear for g in row), default=0.0),
                sum(len(row) for row in clear))

    def check(self, limits: Dict) -> List[Dict]:
        """Each number compared, beside its limit."""
        checks = serve_closed_exaone.Driver.check(self, limits)
        worst, tails = self.state_gap()
        limit = limits["conv_tail_gap_worst"]["limit"]
        checks.insert(3, dict(name="conv_tail_gap_worst", value=worst,
                              limit=limit,
                              ok=tails > 0 and worst <= limit))
        return checks
