"""`serve_closed`'s loop over the decoder described by configuration
(`DecoderLM`: grouped heads, window and full layers, expert layers) and
its own plain reference (`reference/exaone_moe_ref.py`).

What differs from `serve_closed.Driver` is what has to: `build` (the
new module from the configuration's published keys, bfloat16 leaves,
the benchmark's weights), `gaps` and `check` (the new reference, and
the routing near-ties set aside: see the reference's docstring), and
the window's record, which gains the program's expert counts
(`generation_moe_*` on the engine's registry) over the whole window and
over its traced part, and keeps under `detail` the two latencies that
are no end-to-end metric here.  The loop, the clients, the reduction
and the sample are the parent class's, untouched.

`correct` compares, over what the window itself served:

  * `served_logit_gap_p99` — `serve_closed`'s gap (how far a served
    token's reference logit lies below the reference's best), its 99th
    percentile over the served positions whose smallest routing margin
    (the reference's own, over the sparse layers where the last picked
    or the first unpicked expert is held here) is at least the limits
    file's `epsilon`.  Not the widest: about one position in a
    thousand flips an expert at a margin above any usable epsilon
    (the flip's keys and values reach it through attention), and one
    flip is a gap of 0.2 to 0.6;
  * `served_logit_gap_mean` — the same gap, averaged over EVERY served
    position of the sample, the near-ties in: a flipped expert moves
    few positions by much, a lowered precision most of them;
  * `routing_near_tie_share` — the share of served positions under
    that epsilon (the reference's own margins over the served tokens);
  * `served_tokens_compared` — at least one;
  * `moe_dropped_assignments` — the program's own count over the
    window, which has to read 0."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.drivers import serve_closed
from benchmarks.harness import builders, weights

COUNTERS = "generation_moe_"


class CountingTracer:
    """The run's tracer, with the expert counters read as the trace
    starts and stops."""

    def __init__(self, tracer, read):
        self.tracer, self.read = tracer, read
        self.before = self.after = None

    def start(self) -> None:
        # the profiler takes seconds to start and to stop while the
        # engine serves on: the counters are read on the traced side of
        # both, where the tracer stamps its own clock
        self.tracer.start()
        self.before = self.read()

    def stop(self) -> None:
        self.after = self.read()
        self.tracer.stop()


class Driver(serve_closed.Driver):
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        # a program without the decoder (the parent of the PR that
        # brought this cell) ends here, at once and non-zero
        from analytics_zoo_tpu.serving.generation import DecoderLM
        self.module = DecoderLM
        # the loop asks the configuration for the vocabulary it draws
        # prompts from under `serve_closed`'s key
        config = dict(config, model={"vocab": int(config["vocab_size"])})
        super().__init__(config, traffic, devices, seed)
        self.keep_pairs = False

    # -- set-up --------------------------------------------------------

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        self.model = self.module.from_config(
            self.config, compute_dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16)
        abstract = jax.eval_shape(
            self.model.init, jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None])["params"]
        self.params = weights.make_params(abstract, self.seed)
        self.engine = builders.new_engine(self.model, self.params,
                                          self.config["engine"])
        self.engine.warmup()
        self.server = builders.new_server(self.engine)

    # -- the window ----------------------------------------------------

    def moe_counters(self) -> Dict[str, float]:
        snap = self.engine.registry.snapshot()
        return {k[len(COUNTERS):]: v for k, v in snap.items()
                if k.startswith(COUNTERS)}

    def moe_delta(self, before: Dict, after: Dict) -> Dict:
        """What the counters moved by, in the readers' form: `tokens`
        [expert layer][held expert], the assignments `held` here and
        `elsewhere`, those `dropped`, and the expert weights the
        decode rounds and the prefills had to read."""
        moved = {k: after[k] - before.get(k, 0) for k in after}
        first, held = self.model.held
        tokens = [[int(moved[f"expert_tokens_total_layer{layer}"
                             f"_expert{first + e}"])
                   for e in range(held)] for layer in self.model.moe_layers]
        return dict(
            tokens=tokens,
            held=int(moved["assignments_total_held"]),
            elsewhere=int(moved["assignments_total_elsewhere"]),
            dropped=int(moved["dropped_total"]),
            loads_decode=int(moved["expert_loads_total_decode"]),
            loads_prefill=int(moved["expert_loads_total_prefill"]))

    def window(self, seconds: float, tracer) -> Dict:
        before = self.moe_counters()
        counting = (CountingTracer(tracer, self.moe_counters)
                    if tracer is not None else None)
        result = super().window(seconds, counting)
        # (the clients' drain is in it: the counters move until the
        # last request in flight has ended)
        moe = {"window": self.moe_delta(before, self.moe_counters())}
        if counting is not None:
            moe["traced"] = self.moe_delta(counting.before, counting.after)
        result["moe"] = moe
        whole = moe["window"]
        loads = whole["loads_decode"] + whole["loads_prefill"]
        # the median first token and the gap tail are no end-to-end
        # metric of this cell (a closed loop of as many clients as
        # lanes runs at capacity, and both fall between rounds of
        # none, one and several prefills: PERF.md section 2); every
        # line keeps them for the record
        result["detail"].update(
            (k, v) for k, v in result["end_to_end"].items()
            if k != "serve_tokens_per_s")
        result["detail"]["moe"] = dict(
            assignments_held=whole["held"],
            assignments_elsewhere=whole["elsewhere"],
            dropped=whole["dropped"],
            tokens_per_expert_load=(sum(map(sum, whole["tokens"])) / loads
                                    if loads else None))
        return result

    # -- after the window ----------------------------------------------

    def gaps(self, requests: List[Dict], mode: str = "f32",
             epsilon: float = 0.0) -> Tuple[Dict, int]:
        """Over every served token of `requests`: the widest gap by
        which the token's reference logit lies below the reference's
        best at its position, over the positions whose routing margin
        is at least `epsilon` (`gap`; its 99th percentile `gap_p99`)
        and over all of them (`gap_all`), that gap's mean over all of
        them (`gap_mean`), the share of positions under `epsilon`
        (`near_tie_share`), and how many tokens were looked at.  With `mode` below f32 the
        token judged is the one the lowered reference puts first (the
        control); the margins stay the float32 reference's."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.reference import exaone_moe_ref as ref
        length = int(self.config["engine"]["max_context"])
        margins, gaps = [], []
        for r in requests:
            tokens = r["tokens"]
            seq = (r["prompt"] + tokens)[:-1]
            padded = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
            first = len(r["prompt"]) - 1
            rows = slice(first, first + len(tokens))
            want, margin = ref.forward(self.params, padded, self.config,
                                       rows=rows)
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

    def check(self, limits: Dict) -> List[Dict]:
        """Each number compared, beside its limit."""
        epsilon = float(limits["routing_near_tie_share"]["epsilon"])
        read, compared = self.gaps(self.sample(), epsilon=epsilon)
        dropped = self.result["moe"]["window"]["dropped"]

        def under(name, value):
            limit = limits[name]["limit"]
            return dict(name=name, value=value, limit=limit,
                        ok=compared > 0 and value <= limit)
        return [
            under("served_logit_gap_p99", read["gap_p99"]),
            under("served_logit_gap_mean", read["gap_mean"]),
            under("routing_near_tie_share", read["near_tie_share"]),
            dict(name="served_tokens_compared", value=compared,
                 limit=1, ok=compared >= 1),
            dict(name="moe_dropped_assignments", value=dropped,
                 limit=0, ok=dropped == 0),
        ]
