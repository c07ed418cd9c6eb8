"""`serve_closed`'s loop over the hybrid decoder (`HybridLM`: Mamba-2
state-space layers with a recurrent-state pool beside the KV pool,
attention layers, latent routed experts) and its own plain reference
(`reference/nemotron_h_ref.py`).

The loop, the clients, the reduction and the sample are
`serve_closed.Driver`'s; the expert counters over the window and over
its traced part, and the two latencies kept under `detail`, are
`serve_closed_exaone.Driver`'s.  What is new here is what has to be:
`build` (the new module from the configuration's published keys, the
state-space leaves by their published initialisers:
`harness/weights_hybrid.py`), `gaps` (the new reference), the
recurrent pool's counters in the window's record, and a comparison of
the STATE itself.

`correct` compares, over what the window itself served, what
`serve_closed_exaone` compares — `served_logit_gap_p99` over the
positions clear of a routing near-tie, `served_logit_gap_mean` over
every position, `routing_near_tie_share`, `served_tokens_compared`,
`moe_dropped_assignments` — and one number more:

  * `state_gap_worst_head` — once the window has closed and its
    requests have ended, the checked requests and one steady request
    (`steady_request`) are served through the same engine (the
    probes); each leaves, in its lane's
    slot of the recurrent pool, the state after its prompt and all but
    the last of its tokens, and the reference computes the same state
    by the plain recurrence in float32.  The number is the largest,
    over probes, state layers and heads, of |H - H_ref| / |H_ref|
    (Frobenius norms of a head's [head_dim, state] matrix).  A logit
    cannot tell a state pool one precision too low from the bfloat16
    noise every projection carries, and under varying inputs neither
    can the state (both sit near a hundredth); under a steady input
    a state rounded to bfloat16 after every step stalls short of where
    a slowly decaying head's recurrence ends (the `bf16_state`
    control), and a float32 state does not."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.drivers import serve_closed, serve_closed_exaone
from benchmarks.harness import builders, weights_hybrid

STATE = "generation_state_"
#: seconds the probes may take to be served
PROBE_S = 300.0


class Driver(serve_closed_exaone.Driver):
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        # a program without the hybrid decoder (the parent of the PR
        # that brought this cell) ends here, at once and non-zero
        from analytics_zoo_tpu.serving.generation import HybridLM
        self.module = HybridLM
        config = dict(config, model={"vocab": int(config["vocab_size"])})
        serve_closed.Driver.__init__(self, config, traffic, devices, seed)
        self.keep_pairs = False
        self.probes: List[Dict] = []

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
        self.params = weights_hybrid.make_params(abstract, self.seed)
        self.engine = builders.new_engine(self.model, self.params,
                                          self.config["engine"])
        self.engine.warmup()
        self.server = builders.new_server(self.engine)

    # -- the window ----------------------------------------------------

    def state_counters(self) -> Dict[str, float]:
        snap = self.engine.registry.snapshot()
        return {k[len(STATE):]: v for k, v in snap.items()
                if k.startswith(STATE)}

    def decode_rounds(self) -> int:
        """Decode rounds the engine has collected so far."""
        return int(self.engine.registry.snapshot()[
            "generation_decode_seconds"]["calls"])

    def window(self, seconds: float, tracer) -> Dict:
        before, rounds = self.state_counters(), self.decode_rounds()
        result = super().window(seconds, tracer)
        after = self.state_counters()
        result["state"] = dict(
            bytes=after["bytes"],
            resets=after["resets_total"] - before["resets_total"],
            rebuilds=after["rebuilds_total"] - before["rebuilds_total"],
            rounds=self.decode_rounds() - rounds)
        result["detail"]["state"] = dict(result["state"])
        self.probes = self.probe(self.sample() + [self.steady_request()])
        return result

    def steady_request(self) -> Dict:
        """One token, drawn from the seed, as often as the traffic's
        longest prompt, and its shortest answer after it: the input
        under which a recurrent state's precision shows.  Varying
        inputs dither a rounded state; a steady one lets it stall —
        once a slowly decaying head's step adds less than half a
        bfloat16 unit of the state, the state stops short of where the
        recurrence ends."""
        import numpy as np
        token = int(np.random.default_rng([self.seed, 4]).integers(
            0, self.config["model"]["vocab"]))
        return dict(prompt=[token] * int(self.traffic["prompt_len"]["high"]),
                    max_new=int(self.traffic["max_new_tokens"]["low"]))

    def probe(self, requests: List[Dict]) -> List[Dict]:
        """The checked requests served once more, all at once, with the
        clients gone: (prompt, the tokens this serving gave, the scan
        state each state layer holds in the request's slot once it has
        ended)."""
        from analytics_zoo_tpu.observability import request_log
        streams = [self.engine.submit(r["prompt"],
                                      max_new_tokens=r["max_new"],
                                      stream_timeout=PROBE_S)
                   for r in requests]
        out = []
        for r, stream in zip(requests, streams):
            tokens = stream.tokens()
            log = request_log.get(stream.request_id) or {"events": []}
            admits = [e for e in log["events"]
                      if e["kind"] in ("admit", "resume")]
            # preempted or cut short: its slot holds another state
            whole = len(admits) == 1 and stream.finish_reason == "length"
            out.append(dict(prompt=r["prompt"], tokens=tokens,
                            slot=admits[0]["slot"] if whole else None,
                            at=admits[0]["t"] if whole else None))
        # every probe has ended and nothing else was admitted: a slot
        # still holds what the last probe admitted to it left
        last = {}
        for p in out:
            if p["slot"] is not None:
                last[p["slot"]] = max(last.get(p["slot"], p["at"]), p["at"])
        for p in out:
            held = p["slot"] is not None and last[p["slot"]] == p["at"]
            p["ssm"] = (self.engine.recurrent_state(p["slot"])["ssm"]
                        if held else None)
        return out

    # -- after the window ----------------------------------------------

    def _forward(self, tokens: List[int], **kw):
        import jax.numpy as jnp

        from benchmarks.reference import nemotron_h_ref as ref
        length = int(self.config["engine"]["max_context"])
        padded = jnp.asarray(tokens + [0] * (length - len(tokens)),
                             jnp.int32)
        return ref.forward(self.params, padded, self.config, **kw)

    def gaps(self, requests: List[Dict], mode: str = "f32",
             epsilon: float = 0.0) -> Tuple[Dict, int]:
        """`serve_closed_exaone.Driver.gaps` over this model's
        reference: the served tokens' gaps, their 99th percentile over
        the positions clear of a near-tie, their mean over all, the
        near-tie share.  With `mode` below f32 the token judged is the
        one the lowered reference puts first (the control)."""
        import jax.numpy as jnp
        import numpy as np
        margins, gaps = [], []
        for r in requests:
            tokens = r["tokens"]
            seq = (r["prompt"] + tokens)[:-1]
            first = len(r["prompt"]) - 1
            rows = slice(first, first + len(tokens))
            want, margin, _ = self._forward(seq, rows=rows)
            if mode == "f32":
                judged = jnp.asarray(tokens, jnp.int32)
            else:
                judged = self._forward(seq, mode=mode,
                                       rows=rows)[0].argmax(-1)
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

    def clear_state_layers(self) -> int:
        """State layers no routed expert lies in front of."""
        pattern = self.config["hybrid_override_pattern"]
        return pattern.split("E")[0].count("M")

    def state_gap(self, mode: str = "f32") -> Tuple[float, int]:
        """The widest relative gap of a head's state to the float32
        reference's, over the probes — the program's own state (`f32`),
        or the lowered reference's (the control) — and how many heads
        were compared."""
        import numpy as np
        worst, heads = 0.0, 0
        for p in self.probes:
            if p["ssm"] is None:
                continue
            seq = (p["prompt"] + p["tokens"])[:-1]
            none = slice(0, 0)
            want = self._forward(seq, rows=none, length=len(seq))[2]
            got = p["ssm"] if mode == "f32" else self._forward(
                seq, rows=none, length=len(seq), mode=mode)[2]
            for g, w in list(zip(got, want))[:self.clear_state_layers()]:
                g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
                gap = np.sqrt(((g - w) ** 2).sum((-2, -1))
                              / np.maximum((w ** 2).sum((-2, -1)), 1e-60))
                worst = max(worst, float(gap.max()))
                heads += gap.size
        return worst, heads

    def check(self, limits: Dict) -> List[Dict]:
        """Each number compared, beside its limit."""
        checks = super().check(limits)
        worst, heads = self.state_gap()
        limit = limits["state_gap_worst_head"]["limit"]
        checks.insert(3, dict(name="state_gap_worst_head", value=worst,
                              limit=limit,
                              ok=heads > 0 and worst <= limit))
        return checks
