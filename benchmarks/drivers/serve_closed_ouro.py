"""`serve_closed`'s loop over the looped decoder (`DecoderLM` by
`ouro`'s keys: 48 layers run 4 times a token over one set of weights,
a pool of 4 x 48 cache slots) and its own plain reference
(`reference/ouro_ref.py`, a Python loop over steps and layers).

The loop, the clients, the reduction and the sample are
`serve_closed.Driver`'s, untouched.  What is new is what has to be:
`build` (the module from the configuration's published keys, bfloat16
leaves, the benchmark's weights), `gaps` and `check` (the new
reference; the control rounds the CACHED KEYS AND VALUES of every slot
to fp8_e4m3, the precision below the pool's bfloat16, and nothing
else), and the window's record, which gains the program's own numbers
of what a token holds (`generation_kv_row_bytes`,
`generation_loop_steps`, `generation_kv_layer_slots`) and the decode
rounds of the window, which the looped readers read; and, in a traced
run, a tracer started and stopped with the device quiet
(`QuietEdgesTracer`), since this cell leaves the chip no idle time in
which a round could end before the trace's window does.  The median first
token and the gap tail are no end-to-end metric of this cell (a closed
loop of as many clients as lanes runs at capacity): every line keeps
them under `detail`.

`correct` compares, over what the window itself served:

  * `served_logit_gap_p99` — `serve_closed`'s gap (how far a served
    token's float32-reference logit lies below the reference's best at
    its position), its 99th percentile over every served position of
    the sample: no routing makes a near-tie here, but 192 bfloat16
    layer applications leave a tie of random logits flipped now and
    then, each a lone gap of a few hundredths;
  * `served_logit_gap_mean` — the same gap averaged over every
    position: a lowered precision moves most of them;
  * `served_tokens_compared` — at least one."""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Tuple

from benchmarks.drivers import serve_closed
from benchmarks.harness import builders, weights

GAUGES = dict(row_bytes="generation_kv_row_bytes",
              loop_steps="generation_loop_steps",
              layer_slots="generation_kv_layer_slots")


#: how long the engine stays held once the profiler's stop has begun:
#: the device's part of the session ends within a few milliseconds of
#: the call (on a v5e its last event lies about 1 ms past the host's
#: stamp), the export after it takes seconds and needs no quiet device
STOP_GRACE_S = 0.5


class QuietEdgesTracer:
    """The run's tracer, started and stopped with the engine held between
    rounds and nothing of it in flight on the device.

    This cell keeps the chip busy all through a round (a closed loop at
    capacity, the next round enqueued before the last is collected): a
    round that runs across either edge of the profiler's session lands
    in the trace with part of it outside the host's window, and the
    device's busy time then exceeds the window it is read over.  Held
    and drained, the device runs nothing from before the tracer stamps
    its start until after it stamps its stop.  The engine waits out the
    profiler's start (some 50 ms) and the first `STOP_GRACE_S` of its
    stop; the stop's export (tens of seconds here) runs on beside the
    serving, in a thread of its own, as it always did, and `join` waits
    for it.  `held_s` keeps what each edge held the engine."""

    def __init__(self, tracer, engine):
        self.tracer, self.engine = tracer, engine
        self.held_s: Dict[str, float] = {}
        self._stopping = None

    def start(self) -> None:
        t0 = serve_closed.now()
        with self.engine._lock:
            self.engine._drain("idle")
            self.tracer.start()
        self.held_s["start"] = serve_closed.now() - t0

    def stop(self) -> None:
        t0 = serve_closed.now()
        with self.engine._lock:
            self.engine._drain("idle")
            self._stopping = threading.Thread(
                target=self.tracer.stop, name="trace-stop", daemon=True)
            self._stopping.start()
            while self.tracer.t_stop is None:    # stamped, stop called
                time.sleep(0.001)
            time.sleep(STOP_GRACE_S)
        self.held_s["stop"] = serve_closed.now() - t0

    def join(self) -> None:
        if self._stopping is not None:
            self._stopping.join()


class Driver(serve_closed.Driver):
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        # a program without the looped form (the parent of the PR that
        # brought this cell) ends here, at once and non-zero
        from analytics_zoo_tpu.serving.generation.decoder import (
            DecoderLM, Scales)  # noqa: F401
        self.module = DecoderLM
        # the loop asks the configuration for the vocabulary it draws
        # prompts from under `serve_closed`'s key
        config = dict(config, model={"vocab": int(config["vocab_size"])})
        super().__init__(config, traffic, devices, seed)

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

    def decode_rounds(self) -> int:
        """Decode rounds the engine has collected so far."""
        return int(self.engine.registry.snapshot()[
            "generation_decode_seconds"]["calls"])

    def window(self, seconds: float, tracer) -> Dict:
        snap = self.engine.registry.snapshot()
        kv = {key: snap[name] for key, name in GAUGES.items()}
        rounds = self.decode_rounds()
        quiet = (QuietEdgesTracer(tracer, self.engine)
                 if tracer is not None else None)
        result = super().window(seconds, quiet)
        if quiet is not None:
            quiet.join()
        # (the clients' drain is in it: rounds are counted until the
        # last request in flight has ended)
        result["kv"] = dict(kv, rounds=self.decode_rounds() - rounds)
        result["detail"]["kv"] = dict(result["kv"])
        if quiet is not None:
            result["detail"]["trace_edges_held_s"] = quiet.held_s
        result["detail"].update(
            (k, v) for k, v in result["end_to_end"].items()
            if k != "serve_tokens_per_s")
        return result

    # -- after the window ----------------------------------------------

    def gaps(self, requests: List[Dict], mode: str = "f32"
             ) -> Tuple[Dict, int]:
        """Over every served token of `requests`: the widest gap by
        which the token's reference logit lies below the reference's
        best at its position (`gap`), its 99th percentile (`gap_p99`)
        and its mean (`gap_mean`), and how many tokens were looked at.
        With `mode` "fp8" the token judged is the one the reference puts
        first over keys and values cached in fp8 (the control)."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.reference import ouro_ref as ref
        length = int(self.config["engine"]["max_context"])
        gaps = []
        for r in requests:
            tokens = r["tokens"]
            seq = (r["prompt"] + tokens)[:-1]
            padded = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
            first = len(r["prompt"]) - 1
            rows = slice(first, first + len(tokens))
            want = ref.forward(self.params, padded, self.config,
                               rows=rows)[0]
            if mode == "f32":
                judged = jnp.asarray(tokens, jnp.int32)
            else:
                judged = ref.forward(self.params, padded, self.config,
                                     mode=mode, rows=rows)[0].argmax(-1)
            gaps.append(np.asarray(want.max(-1) - jnp.take_along_axis(
                want, judged[:, None], axis=-1)[:, 0]))
        if not gaps:
            return dict(gap=0.0, gap_p99=0.0, gap_mean=0.0), 0
        below = np.concatenate(gaps)
        return dict(gap=float(below.max()),
                    gap_p99=float(np.quantile(below, 0.99)),
                    gap_mean=float(below.mean())), len(below)

    def check(self, limits: Dict) -> List[Dict]:
        """Each number compared, beside its limit."""
        read, compared = self.gaps(self.sample())

        def under(name, value):
            limit = limits[name]["limit"]
            return dict(name=name, value=value, limit=limit,
                        ok=compared > 0 and value <= limit)
        return [
            under("served_logit_gap_p99", read["gap_p99"]),
            under("served_logit_gap_mean", read["gap_mean"]),
            dict(name="served_tokens_compared", value=compared,
                 limit=1, ok=compared >= 1),
        ]
