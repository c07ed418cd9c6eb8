"""Closed-loop serving traffic: as many clients as the traffic file says,
each sending its next `POST /generate` when the last is answered.

That is how Analytics Zoo's Cluster Serving is fed — consumers pulling
from a backlogged queue — and it needs no arrival rate to be found
first.  Everything a cell of this kind varies is in its traffic file:
the number of clients, the two length distributions, the size of the
deck, the traced part of the window and the size of the checked sample.

Every seed gets the same work in another order: the (prompt length,
output length) pairs are a fixed deck of stratified quantiles of the
two distributions, shuffled by the seed and dealt to the clients in
turn; the token ids are drawn from the seed.

All times are the clients' own clock: a token is stamped when the
streaming client hands it over."""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from benchmarks.harness import builders, stats

now = time.perf_counter

#: how long the clients may take to finish what is in flight when the
#: window closes, and to answer their first request during set-up
DRAIN_S = 90.0
FIRST_ANSWER_S = 900.0


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """`n` stratified draws of a length distribution: the mid-quantiles
    of `uniform` or `log_uniform` over [low, high], as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["low"]), float(dist["high"])
    if dist["dist"] == "uniform":
        x = lo + (hi - lo) * u
    elif dist["dist"] == "log_uniform":
        x = np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * u)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.rint(x).astype(np.int64)


def deck(traffic: Dict) -> List[Tuple[int, int]]:
    """The fixed multiset of (prompt length, output length) every seed
    plays: both distributions' quantiles, paired by a fixed shuffle so
    that long prompts do not always get long answers."""
    n = int(traffic["deck"])
    prompt = quantiles(traffic["prompt_len"], n)
    new = quantiles(traffic["max_new_tokens"], n)
    new = new[np.random.default_rng(0).permutation(n)]
    return [(int(p), int(m)) for p, m in zip(prompt, new)]


def request_stream(traffic: Dict, vocab: int, seed: int, client: int
                   ) -> Iterator[Tuple[List[int], int]]:
    """Client `client`'s requests, without end: the seed's shuffles of
    the deck, one after another, dealt to the clients in turn."""
    cards = deck(traffic)
    clients = int(traffic["clients"])
    order = np.random.default_rng([int(seed), 1])
    position, rounds = client, 0
    perm = order.permutation(len(cards))
    while True:
        while position >= len(cards):
            position -= len(cards)
            rounds += 1
            perm = order.permutation(len(cards))
        n_prompt, n_new = cards[perm[position]]
        ids = np.random.default_rng(
            [int(seed), 2, rounds, position]).integers(0, vocab, n_prompt)
        yield [int(t) for t in ids], n_new
        position += clients


class Driver:
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.records: List[Dict] = []
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._answered = [0] * int(traffic["clients"])
        self.engine = self.server = None

    # -- set-up --------------------------------------------------------

    def build(self) -> None:
        """The model, its seeded weights, the engine with every program
        of the configuration compiled, behind the HTTP server."""
        self.model, self.params = builders.new_lm(self.config["model"],
                                                  self.seed)
        self.engine = builders.new_engine(self.model, self.params,
                                          self.config["engine"])
        self.engine.warmup()
        self.server = builders.new_server(self.engine)

    def setup(self) -> None:
        """Build, start the clients, and wait until each has had one
        request answered: the window opens on a steady state, not on as
        many prefills as there are clients."""
        self.build()
        for idx in range(len(self._answered)):
            th = threading.Thread(target=self._client, args=(idx,),
                                  daemon=True, name=f"client-{idx}")
            th.start()
            self._threads.append(th)
        deadline = now() + FIRST_ANSWER_S
        while min(self._answered) < 1:
            if now() > deadline:
                raise RuntimeError(
                    "set-up: not every client was answered once in "
                    f"{FIRST_ANSWER_S} s ({self._answered})")
            time.sleep(0.01)

    def _client(self, idx: int) -> None:
        client = builders.new_client(self.server)
        temperature = float(self.traffic["temperature"])
        stream = request_stream(self.traffic, self.config["model"]["vocab"],
                                self.seed, idx)
        for n, (prompt, max_new) in enumerate(stream):
            if self._stop.is_set():
                return
            rec = dict(client=idx, n=n, prompt=prompt, max_new=max_new,
                       t_send=now(), stamps=[], tokens=[], error=None,
                       finish=None)
            try:
                for token in client.generate(prompt, max_new_tokens=max_new,
                                             temperature=temperature):
                    rec["stamps"].append(now())
                    rec["tokens"].append(int(token))
                rec["finish"] = (client.last_generate or {}).get(
                    "finish_reason")
            except Exception as e:  # a failed request is a result
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["t_done"] = now()
            self.records.append(rec)
            self._answered[idx] += 1

    # -- the window ----------------------------------------------------

    def window(self, seconds: float, tracer) -> Dict:
        """Measure for `seconds`; the clients are already running.  With
        a tracer, `trace_seconds` of the window are traced from
        `trace_lead_s` on.  Closes the window, then lets every client
        finish the request it has in flight."""
        compiles = builders.compile_seconds()
        t_open = now()
        if tracer is not None:
            time.sleep(float(self.traffic["trace_lead_s"]))
            tracer.start()
            time.sleep(float(self.traffic["trace_seconds"]))
            tracer.stop()
        time.sleep(max(0.0, t_open + seconds - now()))
        t_close = now()
        self._stop.set()
        deadline = now() + DRAIN_S
        for th in self._threads:
            th.join(max(0.0, deadline - now()))
        never = sum(th.is_alive() for th in self._threads)
        self.result = self.reduce(t_open, t_close, never)
        self.result["window_compile_s"] = (builders.compile_seconds()
                                           - compiles)
        self.result["decode_compile_count"] = \
            self.engine.decode_compile_count
        return self.result

    @staticmethod
    def ok(rec: Dict) -> bool:
        return (rec["error"] is None and rec["finish"] == "length"
                and len(rec["tokens"]) == rec["max_new"])

    def reduce(self, t_open: float, t_close: float, never: int = 0) -> Dict:
        """The window's numbers from the clients' records."""
        records = list(self.records)
        inside = lambda t: t_open <= t < t_close
        sent = [r for r in records if inside(r["t_send"])]
        failed = sum(not self.ok(r) for r in sent) + never
        tokens = sum(stats.count_in(r["stamps"], t_open, t_close)
                     for r in records)
        ttft = [(r["stamps"][0] - r["t_send"]) * 1e3 for r in records
                if r["stamps"] and inside(r["stamps"][0])]
        ended = [r for r in records if self.ok(r) and inside(r["t_done"])]
        gaps = [g * 1e3 for g in stats.all_gaps(r["stamps"] for r in ended)]
        seconds = t_close - t_open
        return dict(
            t_open=t_open, t_close=t_close, seconds=seconds,
            attempted=len(sent) + never, failed=failed,
            tokens_in_window=tokens, ttft_ms=ttft, itl_ms=gaps,
            finished=ended, records=records,
            end_to_end={
                "serve_tokens_per_s": tokens / seconds,
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "itl_p95_ms": stats.percentile(gaps, 95),
            },
            # the samples behind the percentiles, and the statistics
            # that are no metric of a `--trace 0` run, for the record
            detail={
                "first_tokens": len(ttft), "requests_ended": len(ended),
                "token_gaps": len(gaps),
                "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
                "ttft_p90_ms": stats.percentile(ttft, 90),
                "ttft_p95_ms": stats.percentile(ttft, 95),
                "itl_p50_ms": stats.percentile(gaps, 50),
            })

    # -- after the window ----------------------------------------------

    def release(self) -> None:
        """Stop the server and the engine and drop the pool; the weights
        stay for the reference."""
        if self.server is not None:
            self.server.stop()
        self.server = self.engine = None

    def sample(self) -> List[Dict]:
        """The checked requests: drawn from the seed among those that
        finished in the window, the longest always among them."""
        done = sorted(self.result["finished"],
                      key=lambda r: (r["client"], r["n"]))
        if not done:
            return []
        longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        k = min(int(self.traffic["check_requests"]) - 1, len(rest))
        pick = np.random.default_rng([self.seed, 3]).choice(
            len(rest), size=k, replace=False) if k > 0 else []
        return [longest] + [rest[i] for i in pick]

    def gaps(self, requests: List[Dict], mode: str = "f32"
             ) -> Tuple[float, int]:
        """The widest gap, over every served token of `requests`, by
        which the token's reference logit lies below the reference's
        best at that position — and how many tokens were compared.  With
        `mode` below f32 the token judged is not the served one but the
        one the lowered reference puts first (the control)."""
        import jax.numpy as jnp

        from benchmarks.reference import transformer_ref as ref
        kw = dict(n_head=self.config["model"]["n_head"],
                  n_block=self.config["model"]["n_block"])
        length = int(self.config["engine"]["max_context"])
        widest, compared = 0.0, 0
        for r in requests:
            tokens = r["tokens"]
            seq = (r["prompt"] + tokens)[:-1]
            padded = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
            first = len(r["prompt"]) - 1
            want = ref.decoder_logits(self.params, padded, **kw)[
                first:first + len(tokens)]
            if mode == "f32":
                judged = jnp.asarray(tokens, jnp.int32)
            else:
                judged = ref.decoder_logits(self.params, padded, mode=mode,
                                            **kw)[
                    first:first + len(tokens)].argmax(-1)
            below = want.max(-1) - jnp.take_along_axis(
                want, judged[:, None], axis=-1)[:, 0]
            widest = max(widest, float(below.max()))
            compared += len(tokens)
        return widest, compared

    def check(self, limits: Dict) -> List[Dict]:
        """Each number compared, beside its limit."""
        widest, compared = self.gaps(self.sample())
        return [
            dict(name="served_logit_gap_max", value=widest,
                 limit=limits["served_logit_gap_max"]["limit"],
                 ok=compared > 0
                 and widest <= limits["served_logit_gap_max"]["limit"]),
            dict(name="served_tokens_compared", value=compared,
                 limit=1, ok=compared >= 1),
        ]
