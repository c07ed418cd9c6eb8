"""Greedy serving scenarios for tests/test_round_ahead.py.

Like tests/_lane_cases.py, everything here goes through the engine's
public surface, so the file runs against another commit's package.
`tests/data/round_ahead_parent_tokens.json` holds what PR 33's parent
(416e12d), which fetched a round's tokens before it enqueued the next,
served in each scenario on each decoder, made by

    cd <checkout of that commit> && JAX_PLATFORMS=cpu \\
        PYTHONPATH=$PWD:<this directory> python <this file> \\
        > round_ahead_parent_tokens.json
"""

from __future__ import annotations

import json
import sys

import numpy as np

import _lane_cases as lane_cases

GRID = dict(max_slots=3, block_size=4, max_context=64)
MODELS = {"causal": 61, "decoder": 97}       # kind -> vocabulary


def requests(seed: int, vocab: int, new_tokens, lo: int = 4,
             hi: int = 20):
    rng = np.random.default_rng(seed)
    return [dict(prompt=[int(t) for t in
                         rng.integers(0, vocab, int(rng.integers(lo, hi)))],
                 max_new_tokens=n) for n in new_tokens]


def served(engine, streams):
    engine.run_until_idle()
    return [s.tokens() for s in streams]


def length(new_engine, vocab):
    """Three lanes that stop by length, a dozen to forty rounds."""
    engine = new_engine()
    return served(engine, [engine.submit(**r) for r in
                           requests(1, vocab, (12, 25, 40))])


def eos(new_engine, vocab):
    """A request as it runs free, then again with its sixth token as
    `eos` beside a neighbour that has another's: both stop at the
    first one they sample."""
    engine = new_engine()
    first, second = requests(2, vocab, (24, 24))
    free = [engine.generate(**first), engine.generate(**second)]
    stopped = served(engine, [
        engine.submit(**first, eos_id=free[0][5]),
        engine.submit(**second, eos_id=free[1][9])])
    return free + stopped


def join(new_engine, vocab):
    """Requests joining a running batch three rounds apart."""
    engine = new_engine()
    streams = []
    for request in requests(3, vocab, (30, 10, 18)):
        streams.append(engine.submit(**request))
        for _ in range(3):
            engine.step()
    return served(engine, streams)


def readmit(new_engine, vocab):
    """Two lanes for six requests: each lane freed and taken again."""
    engine = new_engine(max_slots=2)
    return served(engine, [engine.submit(**r) for r in
                           requests(4, vocab, (3, 9, 1, 12, 5, 7))])


def preempt(new_engine, vocab):
    """Three lanes that want 13 blocks each over a pool of 20: the
    newest yields its lane and is prefilled again."""
    engine = new_engine(num_blocks=21)
    return served(engine, [engine.submit(**r) for r in
                           requests(5, vocab, (36, 36, 36), 12, 16)])


def shared(reqs):
    for r in reqs[1:]:
        r["prompt"] = (reqs[0]["prompt"][:12] + r["prompt"])[:30]
    return reqs


def prefix_cache(new_engine, vocab):
    engine = new_engine(prefix_caching=True)
    reqs = shared(requests(6, vocab, (8, 14, 6, 10), 14, 20))
    return served(engine, [engine.submit(**r) for r in reqs])


def chunked(new_engine, vocab):
    engine = new_engine(chunked_prefill=True, prefill_token_budget=16)
    return served(engine, [engine.submit(**r) for r in
                           requests(7, vocab, (8, 14, 6, 10), 20, 40)])


def speculation(new_engine, vocab):
    engine = new_engine(speculative_decoding=True, speculative_k=4)
    reqs = requests(8, vocab, (20, 20, 20, 20))
    for r in reqs:
        r["prompt"] = (r["prompt"][:5] * 6)[:28]
    return served(engine, [engine.submit(**r) for r in reqs])


def host_tier(new_engine, vocab):
    """A pool too small for the prefixes it has seen: they spill to
    the host and a later request's match is restored from there."""
    engine = new_engine(prefix_caching=True, kv_host_tier=1 << 20,
                        max_slots=2, num_blocks=17)
    reqs = shared(requests(9, vocab, (6, 8, 6, 8, 6, 8), 14, 20))
    out = []
    for wave in (reqs[:2], reqs[2:]):   # the second one has to queue
        out += served(engine, [engine.submit(**r) for r in wave])
    return out


SCENARIOS = {f.__name__: f for f in (
    length, eos, join, readmit, preempt, prefix_cache, chunked,
    speculation, host_tier)}


def serve(kind: str, name: str, models, on_engine=None):
    """Run scenario `name` on decoder `kind`; `on_engine(engine)` is
    the tests' tap, after warm-up.  Returns the tokens of every
    request and the preemptions."""
    from analytics_zoo_tpu.observability import MetricsRegistry
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    model, params = models[kind]
    made = []

    def new_engine(**options):
        engine = GenerationEngine(
            model, params, registry=MetricsRegistry(), seed=3,
            **dict(GRID, **options))
        engine.warmup()
        made.append(engine)
        if on_engine is not None:
            on_engine(engine)
        return engine

    try:
        tokens = SCENARIOS[name](new_engine, MODELS[kind])
        return dict(tokens=tokens,
                    preemptions=made[0].scheduler.n_preemptions,
                    decode_compile_count=made[0].decode_compile_count)
    finally:
        for engine in made:
            engine.stop()


def main() -> None:
    models = {"causal": lane_cases.causal_lm(),
              "decoder": lane_cases.decoder_lm()}
    json.dump({kind: {name: serve(kind, name, models)
                      for name in SCENARIOS} for kind in MODELS},
              sys.stdout, indent=0)


if __name__ == "__main__":
    main()
