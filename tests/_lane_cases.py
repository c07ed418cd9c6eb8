"""Seeded serving runs for tests/test_lane_state.py.

Everything here goes through the engine's public surface (constructor,
`warmup`, `submit`, `step`), so the same file runs against another
commit's package.  `tests/data/lane_state_parent_tokens.json` holds the
tokens the commit before the device-resident lane state served (PR 31's
parent, 8f62d1d), made by

    cd <checkout of that commit> && JAX_PLATFORMS=cpu \\
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=$PWD python <this file> > lane_state_parent_tokens.json

`tests/data/round_ahead_tokens.json` is the same command's output at
PR 33, which collects a round after the next one is enqueued: a lane
that finishes is freed a round later, so in the six cases with no
default-off feature on (`mixed`, `preempted`, `int8_pool`, `concat`,
`decoder_lm`, `tp2`) a waiting request is admitted a round later, the
dispatches split the key in another order, and the SAMPLED requests
get other tokens (as many).  Every greedy request's tokens, every
preemption count and the other five cases whole are the parent's, and
tests/test_lane_state.py holds them to it.
"""

from __future__ import annotations

import json
import sys

import numpy as np

VOCAB = 61
SEED = 11
GRID = dict(max_slots=4, block_size=4, max_context=64)


def requests(seed: int, n: int, vocab: int = VOCAB):
    """`n` requests: prompts of 3-29 tokens, 2-23 new tokens, every
    other lane sampled (temperature 0.8 under top-5, then 1.3)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append(dict(
            prompt=[int(t) for t in
                    rng.integers(0, vocab, int(rng.integers(3, 30)))],
            max_new_tokens=int(rng.integers(2, 24)),
            temperature=[0.0, 0.8, 0.0, 1.3][i % 4],
            top_k=[0, 5, 0, 0][i % 4]))
    return out


def shared_prefixes(reqs):
    """Every other prompt behind the first one's first 12 tokens."""
    for r in reqs[1::2]:
        r["prompt"] = (reqs[0]["prompt"][:12] + r["prompt"])[:36]
    return reqs


def repeating(reqs):
    """Prompts that repeat themselves, so an n-gram draft matches."""
    for r in reqs:
        r["prompt"] = (r["prompt"][:5] * 6)[:28]
        r["max_new_tokens"] = 20
    return reqs


#: name -> (model, requests, engine options).  Block size 4 makes every
#: lane cross a block boundary every fourth round; lanes join in three
#: waves and leave as their budgets run out.
CASES = {
    "mixed": ("causal", requests(1, 10), {}),
    "preempted": ("causal", requests(2, 10), dict(num_blocks=24)),
    "int8_pool": ("causal", requests(3, 8),
                  dict(kv_quantization="int8")),
    "concat": ("causal", requests(4, 8), dict(decode_attention="concat")),
    "prefix_cache": ("causal", shared_prefixes(requests(5, 10)),
                     dict(prefix_caching=True)),
    "chunked": ("causal", requests(6, 10),
                dict(chunked_prefill=True, prefill_token_budget=16)),
    "prefix_chunked_preempted": (
        "causal", shared_prefixes(requests(5, 10)),
        dict(prefix_caching=True, chunked_prefill=True,
             prefill_token_budget=16, num_blocks=28)),
    "speculation": ("causal", repeating(requests(7, 10)),
                    dict(speculative_decoding=True, speculative_k=4)),
    "speculation_chunked": (
        "causal", repeating(requests(7, 10)),
        dict(speculative_decoding=True, speculative_k=4,
             chunked_prefill=True, prefill_token_budget=16)),
    "decoder_lm": ("decoder", requests(9, 8, vocab=97), {}),
    "tp2": ("causal", requests(8, 8), dict(tensor_parallel=2)),
}


def causal_lm():
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.generation import CausalLM
    model = CausalLM(vocab=VOCAB, hidden_size=32, n_head=4, n_block=2,
                     intermediate_size=64, max_position_len=128)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.arange(8)[None])["params"]
    return model, params


def decoder_lm():
    """A toy `DecoderLM`: grouped heads, window and full layers, dense
    and expert FFNs, N(0, 0.05) weights from a seed, norm scales near 1."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.generation import DecoderLM
    model = DecoderLM.from_config(dict(
        vocab_size=97, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, routed_scaling_factor=2.5,
        norm_topk_prob=True, sliding_window=8,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 3,
        rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
        rms_norm_eps=1e-5, max_position_embeddings=4096,
        experts_held=[2, 4]))
    ids = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    rng = np.random.default_rng(0)

    def leaf(path, like):
        scale = str(getattr(path[-1], "key", path[-1])) == "scale"
        value = rng.normal(size=like.shape)
        return jnp.asarray(1.0 + 0.1 * value if scale else 0.05 * value,
                           like.dtype)
    return model, jax.tree_util.tree_map_with_path(leaf, abstract)


def serve(name: str, models, on_engine=None, after_round=None):
    """Run case `name`: three waves of submissions three rounds apart,
    then rounds until idle.  `on_engine(engine)` after warm-up and
    `after_round(engine)` after every round are the tests' taps.
    Returns the tokens of every request, the decode step's compile
    count and the preemptions."""
    from analytics_zoo_tpu.observability import MetricsRegistry
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    kind, reqs, options = CASES[name]
    model, params = models[kind]
    engine = GenerationEngine(model, params, registry=MetricsRegistry(),
                              seed=SEED, **GRID, **options)
    engine.warmup()
    if on_engine is not None:
        on_engine(engine)
    try:
        streams, pending = [], iter(reqs)
        for wave in (3, 2, len(reqs)):
            for _ in range(wave):
                request = next(pending, None)
                if request is not None:
                    streams.append(engine.submit(**request))
            for _ in range(3):
                engine.step()
                if after_round is not None:
                    after_round(engine)
        for _ in range(10_000):
            if not engine.scheduler.has_work():
                break
            engine.step()
            if after_round is not None:
                after_round(engine)
        return dict(tokens=[s.tokens() for s in streams],
                    decode_compile_count=engine.decode_compile_count,
                    preemptions=engine.scheduler.n_preemptions)
    finally:
        engine.stop()


def tp2_context():
    """The mesh the `tp2` case needs; the caller stops it."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    stop_orca_context()
    init_orca_context(cluster_mode="local", mesh_shape={"tp": 2})
    return stop_orca_context


def main() -> None:
    models = {"causal": causal_lm(), "decoder": decoder_lm()}
    out = {}
    for name in CASES:
        stop = tp2_context() if name == "tp2" else None
        try:
            out[name] = serve(name, models)
        finally:
            if stop is not None:
                stop()
    json.dump(out, sys.stdout, indent=0)


if __name__ == "__main__":
    main()
