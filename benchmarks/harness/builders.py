"""The system under test, built the way a user builds it.

Copied from `chip_smoke.py` (PR 22), which proved these calls on the
chip; the yardstick keeps its own copy so that later PRs may change the
smoke.  The one departure: the weights are the benchmark's
(`weights.make_params`), not the modules' own initialisers, so that the
program and the plain reference start from values neither has made."""

from __future__ import annotations

import contextlib

from benchmarks.harness import weights


@contextlib.contextmanager
def orca_context(devices, mesh_shape=None):
    """The runtime over `devices`.  When that is every device JAX has,
    this is the user's own `init_orca_context("local", ...)`; a subset
    (the test suite's virtual devices) has no public spelling, so the
    mesh is built by the context's own helper, as the smoke does."""
    import jax

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.common.context import (
        OrcaContextMeta,
        _build_mesh,
    )
    stop_orca_context()
    if list(devices) == jax.devices():
        mesh = init_orca_context("local", mesh_shape=mesh_shape)
    else:
        mesh = _build_mesh(list(devices), mesh_shape)
        OrcaContextMeta._mesh = mesh
        OrcaContextMeta._initialized = True
        OrcaContextMeta._cluster_mode = "local"
    try:
        yield mesh
    finally:
        stop_orca_context()


def compile_seconds() -> float:
    """Wall seconds the program's dispatch ledger has charged to first
    (compiling) dispatches so far."""
    from analytics_zoo_tpu.observability import profiling
    return float(profiling.ledger_snapshot()["compile_seconds_total"])


# --- serving ----------------------------------------------------------

def new_lm(model_kw, seed: int):
    """The decoder at the configuration's widths, bf16 compute, and its
    seeded weights on the default device."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.generation import CausalLM
    model = CausalLM(compute_dtype=jnp.bfloat16, **model_kw)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.arange(8)[None])["params"]
    return model, weights.make_params(abstract, seed)


def new_engine(model, params, engine_kw):
    """The generation engine as a deployment holds it: bf16 KV pool,
    every default-off feature left off."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.observability.registry import MetricsRegistry
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    kw = dict(engine_kw)
    kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    return GenerationEngine(model, params, cache_dtype=jnp.bfloat16,
                            registry=MetricsRegistry(), seed=0, **kw)


def new_server(engine):
    from analytics_zoo_tpu.serving.server import ServingServer
    return ServingServer(generation_engine=engine, host="127.0.0.1",
                         port=0).start()


def new_client(server):
    from analytics_zoo_tpu.serving.client import InputQueue
    return InputQueue(host=server.host, port=server.port)


# --- training ---------------------------------------------------------

def new_classifier(model_kw, est_kw):
    from analytics_zoo_tpu.models.bert import BERTClassifier
    kw = {k: v for k, v in model_kw.items() if k != "type_vocab_size"}
    return BERTClassifier(
        hidden_drop=est_kw["hidden_drop"], attn_drop=est_kw["attn_drop"],
        remat=est_kw["remat"], remat_policy=est_kw["remat_policy"],
        attn_impl=est_kw["attn_impl"], **kw)


def new_estimator(model_kw, est_kw, seed: int):
    """`Estimator.from_flax` over the classifier, then the seeded
    weights loaded through `set_params` — the path of a user who
    fine-tunes from a pretrained checkpoint.  Returns (estimator, the
    weights as a host tree)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.orca.learn import Estimator
    model = new_classifier(model_kw, est_kw)
    ids = jnp.zeros((1, est_kw["seq_len"]), jnp.int32)
    abstract = jax.eval_shape(
        lambda k: model.init(k, ids, ids, ids), jax.random.PRNGKey(0)
    )["params"]
    params = jax.tree_util.tree_map(
        np.asarray, weights.make_params(abstract, seed))
    est = Estimator.from_flax(
        model, loss=est_kw["loss"], optimizer=est_kw["optimizer"],
        learning_rate=est_kw["learning_rate"], seed=seed & 0x7FFFFFFF)
    est.set_params(params)
    return est, params


def make_batches(seed: int, n_batches: int, batch: int, seq: int,
                 vocab: int):
    """Seeded batches of random tokens whose label is a function of the
    input (the segment id of the first eighth of the sequence is the
    class), as the smoke makes them; every row differs."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        ids = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
        y = rng.integers(0, 2, batch).astype(np.int32)
        seg = np.zeros_like(ids)
        seg[:, :seq // 8] = y[:, None]
        out.append({"x": [ids, seg, np.ones_like(ids)], "y": y})
    return out
