"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

    python chip_smoke.py             # one TPU chip: train, serve, kernels
    python chip_smoke.py --chips 4   # four chips: dp=4 training, tp=4 decode

One process, random weights from `--seed`, no network.  It resolves the
device first and exits non-zero unless JAX reports a TPU; any exception
in any phase ends the run non-zero.  Otherwise the LAST line of stdout
is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the lines before it say what each phase did (JSON, one per phase).
"seconds" in them are wall-clock seconds of a cold run, compiles
included: they say how long the smoke takes, never how fast the system
is.

One chip (what the driver runs):
  * train — BERT-base (`models.bert.BERTClassifier`, seq 128, batch 32,
    bf16 compute, remat) through `Estimator.fit`: a few seeded batches,
    several epochs, loss finite at every step and falling; then
    `save_checkpoint` -> `load_orca_checkpoint` into a fresh Estimator
    -> the same `evaluate` loss.
  * serve — `CausalLM` at GPT-2-small widths behind
    `ServingServer(generation_engine=...)`: four concurrent
    `POST /generate` requests answered in full, the decode step
    compiled once, the first decode round's logits compared with the
    repo's oracle (a second engine with `decode_attention="concat"`),
    then the same requests against an int8 KV pool.
  * kernel presence — whether the programs `impl="auto"` lowers to on
    this device contain the Pallas kernels (paged decode, LayerNorm
    fwd+bwd, bias-GELU, flash), and how far each is from its XLA form.

`--chips 4` runs only what exists across chips, and what each is
compared with: the train phase on `mesh_shape={"dp": 4}` against the
same batches on one device, and the serve engine with
`tensor_parallel=4` against the single-device engine.

Every phase is a function of its sizes and of the devices it may use,
so `tests/test_chip_smoke.py` rehearses them at toy widths on the
suite's virtual CPU devices; `main()` itself never passes on a CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
# the one cache rule: the environment's directory when it names one,
# else a fixed path in the checkout (the path is part of the cache key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))

#: BERT-base as `models/bert.py` defaults it, at the fine-tune shape of
#: BASELINE config #5
BERT_BASE = dict(vocab=30522, hidden_size=768, n_head=12, n_block=12,
                 intermediate_size=3072, max_position_len=128)
#: GPT-2 small's widths for `serving/generation/model.py`'s decoder
GPT2_SMALL = dict(vocab=50257, hidden_size=768, n_head=12, n_block=12,
                  intermediate_size=3072, max_position_len=1024)
#: the serving engine of the smoke; prompts of 64-512 tokens use the
#: three middle buckets, the top one is the engine's own requirement
ENGINE = dict(max_slots=8, block_size=16, max_context=1024,
              prefill_buckets=(128, 256, 512, 1024))
PROMPT_LENS = (64, 160, 300, 512)
MAX_NEW_TOKENS = 32
#: the BERT fine-tune rate.  From a random init, post-LN
#: BERT-base takes no more without warm-up (at 1e-4 the loss jumps
#: about), and at this rate Adam fits the smoke's four batches in
#: sixteen steps — at BERT-base as at the tests' toy width
LEARNING_RATE = 2e-5
#: first decode round, paged kernel vs the concat oracle / tp vs one
#: device: largest |logit difference| over the largest |logit|.  The
#: weights are random, so logits are near-tied and a bf16 reordering
#: may flip an argmax — the logits decide, token agreement is printed.
LOGITS_TOL = 0.03
#: per-step loss, dp=N vs one device on the same global batches (bf16
#: activations, a different reduction order, Adam's sign-like steps)
DP_LOSS_TOL = 0.02
#: a Pallas kernel vs its XLA form at the smoke's shapes, same measure
#: as LOGITS_TOL (bf16 operands)
KERNEL_TOL = 0.05


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def relative_gap(got, want) -> float:
    """max |got - want| over max |want| — the smoke's one measure of
    how far two float arrays are apart."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


@contextlib.contextmanager
def orca_context(devices, mesh_shape=None):
    """The runtime over `devices`.  When that is every device JAX has,
    this is the user's own `init_orca_context("local", mesh_shape=...)`.
    A subset — the one-device comparison on a four-chip host, four of
    the test suite's eight virtual devices — has no public spelling
    (`init_orca_context` takes all of `jax.devices()`), so the mesh is
    built by the context's own helper and installed the way
    `__graft_entry__._dryrun_mesh` does."""
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
    """Wall seconds the repo's dispatch ledger has charged to first
    (compiling) dispatches so far."""
    from analytics_zoo_tpu.observability import profiling
    return float(profiling.ledger_snapshot()["compile_seconds_total"])


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------

def make_batches(seed: int, n_batches: int, batch: int, seq: int,
                 vocab: int):
    """Seeded batches of random tokens whose label is a function of the
    input: the segment id of the first eighth of the sequence IS the
    class (the rest is segment 0), so the signal is there from the
    first step but has to be found among the other positions."""
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


def new_estimator(model_kw, seed: int):
    from analytics_zoo_tpu.models.bert import BERTClassifier
    from analytics_zoo_tpu.orca.learn import Estimator
    model = BERTClassifier(num_classes=2, hidden_drop=0.0, attn_drop=0.0,
                           remat=True, attn_impl="auto", **model_kw)
    return Estimator.from_flax(
        model, loss="sparse_categorical_crossentropy", optimizer="adam",
        learning_rate=LEARNING_RATE, seed=seed)


def fit_steps(est, batches, epochs: int):
    """`Estimator.fit` over the same batches for `epochs` epochs, one
    call per batch so that every STEP's loss comes back (a fit reports
    per epoch).  Returns the per-step losses; raises unless every one
    is finite."""
    import numpy as np
    losses = []
    for _ in range(epochs):
        for b in batches:
            est.fit(b, epochs=1, batch_size=len(b["y"]), shuffle=False)
            stats = est.train_summary[-1]
            if stats.get("nan_steps") or not np.isfinite(stats["loss"]):
                raise RuntimeError(
                    f"step {len(losses) + 1}: non-finite loss/gradients "
                    f"({stats})")
            losses.append(float(stats["loss"]))
    return losses


def param_bytes_by_device(tree):
    """Bytes each device actually holds of `tree` (a shard counts where
    its buffer lives, so four views of one device would show as one
    device)."""
    import jax
    held = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            held[key] = held.get(key, 0) + shard.data.nbytes
    return held


def train_phase(devices, *, model_kw=BERT_BASE, batch=32, seq=128,
                n_batches=4, epochs=4, seed=0):
    """Fit, then the checkpoint round trip through a fresh Estimator
    (on a device other than the CPU the save takes the async path of
    `orca/learn/checkpoint.py`)."""
    from analytics_zoo_tpu.observability import now
    t0, c0 = now(), compile_seconds()
    batches = make_batches(seed, n_batches, batch, seq,
                           model_kw["vocab"])
    with orca_context(devices), \
            tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        est = new_estimator(model_kw, seed)
        losses = fit_steps(est, batches, epochs)
        first = sum(losses[:n_batches]) / n_batches
        last = sum(losses[-n_batches:]) / n_batches
        if not last < first:
            raise RuntimeError(
                f"loss did not fall: first epoch {first:.4f}, last "
                f"epoch {last:.4f} ({losses})")
        before = est.evaluate(batches[0], batch_size=batch)["loss"]
        est.model_dir = d
        est.save_checkpoint()
        fresh = new_estimator(model_kw, seed + 1)
        fresh.load_orca_checkpoint(d)
        after = fresh.evaluate(batches[0], batch_size=batch)["loss"]
        if before != after:
            raise RuntimeError(
                f"checkpoint round trip moved the evaluate loss: "
                f"{before!r} -> {after!r}")
        held = param_bytes_by_device(est._engine.state.params)
    return dict(phase="train", steps=len(losses), losses=losses,
                first_epoch_loss=first, last_epoch_loss=last,
                checkpoint_eval_loss=[before, after],
                param_bytes_by_device=held,
                compile_seconds=compile_seconds() - c0,
                seconds=now() - t0)


def dp_phase(devices, *, model_kw=BERT_BASE, batch=128, seq=128,
             n_batches=4, epochs=2, seed=0):
    """The train steps data-parallel over all of `devices`, then the
    same seeded batches on the first device alone: per-step losses
    within DP_LOSS_TOL, and a full copy of the parameters resident on
    every device of the mesh."""
    import jax

    from analytics_zoo_tpu.observability import now
    t0, c0 = now(), compile_seconds()
    n = len(devices)
    batches = make_batches(seed, n_batches, batch, seq,
                           model_kw["vocab"])
    with orca_context(devices, {"dp": n}):
        est = new_estimator(model_kw, seed)
        losses_dp = fit_steps(est, batches, epochs)
        held = param_bytes_by_device(est._engine.state.params)
        total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            est._engine.state.params))
        del est
    if sorted(held) != sorted(str(d) for d in devices) \
            or any(b != total for b in held.values()):
        raise RuntimeError(
            f"parameters are not resident on all {n} devices: "
            f"{held} (a full copy is {total} bytes)")
    with orca_context(devices[:1]):
        est = new_estimator(model_kw, seed)
        losses_one = fit_steps(est, batches, epochs)
        del est
    gap = max(abs(a - b) for a, b in zip(losses_dp, losses_one))
    if gap > DP_LOSS_TOL:
        raise RuntimeError(
            f"dp={n} losses differ from one device by {gap} > "
            f"{DP_LOSS_TOL}: {losses_dp} vs {losses_one}")
    return dict(phase=f"train_dp{n}", steps=len(losses_dp),
                losses_dp=losses_dp, losses_one_device=losses_one,
                max_step_loss_gap=gap, tolerance=DP_LOSS_TOL,
                param_bytes_by_device=held,
                compile_seconds=compile_seconds() - c0,
                seconds=now() - t0)


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------

def new_lm(model_kw, seed: int, device):
    """The decoder and its seeded random weights, committed to `device`
    (an engine without tensor parallelism runs where its params are)."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.serving.generation import CausalLM
    model = CausalLM(compute_dtype=jnp.bfloat16, **model_kw)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32),
        jnp.arange(8)[None])["params"]
    return model, jax.device_put(params, device)


def new_engine(model, params, engine_kw, **kw):
    import jax.numpy as jnp

    from analytics_zoo_tpu.observability.registry import MetricsRegistry
    from analytics_zoo_tpu.serving.generation import GenerationEngine
    return GenerationEngine(model, params, cache_dtype=jnp.bfloat16,
                            registry=MetricsRegistry(), seed=0,
                            **dict(engine_kw, **kw))


def make_prompts(seed: int, lens, vocab: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab, n)] for n in lens]


def first_round(engine, prompts, max_new: int):
    """Submit every prompt, run ONE scheduling round (admit and prefill
    them all, then one decode step over all their lanes) and take that
    decode step's logits; then run the requests out.  Returns (logits
    [slots, vocab], the generated tokens per prompt).  The logits are
    the decode program's own fourth output, which the engine's loop
    drops: the compiled step is wrapped for this one round."""
    import numpy as np
    ledgered = engine._decode_jit
    step, taken = ledgered.fn, []

    def tapped(*args):
        out = step(*args)
        taken.append(out[3])
        return out

    ledgered.fn = tapped
    try:
        streams = [engine.submit(p, max_new_tokens=max_new,
                                 temperature=0.0) for p in prompts]
        engine.step()
    finally:
        ledgered.fn = step
    if len(taken) != 1 or len(engine.scheduler.running()) != len(prompts):
        raise RuntimeError(
            "the first scheduling round did not decode every request "
            f"once ({len(taken)} decode steps, "
            f"{len(engine.scheduler.running())} of {len(prompts)} lanes)")
    engine.run_until_idle()
    return np.asarray(taken[0], np.float32), [s.tokens() for s in streams]


def token_agreement(a, b) -> float:
    same = sum(x == y for ta, tb in zip(a, b) for x, y in zip(ta, tb))
    return same / max(1, sum(len(t) for t in a))


def generate_over_http(engine, prompts, max_new: int):
    """The engine behind `ServingServer`, every prompt POSTed to
    /generate at once through the repo's client; each must come back
    in full."""
    from analytics_zoo_tpu.serving.client import InputQueue
    from analytics_zoo_tpu.serving.server import ServingServer
    server = ServingServer(generation_engine=engine, host="127.0.0.1",
                           port=0).start()

    def ask(prompt):
        client = InputQueue(host=server.host, port=server.port)
        tokens = client.generate_tokens(prompt, max_new_tokens=max_new)
        return tokens, client.last_generate
    try:
        with ThreadPoolExecutor(len(prompts)) as pool:
            answers = list(pool.map(ask, prompts))
    finally:
        server.stop()
    for tokens, done in answers:
        if len(tokens) != max_new or done["finish_reason"] != "length":
            raise RuntimeError(f"/generate answered short: {done}")
    return [tokens for tokens, _ in answers]


def serve_phase(devices, *, model_kw=GPT2_SMALL, engine_kw=ENGINE,
                prompt_lens=PROMPT_LENS, max_new=MAX_NEW_TOKENS, seed=0):
    from analytics_zoo_tpu.observability import now
    t0, c0 = now(), compile_seconds()
    prompts = make_prompts(seed, prompt_lens, model_kw["vocab"])
    model, params = new_lm(model_kw, seed, devices[0])
    engine = new_engine(model, params, engine_kw)
    engine.warmup()
    logits, direct = first_round(engine, prompts, max_new)
    oracle = new_engine(model, params, engine_kw,
                        decode_attention="concat")
    want, oracle_tokens = first_round(oracle, prompts, max_new)
    del oracle
    gap = relative_gap(logits, want)
    if gap > LOGITS_TOL:
        raise RuntimeError(
            f"first decode round: paged logits are {gap} from the "
            f"concat oracle's (> {LOGITS_TOL})")
    served = generate_over_http(engine, prompts, max_new)
    compiles = engine.decode_compile_count
    if compiles != 1:
        raise RuntimeError(
            f"the decode step compiled {compiles} times, not once")
    del engine
    int8 = new_engine(model, params, engine_kw, kv_quantization="int8")
    served_int8 = generate_over_http(int8, prompts, max_new)
    return dict(phase="serve", requests=len(prompts),
                prompt_lens=list(prompt_lens),
                tokens_generated=sum(map(len, served)),
                tokens_generated_int8=sum(map(len, served_int8)),
                decode_compile_count=compiles,
                first_round_logits_gap=gap, tolerance=LOGITS_TOL,
                token_agreement_with_oracle=token_agreement(
                    direct, oracle_tokens),
                token_agreement_http_vs_direct=token_agreement(
                    served, direct),
                token_agreement_int8_vs_bf16=token_agreement(
                    served_int8, served),
                compile_seconds=compile_seconds() - c0,
                seconds=now() - t0)


def tp_phase(devices, *, model_kw=GPT2_SMALL, engine_kw=ENGINE,
             prompt_lens=PROMPT_LENS, max_new=MAX_NEW_TOKENS, seed=0):
    """The engine tensor-parallel over all of `devices` against the
    single-device engine on the same params: first-round logits within
    LOGITS_TOL, the KV pool head-sharded, and by name every parameter a
    `TP_PARAM_RULES` rule covers that `serving/distributed/tp.py` left
    whole on every device because "tp" does not divide its dim."""
    import jax

    from analytics_zoo_tpu.observability import now
    from analytics_zoo_tpu.serving.distributed.tp import TP_PARAM_RULES
    t0, c0 = now(), compile_seconds()
    n = len(devices)
    prompts = make_prompts(seed, prompt_lens, model_kw["vocab"])
    with orca_context(devices, {"tp": n}):
        model, params = new_lm(model_kw, seed, devices[0])
        one = new_engine(model, params, engine_kw)
        want, one_tokens = first_round(one, prompts, max_new)
        del one
        engine = new_engine(model, params, engine_kw, tensor_parallel=n)
        logits, tokens = first_round(engine, prompts, max_new)
        gap = relative_gap(logits, want)
        if gap > LOGITS_TOL:
            raise RuntimeError(
                f"first decode round: tp={n} logits are {gap} from the "
                f"single-device engine's (> {LOGITS_TOL})")
        compiles = engine.decode_compile_count
        if compiles != 1:
            raise RuntimeError(
                f"the tp decode step compiled {compiles} times, not once")
        kv = engine.cache.kv
        # heads are contiguous slices of the pool's merged last axis
        shard_cols = {s.data.shape[3] for s in kv.addressable_shards}
        kv_devices = {str(s.device) for s in kv.addressable_shards}
        if shard_cols != {kv.shape[3] // n} or len(kv_devices) != n:
            raise RuntimeError(
                f"KV pool is not head-sharded over {n} devices: shard "
                f"columns {shard_cols}, devices {sorted(kv_devices)}")
        # paths joined as `parallel/sharding.py` joins them to match
        # a rule ("block_0_qkv/kernel" contains "qkv/kernel")
        named = [("/".join(str(k.key) for k in path), leaf) for path, leaf
                 in jax.tree_util.tree_flatten_with_path(engine.params)[0]]
        left_whole = sorted(
            name for name, leaf in named
            if any(rule in name for rule in TP_PARAM_RULES)
            and "tp" not in str(leaf.sharding.spec))
        per_device_kv = engine._tp.per_device_kv_bytes(engine.cache)
        held = param_bytes_by_device(engine.params)
    return dict(phase=f"serve_tp{n}", requests=len(prompts),
                first_round_logits_gap=gap, tolerance=LOGITS_TOL,
                token_agreement_with_one_device=token_agreement(
                    tokens, one_tokens),
                decode_compile_count=compiles,
                per_device_kv_bytes=per_device_kv,
                kv_bytes=int(kv.nbytes),
                param_bytes_by_device=held,
                params_left_replicated_by_tp_rules=left_whole,
                compile_seconds=compile_seconds() - c0,
                seconds=now() - t0)


# ---------------------------------------------------------------------
# kernel presence
# ---------------------------------------------------------------------

def kernel_presence(*, batch=32, seq=128, hidden=768, heads=12,
                    inter=3072, lanes=8, block=16, table=64, seed=0):
    """For each Pallas kernel of the two paths, at the smoke's shapes:
    whether the program `impl="auto"` lowers to on the default device
    contains it (`tpu_custom_call` in the lowered text — a dispatcher
    that fell back to its XLA form, or an `interpret` resolved to the
    interpreter, leaves none), and how far its result is from the XLA
    form's.  Returns {kernel: {"present": bool, "gap": float}}."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.ops.attention import (
        dot_product_attention,
        paged_decode_attention,
    )
    from analytics_zoo_tpu.ops.dense import dense_bias_gelu
    from analytics_zoo_tpu.ops.normalization import layer_norm
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        flash_attention)

    rng = np.random.default_rng(seed)
    hd = hidden // heads
    rows = batch * seq
    nb = lanes * table + 1
    bf16 = jnp.bfloat16

    def rand(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def ln_grads(impl):
        return jax.grad(
            lambda x, s, b: (layer_norm(x, s, b, impl=impl)
                             * jnp.arange(hidden)).sum(),
            argnums=(0, 1, 2))

    def auto_and_xla(op, **kw):
        return partial(op, impl="auto", **kw), partial(op, impl="xla", **kw)

    # the pool whole, as the engine's decode step hands it over
    lane, pool = (lanes, heads, hd), (2, 2, nb, block, heads * hd)
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1).reshape(lanes, table), jnp.int32)
    ctx = jnp.asarray(rng.integers(1, table * block, lanes), jnp.int32)
    # name: (auto form, XLA form, arguments, Pallas calls expected)
    cases = {
        "paged_decode": (
            *auto_and_xla(paged_decode_attention, layer=1,
                          compute_dtype=bf16),
            (rand(lane, bf16), rand(lane, bf16), rand(lane, bf16),
             rand(pool, bf16), tables, ctx), 1),
        # the forward AND the backward kernel
        "layer_norm_fwd_bwd": (
            ln_grads("auto"), ln_grads("xla"),
            (rand((rows, hidden), jnp.float32),
             rand((hidden,), jnp.float32),
             rand((hidden,), jnp.float32)), 2),
        "bias_gelu": (
            *auto_and_xla(dense_bias_gelu),
            (rand((rows, hidden), bf16),
             rand((hidden, inter), bf16, hidden ** -0.5),
             rand((inter,), bf16)), 1),
        # flash has no dispatcher at this length (`MultiHeadAttention`
        # picks it from t >= 4096): its "auto" is the kernel's own
        # `interpret=None`, its XLA form the einsum attention
        "flash": (
            flash_attention,
            partial(dot_product_attention, compute_dtype=bf16),
            tuple(rand((batch, seq, heads, hd), bf16)
                  for _ in range(3)), 1),
    }
    out = {}
    for name, (auto, xla, args, n_calls) in cases.items():
        auto = jax.jit(auto)
        text = auto.lower(*args).as_text()
        got = jax.tree_util.tree_leaves(auto(*args))
        want = jax.tree_util.tree_leaves(jax.jit(xla)(*args))
        out[name] = dict(
            present=text.count("tpu_custom_call") >= n_calls,
            gap=max(relative_gap(g, w) for g, w in zip(got, want)))
    return out


# ---------------------------------------------------------------------

def resolve_devices(chips: int):
    """The `chips` devices the run may use — or exit non-zero: a smoke
    that passed without a TPU would say nothing about the chip."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX found "
                 f"{len(devices)} device(s)")
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = resolve_devices(args.chips)
    # before a word is printed: beside nothing but this file, the run
    # ends here
    import analytics_zoo_tpu  # noqa: F401
    say(phase="start", chips=args.chips, seed=args.seed,
        device_kind=devices[0].device_kind,
        compile_cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"])
    if args.chips == 1:
        say(**train_phase(devices, seed=args.seed))
        say(**serve_phase(devices, seed=args.seed))
        kernels = kernel_presence(seed=args.seed)
        say(phase="kernel_presence", tolerance=KERNEL_TOL, **kernels)
        bad = [k for k, v in kernels.items()
               if not v["present"] or v["gap"] > KERNEL_TOL]
        if bad:
            raise RuntimeError(
                f"kernels absent from the auto program, or off their "
                f"XLA form by more than {KERNEL_TOL}: {bad}")
    else:
        say(**dp_phase(devices, seed=args.seed))
        say(**tp_phase(devices, seed=args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
