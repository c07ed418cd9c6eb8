"""Keeps `chip_smoke.py` alive: its phase functions called at toy
widths on the suite's virtual CPU devices (the rehearsals the chip
budget is spared by — a wrong path, argument, mesh or sharding rule
shows here), and its `main()` refusing to pass without a TPU.

What only the chip can say — that Mosaic accepts the kernels, that the
results agree there — is `tests/test_tpu_compile.py`'s and the chip
run's to say; on the CPU every `impl="auto"` takes its XLA form.
"""

import os
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TOY_BERT = dict(vocab=512, hidden_size=128, n_head=4, n_block=2,
                intermediate_size=256, max_position_len=32)
# an odd vocab, like GPT-2's 50257: "tp" cannot divide the head
TOY_LM = dict(vocab=257, hidden_size=128, n_head=4, n_block=2,
              intermediate_size=256, max_position_len=128)
TOY_ENGINE = dict(max_slots=4, block_size=8, max_context=128,
                  prefill_buckets=(16, 32, 64, 128))
TOY_SERVE = dict(model_kw=TOY_LM, engine_kw=TOY_ENGINE,
                 prompt_lens=(9, 20, 40, 64), max_new=8)


def _train(devices):
    out = chip_smoke.train_phase(devices[:1], model_kw=TOY_BERT,
                                 batch=8, seq=32, n_batches=4, epochs=4)
    assert out["steps"] == 16
    assert out["last_epoch_loss"] < out["first_epoch_loss"]
    before, after = out["checkpoint_eval_loss"]
    assert before == after


def _serve(devices):
    out = chip_smoke.serve_phase(devices[:1], **TOY_SERVE)
    assert out["decode_compile_count"] == 1
    assert out["tokens_generated"] == out["tokens_generated_int8"] == 32
    assert out["first_round_logits_gap"] <= chip_smoke.LOGITS_TOL


def _dp4(devices):
    out = chip_smoke.dp_phase(devices[:4], model_kw=TOY_BERT, batch=32,
                              seq=32, n_batches=4, epochs=2)
    assert out["phase"] == "train_dp4" and out["steps"] == 8
    assert out["max_step_loss_gap"] <= chip_smoke.DP_LOSS_TOL
    assert len(out["param_bytes_by_device"]) == 4


def _tp4(devices):
    out = chip_smoke.tp_phase(devices[:4], **TOY_SERVE)
    assert out["phase"] == "serve_tp4"
    assert out["first_round_logits_gap"] <= chip_smoke.LOGITS_TOL
    assert out["per_device_kv_bytes"] * 4 == out["kv_bytes"]
    assert out["params_left_replicated_by_tp_rules"] == [
        "lm_head/bias", "lm_head/kernel"]
    assert len(out["param_bytes_by_device"]) == 4


@pytest.mark.parametrize("phase", [_train, _serve, _dp4, _tp4],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_phase_at_toy_size(phase):
    from analytics_zoo_tpu import stop_orca_context
    try:
        phase(jax.devices())
    finally:
        stop_orca_context()


def test_kernel_presence_reports_the_xla_fallback_on_cpu():
    """On the CPU every dispatcher takes its XLA form (and flash the
    interpreter), so nothing may be reported present — `main()` would
    fail the run on exactly this report."""
    out = chip_smoke.kernel_presence(
        batch=2, seq=64, hidden=128, heads=4, inter=256, lanes=4,
        block=8, table=8)
    assert sorted(out) == ["bias_gelu", "flash", "layer_norm_fwd_bwd",
                           "paged_decode"]
    for name, row in out.items():
        assert row["present"] is False, name
        assert row["gap"] <= chip_smoke.KERNEL_TOL, (name, row)


def test_main_refuses_to_pass_without_a_tpu(capsys):
    """`main()` where JAX finds only the CPU: a non-zero exit and no
    result line, as the driver's sandbox run expects."""
    with pytest.raises(SystemExit) as exit_:
        chip_smoke.main([])
    assert exit_.value.code not in (0, None)
    assert "needs a TPU" in str(exit_.value.code)
    assert '"ok": true' not in capsys.readouterr().out
