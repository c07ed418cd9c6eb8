"""Operations and bytes a hybrid decoder needs — one sub-layer a layer:
Mamba-2 state-space (`M`), attention (`*`), latent routed experts (`E`)
— from the configuration's published keys alone (`nemotron_h`:
`benchmarks/configs/nemotron3_super_120b_ep4_serve.json`).

The rules are `flops.py`'s: a matmul of [m, k] by [k, n] is 2*m*k*n,
lookups, norms and activations count as nothing, recomputation is never
credited.  What is new here is counted as the chip has to do it, not as
the code does it: the selective scan as the recurrence (a token's
update of and read from its state, 4 * heads * head_dim * state: the
chunked form's extra products are not credited), a routed expert only
for the assignments that fell on an expert held here (the program's own
count), the head over the held slice of the vocabulary.  A decode
round's bytes count the recurrent state of the live lanes once read and
once written."""

from __future__ import annotations

from typing import Dict, Iterable

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
BF16, F32 = 2, 4


def kinds(c: Dict, kind: str) -> int:
    return c["hybrid_override_pattern"].count(kind)


def d_inner(c: Dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: Dict) -> int:
    """The convolution runs over [x | B | C]."""
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_matmul_params(c: Dict) -> int:
    """in_proj to [z | xBC | dt] and out_proj."""
    d = c["hidden_size"]
    return d * (d_inner(c) + conv_channels(c) + c["mamba_num_heads"]) \
        + d_inner(c) * d


def mamba_params(c: Dict) -> int:
    """A whole `M` layer: the two projections, the convolution and its
    bias, A_log, D and dt_bias, the gated norm's scale and the layer's
    own norm."""
    return (mamba_matmul_params(c)
            + (c["conv_kernel"] + 1) * conv_channels(c)
            + 3 * c["mamba_num_heads"] + d_inner(c) + c["hidden_size"])


def attention_matmul_params(c: Dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def attention_params(c: Dict) -> int:
    return attention_matmul_params(c) + c["hidden_size"]


def expert_params(c: Dict) -> int:
    """One routed expert: up and down, in the latent space."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def expert_layer_matmul_params(c: Dict) -> int:
    """What every token of an `E` layer is multiplied by, whatever it
    is routed to: router, both latent projections, the shared expert."""
    d = c["hidden_size"]
    return (d * c["n_routed_experts"] + 2 * d * c["moe_latent_size"]
            + 2 * d * c["moe_shared_expert_intermediate_size"])


def expert_layer_params(c: Dict) -> int:
    """An `E` layer outside its routed experts (the correction bias and
    the layer's norm in)."""
    return (expert_layer_matmul_params(c) + c["n_routed_experts"]
            + c["hidden_size"])


def total_params(c: Dict) -> int:
    """Everything this chip holds: `experts_held` routed experts a
    layer, embedding table and head over the held vocabulary."""
    held = c.get("experts_held", [0, c["n_routed_experts"]])[1]
    return (kinds(c, MAMBA) * mamba_params(c)
            + kinds(c, ATTENTION) * attention_params(c)
            + kinds(c, EXPERTS) * (expert_layer_params(c)
                                   + held * expert_params(c))
            + 2 * c["hidden_size"] * c["vocab_size"] + c["hidden_size"])


def scan_flops(c: Dict) -> float:
    """One token through one layer's recurrence: the state's update by
    dt * x (x) B and its read by C."""
    return 4.0 * d_inner(c) * c["ssm_state_size"]


def token_flops(c: Dict, context: int, head: bool) -> float:
    """Forward operations of one token at `context` on this chip,
    without its routed experts."""
    qd = c["num_attention_heads"] * c["head_dim"]
    flops = kinds(c, MAMBA) * (
        2.0 * mamba_matmul_params(c)
        + 2.0 * c["conv_kernel"] * conv_channels(c) + scan_flops(c))
    flops += kinds(c, ATTENTION) * (2.0 * attention_matmul_params(c)
                                    + 4.0 * qd * context)
    flops += kinds(c, EXPERTS) * 2.0 * expert_layer_matmul_params(c)
    if head:
        flops += 2.0 * c["hidden_size"] * c["vocab_size"]
    return flops


def serve_flops(c: Dict, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int],
                held_assignments: int) -> float:
    """Operations of the prompts prefilled (token i of a prompt attends
    over i + 1 positions; one set of logits a prompt), the tokens
    decoded (each at its own context), and the routed experts:
    `held_assignments` (token, expert) pairs that fell on an expert
    held here, by the program's count, 2 * expert_params each."""
    flops = sum(token_flops(c, i + 1, i == n - 1)
                for n in prompt_lens for i in range(n))
    flops += sum(token_flops(c, ctx, True) for ctx in decode_contexts)
    return flops + 2.0 * held_assignments * expert_params(c)


def decode_round_weight_bytes(c: Dict) -> float:
    """Bytes of weights every decode round has to read: everything but
    the embedding table (a lookup of a row a lane) and the routed
    experts (`expert_bytes` each, for those the round's count shows a
    token for)."""
    params = (c["hidden_size"] * c["vocab_size"] + c["hidden_size"]
              + kinds(c, MAMBA) * mamba_params(c)
              + kinds(c, ATTENTION) * attention_params(c)
              + kinds(c, EXPERTS) * expert_layer_params(c))
    return float(BF16 * params)


def expert_bytes(c: Dict) -> float:
    return float(BF16 * expert_params(c))


def lane_state_bytes(c: Dict) -> float:
    """What the recurrent pool holds one lane: every `M` layer's scan
    state in float32 and its convolution tail in bfloat16."""
    return float(kinds(c, MAMBA) * (
        F32 * d_inner(c) * c["ssm_state_size"]
        + BF16 * (c["conv_kernel"] - 1) * conv_channels(c)))


def state_bytes(c: Dict, live_lane_rounds: int) -> float:
    """Recurrent state the decode of `live_lane_rounds` tokens moves:
    each token's lane reads its state and writes it back."""
    return 2.0 * live_lane_rounds * lane_state_bytes(c)


def kv_bytes(c: Dict, contexts: Iterable[int]) -> float:
    """Keys and values the decode of one token at each of `contexts`
    cached positions reads, over the attention layers."""
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * BF16
    return float(row * kinds(c, ATTENTION) * sum(contexts))
