"""Operations and bytes the `lfm2_moe` decoder needs — two sub-layers a
layer: a gated short convolution (`conv`) or grouped-query attention
(`full_attention`), then a dense SwiGLU FFN (below `num_dense_layers`)
or routed SwiGLU experts — from the configuration's published keys
alone (`benchmarks/configs/lfm2_24b_a2b_serve.json`).

The rules are `flops.py`'s: a matmul of [m, k] by [k, n] is 2*m*k*n,
lookups, norms, gates and activations count as nothing, recomputation is
never credited.  The short convolution's taps count as the products
they are (2 * L a channel a token); a routed expert counts only for the
assignments that fell on an expert held here (the program's own count).
A decode round's bytes count each live lane's convolution tail once
read and once written, at the size the program's own gauge gives a
lane."""

from __future__ import annotations

from typing import Dict, Iterable

CONV, ATTENTION = "conv", "full_attention"
BF16 = 2


def layers(c: Dict, kind: str) -> int:
    return c["layer_types"].count(kind)


def routed_layers(c: Dict) -> int:
    return len(c["layer_types"]) - c["num_dense_layers"]


def head_dim(c: Dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_matmul_params(c: Dict) -> int:
    """in_proj to [B | C | x] and out_proj."""
    d = c["hidden_size"]
    return d * 3 * d + d * d


def conv_params(c: Dict) -> int:
    """A whole convolution operator: the two projections, the taps and
    the operator's norm."""
    return (conv_matmul_params(c) + c["conv_L_cache"] * c["hidden_size"]
            + c["hidden_size"])


def attention_matmul_params(c: Dict) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def attention_params(c: Dict) -> int:
    """q, k, v, o, the q and k norms and the operator's norm."""
    return attention_matmul_params(c) + 2 * head_dim(c) + c["hidden_size"]


def dense_ffn_params(c: Dict) -> int:
    """w1, w3 and w2 and the FFN's norm."""
    return 3 * c["hidden_size"] * c["intermediate_size"] + c["hidden_size"]


def expert_params(c: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_layer_params(c: Dict) -> int:
    """An expert FFN outside its experts: router, expert bias, norm."""
    return router_params(c) + c["num_experts"] + c["hidden_size"]


def total_params(c: Dict) -> int:
    """Everything this chip holds: `experts_held` routed experts a
    layer (all of them where the configuration names no share), the
    embedding table, the head and the final norm."""
    held = c.get("experts_held", [0, c["num_experts"]])[1]
    return (layers(c, CONV) * conv_params(c)
            + layers(c, ATTENTION) * attention_params(c)
            + c["num_dense_layers"] * dense_ffn_params(c)
            + routed_layers(c) * (expert_layer_params(c)
                                  + held * expert_params(c))
            + 2 * c["hidden_size"] * c["vocab_size"] + c["hidden_size"])


def token_flops(c: Dict, context: int, head: bool) -> float:
    """Forward operations of one token at `context` on this chip,
    without its routed experts."""
    d, q = c["hidden_size"], c["num_attention_heads"] * head_dim(c)
    flops = layers(c, CONV) * (2.0 * conv_matmul_params(c)
                               + 2.0 * c["conv_L_cache"] * d)
    flops += layers(c, ATTENTION) * (2.0 * attention_matmul_params(c)
                                     + 4.0 * q * context)
    flops += c["num_dense_layers"] * 6.0 * d * c["intermediate_size"]
    flops += routed_layers(c) * 2.0 * router_params(c)
    if head:
        flops += 2.0 * d * c["vocab_size"]
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
              + layers(c, CONV) * conv_params(c)
              + layers(c, ATTENTION) * attention_params(c)
              + c["num_dense_layers"] * dense_ffn_params(c)
              + routed_layers(c) * expert_layer_params(c))
    return float(BF16 * params)


def expert_bytes(c: Dict) -> float:
    return float(BF16 * expert_params(c))


def kv_bytes(c: Dict, contexts: Iterable[int]) -> float:
    """Keys and values the decode of one token at each of `contexts`
    cached positions reads, over the attention layers."""
    row = 2 * c["num_key_value_heads"] * head_dim(c) * BF16
    return float(row * layers(c, ATTENTION) * sum(contexts))


def kv_flops(c: Dict, contexts: Iterable[int]) -> float:
    """The two attention products over those positions."""
    q = c["num_attention_heads"] * head_dim(c)
    return 4.0 * q * layers(c, ATTENTION) * sum(contexts)


def tail_bytes(lane_bytes: float, live_lane_rounds: int) -> float:
    """Convolution tails the decode of `live_lane_rounds` tokens moves:
    each token's lane reads its tails and writes them back."""
    return 2.0 * live_lane_rounds * lane_bytes
