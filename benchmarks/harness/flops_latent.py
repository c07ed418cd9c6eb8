"""Operations and bytes a decoder with latent attention in front of
expert layers needs, from the configuration's published keys alone
(`sarvam_mla`: `benchmarks/configs/sarvam_105b_ep8_serve.json`).

The rules are `flops.py`'s: a matmul of [m, k] by [k, n] is 2*m*k*n,
lookups and norms count as nothing, recomputation is never credited.
Latent attention is counted in the form the chip has to run it in: a
decoded token ABSORBED — its 64 heads against each cached row of
`kv_lora_rank + qk_rope_head_dim` columns as key and against its first
`kv_lora_rank` columns as value, the row read once — a prompt EXPANDED:
every position's key (`qk_nope_head_dim + qk_rope_head_dim`) and value
(`v_head_dim`) up-projected once, token i's heads over i + 1 of them.
Either way a token passes the up-projection `W_kvb` once (into its own
key and value at prefill, around the sum over the rows at decode).
A routed expert is counted only for the assignments that fell on an
expert held here (the program's own count), the head over the held
slice of the vocabulary."""

from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def row_width(c: Dict) -> int:
    """Columns of the row a token caches in a layer: [c | k_r]."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attention_params(c: Dict) -> int:
    """W_q, W_kva, W_kvb and W_o of one layer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    q = h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    kv_b = c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                    + c["v_head_dim"])
    return d * q + d * row_width(c) + kv_b + h * c["v_head_dim"] * d


def dense_ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict) -> int:
    """One routed expert (the shared expert is `num_shared_experts` of
    them side by side)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def layers(c: Dict):
    """(dense layers, sparse layers) of the configuration's depth."""
    dense = min(c.get("first_k_dense_replace", 0), c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def ffn_params(c: Dict) -> int:
    """Every layer's FFN without its routed experts: the dense FFNs,
    and a sparse layer's router and shared expert."""
    dense, sparse = layers(c)
    return dense * dense_ffn_params(c) + sparse * (
        router_params(c) + c["num_shared_experts"] * expert_params(c))


def held_params(c: Dict) -> int:
    """Matrix parameters this chip holds: every layer's attention and
    FFN, the held experts, the embedding table and the head over the
    held vocabulary (norm scales and the expert bias left out)."""
    held = c.get("experts_held", (0, c["num_experts"]))[1]
    return (c["num_hidden_layers"] * attention_params(c) + ffn_params(c)
            + layers(c)[1] * held * expert_params(c)
            + 2 * c["hidden_size"] * c["vocab_size"])


def absorbed_flops_per_row(c: Dict) -> int:
    """One decoded token's heads against one cached row of one layer:
    scores over the whole row, the sum over its latent part."""
    return 2 * c["num_attention_heads"] * (row_width(c) + c["kv_lora_rank"])


def expanded_flops_per_position(c: Dict) -> int:
    """One prompt token's heads against one position of one layer:
    scores over the expanded key, the sum over the expanded value."""
    return 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def token_flops(c: Dict, context: int, head: bool, absorbed: bool
                ) -> float:
    """Forward operations of one token that attends over `context`
    positions (itself included) on this chip, without its routed
    experts: every layer's projections and attention products (in the
    absorbed or the expanded form), the FFNs, and the head over the
    held vocabulary where the token's logits are needed."""
    per_position = absorbed_flops_per_row(c) if absorbed \
        else expanded_flops_per_position(c)
    flops = 2.0 * (c["num_hidden_layers"] * attention_params(c)
                   + ffn_params(c))
    flops += float(c["num_hidden_layers"]) * per_position * context
    if head:
        flops += 2.0 * c["hidden_size"] * c["vocab_size"]
    return flops


def serve_flops(c: Dict, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int],
                held_assignments: int) -> float:
    """Operations of the prompts prefilled (expanded: token i of a
    prompt attends over i + 1 positions; one set of logits a prompt),
    the tokens decoded (absorbed, each at its own context), and the
    routed experts: `held_assignments` (token, expert) pairs that fell
    on an expert held here, by the program's count, 2 * expert_params
    each."""
    matmuls = 2.0 * (c["num_hidden_layers"] * attention_params(c)
                     + ffn_params(c))
    per_position = c["num_hidden_layers"] * expanded_flops_per_position(c)
    head = 2.0 * c["hidden_size"] * c["vocab_size"]
    flops = sum(n * matmuls + per_position * n * (n + 1) / 2 + head
                for n in prompt_lens)
    flops += sum(token_flops(c, ctx, True, True)
                 for ctx in decode_contexts)
    return flops + 2.0 * held_assignments * expert_params(c)


def decode_round_weight_bytes(c: Dict) -> float:
    """Bytes of weights every decode round has to read: every matrix
    but the embedding table (a lookup of a row a lane) and the routed
    experts (`expert_bytes` each, for those the round's count shows a
    token for)."""
    return float(BF16 * (c["hidden_size"] * c["vocab_size"]
                         + c["num_hidden_layers"] * attention_params(c)
                         + ffn_params(c)))


def expert_bytes(c: Dict) -> float:
    return float(BF16 * expert_params(c))


def latent_row_bytes(c: Dict) -> int:
    """Bytes of the row a token caches in ONE layer."""
    return BF16 * row_width(c)


def latent_bytes(c: Dict, contexts: Iterable[int]) -> float:
    """Bytes of cached rows the decode of one token at each of
    `contexts` cached positions reads, over all layers (the token's
    own row comes from the step, not the pool)."""
    return float(latent_row_bytes(c) * c["num_hidden_layers"]
                 * sum(contexts))


def latent_flops(c: Dict, contexts: Iterable[int]) -> float:
    """The absorbed products over those rows."""
    return float(absorbed_flops_per_row(c) * c["num_hidden_layers"]
                 * sum(contexts))
