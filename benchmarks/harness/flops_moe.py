"""Operations and bytes a decoder with grouped heads, window layers and
expert layers needs, from the configuration's published keys alone
(`exaone_moe`: `benchmarks/configs/kexaone_236b_ep8_serve.json`).

The rules are `flops.py`'s: a matmul of [m, k] by [k, n] is 2*m*k*n,
lookups and norms count as nothing, recomputation is never credited.
What is new here is counted as the chip has to do it, not as the code
does it: a window layer's products over the positions in sight only, a
routed expert only for the assignments that fell on an expert held here
(the program's own count), the head over the held slice of the
vocabulary."""

from __future__ import annotations

from typing import Dict, Iterable

SLIDING, SPARSE = "sliding_attention", "sparse"
BF16 = 2


def attention_params(c: Dict) -> int:
    """q, k, v and o of one layer: heads * head_dim need not be the
    hidden size."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def dense_ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict) -> int:
    """One routed expert (the shared expert is `num_shared_experts` of
    them side by side)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def visible(c: Dict, kind: str, context: int) -> int:
    """Positions a token that attends over `context` positions (itself
    included) reads on a layer of `kind`."""
    return min(context, c["sliding_window"]) if kind == SLIDING \
        else context


def token_flops(c: Dict, context: int, head: bool) -> float:
    """Forward operations of one token at `context` on this chip,
    without its routed experts: every layer's projections and its two
    attention products over the positions in sight, the dense FFN, the
    router and the shared expert, and the head over the held vocabulary
    where the token's logits are needed."""
    qd = c["num_attention_heads"] * c["head_dim"]
    flops = 0.0
    for kind, ffn in zip(c["layer_types"], c["mlp_layer_types"]):
        flops += 2.0 * attention_params(c)
        flops += 4.0 * qd * visible(c, kind, context)
        if ffn == SPARSE:
            flops += 2.0 * (router_params(c)
                            + c["num_shared_experts"] * expert_params(c))
        else:
            flops += 2.0 * dense_ffn_params(c)
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
    """Bytes of weights every decode round has to read: every matrix
    but the embedding table (a lookup of a row a lane) and the routed
    experts (`expert_bytes` each, for those the round's count shows a
    token for)."""
    params = c["hidden_size"] * c["vocab_size"]            # the head
    for ffn in c["mlp_layer_types"]:
        params += attention_params(c)
        params += (router_params(c)
                   + c["num_shared_experts"] * expert_params(c)
                   if ffn == SPARSE else dense_ffn_params(c))
    return float(BF16 * params)


def expert_bytes(c: Dict) -> float:
    return float(BF16 * expert_params(c))


def cached_in_sight(c: Dict, contexts: Iterable[int]) -> int:
    """Cached positions the decode of one token at each of `contexts`
    cached positions reads, summed over the layers: a window layer
    reads the `sliding_window` - 1 in sight (the token's own key and
    value come from the step, not the pool)."""
    contexts = list(contexts)
    return sum(min(n, c["sliding_window"] - 1) if kind == SLIDING else n
               for kind in c["layer_types"] for n in contexts)


def kv_bytes(c: Dict, contexts: Iterable[int]) -> float:
    """Bytes of keys and values those positions hold."""
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * BF16
    return float(row * cached_in_sight(c, contexts))


def kv_flops(c: Dict, contexts: Iterable[int]) -> float:
    """The two attention products over those positions."""
    qd = c["num_attention_heads"] * c["head_dim"]
    return 4.0 * qd * cached_in_sight(c, contexts)
