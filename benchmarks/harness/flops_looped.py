"""Operations and bytes a looped decoder needs, from the configuration's
published keys alone (`ouro`: `benchmarks/configs/ouro_2p6b_serve.json`).

The rules are `flops.py`'s: a matmul of [m, k] by [k, n] is 2*m*k*n,
lookups and norms count as no operations, recomputation is never
credited.  What is the loop's own: every layer runs `total_ut_steps`
times a token, so its products are counted that many times and its
weights are read that many times a decode round, and each application
has a cache slot of its own (`total_ut_steps * num_hidden_layers`
slots a token), whose keys and values the token's later positions
read.  The readers take the loop's two numbers from the program's own
gauges (`generation_loop_steps`, `generation_kv_layer_slots`) where
they have them; the configuration's are the defaults."""

from __future__ import annotations

from typing import Dict, Iterable, Optional

BF16 = 2


def heads(c: Dict):
    """(query heads, KV heads, head dim)."""
    h = int(c["num_attention_heads"])
    return (h, int(c.get("num_key_value_heads") or h),
            int(c.get("head_dim") or c["hidden_size"] // h))


def attention_params(c: Dict) -> int:
    """W_q, W_k, W_v and W_o of one layer."""
    d = c["hidden_size"]
    h, g, hd = heads(c)
    return d * h * hd + 2 * d * g * hd + h * hd * d


def ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_matmul_params(c: Dict) -> int:
    return attention_params(c) + ffn_params(c)


def layer_params(c: Dict) -> int:
    """One layer's weights: its matrices and its four norm scales (two
    before and two after the sub-layers)."""
    return layer_matmul_params(c) + 4 * c["hidden_size"]


def held_params(c: Dict) -> int:
    """Every weight the chip holds: the layers once (a step reuses
    them), the embedding table, the untied head, the final norm."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * d * c["vocab_size"] + d)


def loop_steps(c: Dict, steps: Optional[int] = None) -> int:
    return int(steps if steps is not None else c["total_ut_steps"])


def slots(c: Dict, steps: Optional[int] = None) -> int:
    """Cache slots a token holds rows in: one a layer a step."""
    return loop_steps(c, steps) * int(c["num_hidden_layers"])


def kv_token_bytes(c: Dict, n_slots: Optional[int] = None) -> int:
    """Bytes a cached token holds over all slots: a key and a value of
    every KV head, bfloat16."""
    _, g, hd = heads(c)
    n = slots(c) if n_slots is None else int(n_slots)
    return n * 2 * g * hd * BF16


def attention_flops_per_position(c: Dict) -> int:
    """One token's heads against one cached position of one slot: the
    scores and the sum over the values."""
    h, _, hd = heads(c)
    return 4 * h * hd


def token_flops(c: Dict, context: int, head: bool,
                steps: Optional[int] = None) -> float:
    """Forward operations of one token that attends over `context`
    positions (itself included): every layer's products and attention
    at every step, and the head where its logits are needed."""
    n = slots(c, steps)
    flops = 2.0 * n * layer_matmul_params(c) \
        + float(n) * attention_flops_per_position(c) * context
    if head:
        flops += 2.0 * c["hidden_size"] * c["vocab_size"]
    return flops


def serve_flops(c: Dict, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int],
                steps: Optional[int] = None) -> float:
    """Operations of the prompts prefilled (token i of a prompt attends
    over i + 1 positions, one set of logits a prompt) and of the tokens
    decoded, each at its own context."""
    n = slots(c, steps)
    head = 2.0 * c["hidden_size"] * c["vocab_size"]
    per_position = float(n) * attention_flops_per_position(c)
    flops = sum(p * 2.0 * n * layer_matmul_params(c)
                + per_position * p * (p + 1) / 2 + head
                for p in prompt_lens)
    return flops + sum(token_flops(c, ctx, True, steps)
                       for ctx in decode_contexts)


def decode_round_weight_bytes(c: Dict, steps: Optional[int] = None
                              ) -> float:
    """Bytes of weights every decode round has to read: each layer's
    once a step, and the head (the embedding table is a lookup of a
    row a lane)."""
    return float(BF16 * (loop_steps(c, steps) * c["num_hidden_layers"]
                         * layer_params(c)
                         + c["hidden_size"] * c["vocab_size"]))


def kv_bytes(c: Dict, contexts: Iterable[int],
             n_slots: Optional[int] = None) -> float:
    """Bytes of cached keys and values the decode of one token at each
    of `contexts` positions reads, over every slot."""
    return float(kv_token_bytes(c, n_slots) * sum(contexts))


def kv_flops(c: Dict, contexts: Iterable[int],
             n_slots: Optional[int] = None) -> float:
    """The two attention products over those rows."""
    n = slots(c) if n_slots is None else int(n_slots)
    return float(n * attention_flops_per_position(c) * sum(contexts))
