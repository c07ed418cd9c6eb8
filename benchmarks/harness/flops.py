"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the yardstick so that no later PR can change how a share of a
peak is counted.  Recomputed operations are never credited; embedding
lookups count as no operations; a matmul of [m, k] by [k, n] is 2*m*k*n.
"""

from __future__ import annotations

from typing import Dict, Iterable


def block_matmul_params(model: Dict) -> int:
    """Weights of one block that every token is multiplied by: qkv,
    proj, fc1, fc2."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    return 4 * d * d + 2 * d * ff


def decoder_token_flops(model: Dict, context: int, head: bool) -> float:
    """Forward operations of one token that attends over `context`
    positions (itself included): the blocks' matmuls, the two attention
    products, and the output head where this token's logits are needed
    (`head`: the last prompt token and every generated one)."""
    d, layers = model["hidden_size"], model["n_block"]
    flops = 2.0 * layers * block_matmul_params(model)
    flops += 4.0 * layers * d * context
    if head:
        flops += 2.0 * d * model["vocab"]
    return flops


def prefill_flops(model: Dict, prompt_len: int) -> float:
    """Forward operations a prompt of `prompt_len` tokens needs: causal,
    so token i attends over i + 1 positions; one set of logits."""
    d, layers = model["hidden_size"], model["n_block"]
    n = prompt_len
    return (2.0 * layers * block_matmul_params(model) * n
            + 4.0 * layers * d * n * (n + 1) / 2
            + 2.0 * d * model["vocab"])


def serve_flops(model: Dict, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Operations of the prompts prefilled and the tokens decoded (each
    at its own context length)."""
    return (sum(prefill_flops(model, n) for n in prompt_lens)
            + sum(decoder_token_flops(model, c, True)
                  for c in decode_contexts))


def train_token_flops(model: Dict, seq_len: int) -> float:
    """Forward and backward operations of one trained token:
    6 * N + 12 * L * H * t, N the blocks' matmul weights (the embedding
    tables are lookups, the pooler and classifier see one token a row)."""
    layers, d = model["n_block"], model["hidden_size"]
    return (6.0 * layers * block_matmul_params(model)
            + 12.0 * layers * d * seq_len)


def paged_decode_kv_bytes(model: Dict, contexts: Iterable[int],
                          bytes_per_value: int = 2) -> float:
    """Bytes of keys and values one layer's paged-decode call has to
    read for lanes at `contexts`."""
    return float(sum(contexts)) * 2 * model["hidden_size"] * bytes_per_value


def bias_gelu_flops(rows: int, model: Dict) -> float:
    """One fc1 + bias + GELU call over `rows` rows."""
    return 2.0 * rows * model["hidden_size"] * model["intermediate_size"]


def bias_gelu_bytes(rows: int, model: Dict, bytes_per_value: int = 2
                    ) -> float:
    """What that call has to read and write: its input rows, the
    weights and the bias, its output rows."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    return float(bytes_per_value) * (rows * d + d * ff + ff + rows * ff)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict) -> Dict:
    """The least time the chip could take over the time taken, in %,
    and which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"share": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}
