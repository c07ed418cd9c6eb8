"""Continuous-batching generation engine (L7, autoregressive serving).

The reference's Cluster Serving layer streams fixed-shape record
batches; generative workloads need the opposite shape of pipeline —
iteration-level scheduling over a paged KV cache (vLLM-style
PagedAttention block tables; Orca-style join/leave between decode
steps; SGLang-style radix-tree prefix reuse).  Five pieces, one
subsystem:

* `PagedKVCache` / `BlockAllocator` — fixed-size KV blocks in one
  preallocated device buffer, host-side free-list allocation,
  per-sequence block tables, release-on-finish, cache-pressure
  preemption (kv_cache.py).
* `SlotScheduler` — fixed slot count + prefill token budget, FCFS
  admission, sequences join/leave between steps via the active-slot
  mask so steady-state serving never changes a compiled shape
  (scheduler.py).
* `PrefixCache` — radix tree over token-id block chunks mapping
  prompt prefixes to committed, refcount-shared KV pool blocks with
  copy-on-write and LRU eviction (prefix_cache.py;
  `GenerationEngine(prefix_caching=True)`).
* `CausalLM` — a GPT-style decoder on
  `ops.attention.dot_product_attention`'s KV-cache read path
  (model.py), with greedy/temperature/top-k sampling (sampling.py).
* `DecoderLM` — a decoder described by configuration (decoder.py):
  RMSNorm, rotary positions, grouped query heads, window and full
  attention layers mixed, dense and expert (`ExpertLayer`) FFNs, under
  the same call contract, so the one engine serves both.
* `HybridLM` — one sub-layer a layer, by a pattern string (hybrid.py):
  Mamba-2 state-space mixers (`ops.ssm`), attention without positions,
  latent squared-ReLU experts; its state-space layers' fixed-size
  state a lane lives in a second pool beside the KV pool
  (`kv_cache.RecurrentStatePool`), found by the lane's slot.
* `Speculator` / `ngram_draft` — draft-free speculative decoding:
  n-gram prompt-lookup proposals verified k-at-a-time by one compiled
  step, accepted-prefix emission, free-list rollback (speculation.py;
  `GenerationEngine(speculative_decoding=True)`).
* `GenerationEngine` — the decode loop tying them together: bucketed
  prefill + ONE static-shape decode step (zero recompiles after
  warmup; the compiled programs are steps.py's), token streaming,
  tokens/sec + cache-occupancy metrics (engine.py).  Every feature is
  an argument of its constructor, default off.  `ServingServer` exposes it as POST /generate with
  chunked streaming responses.
"""

from analytics_zoo_tpu.serving.generation.engine import (  # noqa: F401
    GenerationEngine,
    GenerationStream,
    QueueFull,
    RequestTooLarge,
)
from analytics_zoo_tpu.serving.generation.kv_cache import (  # noqa: F401
    BlockAllocator,
    PagedKVCache,
    RecurrentStatePool,
    dequantize_kv_tokens,
    quantize_kv_tokens,
)
from analytics_zoo_tpu.serving.generation.decoder import (  # noqa: F401
    DecoderLM,
    ExpertLayer,
)
from analytics_zoo_tpu.serving.generation.hybrid import (  # noqa: F401
    HybridLM,
)
from analytics_zoo_tpu.serving.generation.model import (  # noqa: F401
    CausalLM,
)
from analytics_zoo_tpu.serving.generation.prefix_cache import (  # noqa: F401,E501
    PrefixCache,
)
from analytics_zoo_tpu.serving.generation.sampling import (  # noqa: F401
    sample_tokens,
)
from analytics_zoo_tpu.serving.generation.scheduler import (  # noqa: F401
    Sequence,
    SlotScheduler,
)
from analytics_zoo_tpu.serving.generation.speculation import (  # noqa: F401,E501
    SpecState,
    Speculator,
    ngram_draft,
)

__all__ = ["BlockAllocator", "CausalLM", "DecoderLM", "ExpertLayer",
           "GenerationEngine",
           "GenerationStream", "HybridLM", "PagedKVCache", "PrefixCache",
           "QueueFull", "RecurrentStatePool", "RequestTooLarge", "Sequence", "SlotScheduler",
           "SpecState", "Speculator", "dequantize_kv_tokens",
           "ngram_draft", "quantize_kv_tokens", "sample_tokens"]
