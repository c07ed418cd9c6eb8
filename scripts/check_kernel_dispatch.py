#!/usr/bin/env python
"""Lint: models, keras layers AND the generation decode path must
route attention and LayerNorm through the `ops` dispatch layer.

The fused Pallas kernels (flash attention, fused LayerNorm, the
bias+GELU epilogue, paged decode attention — docs/kernels.md) only
reach a model if it goes through the dispatch points (`ops.attention`,
`ops.pallas.flash_attention`, `ops.normalization.layer_norm`/
`LayerNorm`, `ops.dense`): an ad-hoc `flax.linen.LayerNorm` or a
hand-rolled scores-softmax einsum silently opts that model out of
every kernel win AND out of the autotuner.  This check fails the build
when such a reimplementation appears under `analytics_zoo_tpu/models/`
or `analytics_zoo_tpu/keras/layers/`:

  * `nn.LayerNorm(` / `linen.LayerNorm(` / `import ... LayerNorm` —
    use `analytics_zoo_tpu.ops.normalization.LayerNorm` (same params).
  * the multi-head attention einsum signatures ("bqhd,bkhd" scores,
    "bhqk,bkhd" combine) — use `ops.attention.dot_product_attention`
    or `ops.pallas.flash_attention` (string mentions in docstrings
    count too: the signature IS the reimplementation).

`analytics_zoo_tpu/serving/generation/` (the decode hot path —
engine.py, model.py, scheduler.py, kv_cache.py, prefix_cache.py,
speculation.py, host_tier.py and anything that joins them) is held
to the same
einsum rule PLUS a
stricter one: no direct Pallas imports (`ops.pallas.*`,
`jax.experimental.pallas`, `pallas_call`).  Decode attention must go
through `ops.attention.paged_decode_attention` /
`latent_decode_attention` (a model that caches one latent row a
token) / `dot_product_attention` — a raw concat-attend einsum, a raw
scores-softmax over cached latent rows ("shw,scw" scores, "shc,scv"
combine) or a privately
wired kernel in the engine (or an attention shortcut inside the
prefix-cache/chunked-prefill machinery) would silently bitrot the
decode path off the tuned paged kernel (or pin it to one kernel
version), invisible to every parity test that pins ops/.

Run directly (`python scripts/check_kernel_dispatch.py`) or via the
tier-1 wrapper `tests/test_kernel_dispatch.py`.  Exit code 0 = clean.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "analytics_zoo_tpu")

PATTERNS = (
    (re.compile(r"\bnn\.LayerNorm\s*\("),
     "use analytics_zoo_tpu.ops.normalization.LayerNorm"),
    (re.compile(r"\blinen\.LayerNorm\s*\("),
     "use analytics_zoo_tpu.ops.normalization.LayerNorm"),
    (re.compile(r"from\s+flax[.\w]*\s+import\s+.*\bLayerNorm\b"),
     "use analytics_zoo_tpu.ops.normalization.LayerNorm"),
    (re.compile(r"bqhd,bkhd|bhqk,bkhd"),
     "use ops.attention.dot_product_attention / "
     "ops.pallas.flash_attention"),
    (re.compile(r"shw,scw|shc,scv"),
     "use ops.attention.latent_decode_attention"),
)

#: the decode path additionally may not wire kernels privately — the
#: ops.attention dispatch layer is where impl choice, the autotuner
#: and the XLA fallback live
GENERATION_PATTERNS = PATTERNS + (
    (re.compile(r"ops\.pallas\b"),
     "import nothing from ops.pallas here — dispatch through "
     "ops.attention.paged_decode_attention"),
    (re.compile(r"jax\.experimental[.\s]+import\s+pallas"
                r"|jax\.experimental\.pallas|\bpallas_call\b"),
     "no raw Pallas in the decode path — dispatch through "
     "ops.attention.paged_decode_attention"),
)

#: directories whose code must dispatch through ops/, with the pattern
#: set each is held to
SCANNED = (
    (os.path.join(PACKAGE, "models"), PATTERNS),
    (os.path.join(PACKAGE, "keras", "layers"), PATTERNS),
    (os.path.join(PACKAGE, "serving", "generation"),
     GENERATION_PATTERNS),
    (os.path.join(PACKAGE, "serving", "distributed"),
     GENERATION_PATTERNS),
)

#: back-compat alias (tests iterate SCANNED_DIRS)
SCANNED_DIRS = tuple(root for root, _pats in SCANNED)


def find_violations():
    violations = []
    for root, patterns in SCANNED:
        for dirpath, _dirnames, filenames in os.walk(root):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    for lineno, line in enumerate(f, 1):
                        for pat, fix in patterns:
                            if pat.search(line):
                                violations.append(
                                    (os.path.relpath(path, REPO),
                                     lineno, line.rstrip(), fix))
    return violations


def main() -> int:
    violations = find_violations()
    if not violations:
        print("check_kernel_dispatch: clean")
        return 0
    print("check_kernel_dispatch: ad-hoc attention/LayerNorm "
          "reimplementations outside the ops dispatch layer:",
          file=sys.stderr)
    for path, lineno, line, fix in violations:
        print(f"  {path}:{lineno}: {line}\n      -> {fix}",
              file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
