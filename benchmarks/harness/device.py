"""The device a run is on: resolved first, stamped on every result.

A measurement without the chip says nothing about the chip, so
`resolve` ends the process non-zero unless JAX reports a TPU whose
`device_kind` has a row in `peaks.json` and there are as many chips as
the cell asks for.  Nothing here falls back to another platform."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def use_compile_cache() -> str:
    """The one cache rule of the repo: the environment's directory when
    it names one, else a fixed path inside the checkout (the path is
    part of the cache's key).  Must run before JAX is imported."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".jax_cache"))
    # every program of a cell is found again by the cell's next run,
    # the sub-second ones too: set-up is then the same work each time
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def resolve(chips: int):
    """(devices, the peaks row of their kind) — or exit non-zero."""
    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, JAX found {first.platform} "
                 f"({first.device_kind}); no result")
    peaks = load_peaks()
    if first.device_kind not in peaks:
        sys.exit(f"benchmark: device kind {first.device_kind!r} has no row "
                 f"in harness/peaks.json ({sorted(peaks)}); no result")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), JAX found "
                 f"{len(devices)}; no result")
    return devices[:chips], peaks[first.device_kind]


def memory_peak(device) -> dict:
    """One device's peak, in its two parts.  On this runtime
    `peak_bytes_in_use` counts the buffers the process holds (weights,
    optimizer state, the KV pool, inputs) and leaves out the scratch
    memory the loaded programs reserve for their temporaries, which
    `peak_bytes_reserved` counts; the chip's memory holds both."""
    stats = device.memory_stats() or {}
    return {"in_use": int(stats.get("peak_bytes_in_use", 0)),
            "reserved": int(stats.get("peak_bytes_reserved", 0))}


def memory_peak_bytes(devices) -> dict:
    """The peak on the fullest of `devices` so far: buffers in use plus
    program scratch reserved, with the two parts beside their sum.  (A
    backend that keeps no statistics — the CPU of the test suite — reads
    0; `resolve` lets no such backend reach a result.)"""
    parts = max((memory_peak(d) for d in devices),
                key=lambda p: p["in_use"] + p["reserved"])
    return {"memory_peak_bytes": parts["in_use"] + parts["reserved"],
            "memory_in_use_peak_bytes": parts["in_use"],
            "memory_reserved_peak_bytes": parts["reserved"]}


def stamp(devices, memory: dict) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), **memory}
