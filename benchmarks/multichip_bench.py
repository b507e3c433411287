"""Multichip ingest benchmark: single-device vs dp=4,tp=2 mesh backend.

A/Bs the framework device-phase ingest path (packed slabs -> async
device pipeline -> fused embed+add into DeviceKnnIndex) with the mesh
execution backend (internals/mesh_backend.py) armed against the plain
single-device pipeline, on the same corpus and encoder, and checks
sharded-vs-single-device retrieval ranking parity on the way out.

On a real 8-chip pod slice the sharded path targets >= 6x the
single-chip device-phase ingest rate (dp=4 concurrent replicas x tp=2
matmul split, minus merge overhead).  The devices are whatever jax finds:
with fewer than 8, `mesh_backend.activate` raises and the bench fails.
Only when the CALLER set `JAX_PLATFORMS=cpu` does the bench ask XLA for 8
virtual CPU devices (the tests/conftest.py trick, before jax starts) so
the path and the parity check can be exercised without chips — every
virtual device shares the same host cores, so that "speedup" reflects
sharding overhead only, and the output says `cpu_emulated: true`.

Prints ONE JSON line, stamped with platform, device_kind and device count.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8
DP, TP = 4, 2
N_DOCS = 256
TARGET_SPEEDUP = 6.0

# Emulation is the caller's decision, never a fallback: with
# JAX_PLATFORMS=cpu the host-platform device-count flag goes into the
# environment BEFORE jax initializes its backends.
CPU_EMULATED = os.environ.get("JAX_PLATFORMS") == "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if CPU_EMULATED and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()


def _corpus() -> list[str]:
    import random

    rng = random.Random(11)
    words = [f"tok{i}" for i in range(512)]
    return [
        " ".join(rng.choices(words, k=rng.randint(12, 48)))
        for _ in range(N_DOCS)
    ]


def _ingest_once(enc, texts, capacity: int):
    """Build a fresh fused impl, ingest the corpus through the async
    pipeline, and return (impl, seconds)."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    keys = list(range(len(texts)))
    impl = _FusedKnnIndexImpl(enc, "cos", capacity)
    t0 = time.perf_counter()
    impl.add_many(keys, texts, [None] * len(keys))
    impl.drain()
    return impl, time.perf_counter() - t0


def main() -> None:
    os.environ["PATHWAY_DEVICE_PIPELINE"] = "1"
    os.environ.setdefault("PATHWAY_DEVICE_PROBE", "0")

    import jax

    from pathway_tpu.analysis.mesh import MeshSpec
    from pathway_tpu.internals import compile_cache, mesh_backend
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig

    compile_cache.configure()
    devices = jax.devices()

    config = TransformerConfig(
        vocab_size=30522, hidden=128, layers=3, heads=4, mlp_dim=512,
        max_len=64,
    )
    enc = SentenceEncoder("multichip-bench", config=config, max_len=64)
    texts = _corpus()
    capacity = 1 << (N_DOCS - 1).bit_length()
    queries = [texts[3], texts[N_DOCS // 2], texts[-1]]

    # single-device reference (warmup run pays the XLA compiles, then a
    # measured run)
    _ingest_once(enc, texts[: N_DOCS // 4], capacity)
    ref, single_s = _ingest_once(enc, texts, capacity)
    ref_rows = ref.search_many(queries, [5] * len(queries), [None] * 3)

    mesh_backend.activate(MeshSpec.parse(f"dp={DP},tp={TP}"))
    try:
        _ingest_once(enc, texts[: N_DOCS // 4], capacity)  # sharded compiles
        impl, sharded_s = _ingest_once(enc, texts, capacity)
        rows = impl.search_many(queries, [5] * len(queries), [None] * 3)
        parity_ok = [[k for k, _ in r] for r in rows] == [
            [k for k, _ in r] for r in ref_rows
        ]
        per_replica = (
            impl._pipeline.replica_stats() if impl._pipeline else []
        )
    finally:
        mesh_backend.deactivate()

    single_rate = N_DOCS / single_s
    sharded_rate = N_DOCS / sharded_s
    print(
        json.dumps(
            {
                "metric": "multichip_device_phase_ingest",
                "round": "r06",
                "n_devices": N_DEVICES,
                "dp": DP,
                "tp": TP,
                "cpu_emulated": CPU_EMULATED,
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "n_docs": N_DOCS,
                "single_device_docs_per_sec": round(single_rate, 1),
                "sharded_docs_per_sec": round(sharded_rate, 1),
                "speedup": round(sharded_rate / single_rate, 2),
                "target_speedup": TARGET_SPEEDUP,
                "target_met": (
                    None
                    if CPU_EMULATED
                    else sharded_rate / single_rate >= TARGET_SPEEDUP
                ),
                "parity_ok": parity_ok,
                "per_replica": per_replica,
            }
        )
    )


if __name__ == "__main__":
    main()
