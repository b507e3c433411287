"""Degraded-mode ingest benchmark: dp=4,tp=2 mesh with one replica drained.

Measures what the self-healing runtime (internals/health.py) costs when
it acts: the device-phase ingest rate with one dp replica drained (the
health controller's detour routing sends that shard's rows to the
remaining replicas), the latency of the drain itself (mark drained +
pipeline barrier over in-flight dispatches), and the latency of
re-admission.  The degraded throughput target is (dp-1)/dp of the
healthy rate — losing one of dp replicas should cost at most its
proportional share, because `pack_batch_dp` detours the drained shard's
rows instead of stalling on them.

The devices are whatever jax finds; with fewer than 8 the mesh raises and
the bench fails.  Only when the CALLER set `JAX_PLATFORMS=cpu` does it ask
XLA for 8 virtual CPU devices (the tests/conftest.py trick, before jax
starts): every virtual device shares the same host cores, so a drained
replica frees compute for the survivors and the ratio is structural, not
comparative — the output says `cpu_emulated: true`, and `target_met` is
only judged on real chips (same convention as multichip_bench.py).

Prints ONE JSON line, stamped with platform, device_kind and device count.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8
DP, TP = 4, 2
N_DOCS = 256
DRAIN_REPLICA = 2

# emulation is the caller's decision (JAX_PLATFORMS=cpu), never a fallback
CPU_EMULATED = os.environ.get("JAX_PLATFORMS") == "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if CPU_EMULATED and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()


def _corpus() -> list[str]:
    import random

    rng = random.Random(13)
    words = [f"tok{i}" for i in range(512)]
    return [
        " ".join(rng.choices(words, k=rng.randint(12, 48)))
        for _ in range(N_DOCS)
    ]


def _ingest_once(enc, texts, capacity: int):
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
    from pathway_tpu.internals.device_pipeline import _PIPELINES
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig

    compile_cache.configure()
    devices = jax.devices()

    config = TransformerConfig(
        vocab_size=30522, hidden=128, layers=3, heads=4, mlp_dim=512,
        max_len=64,
    )
    enc = SentenceEncoder("degraded-bench", config=config, max_len=64)
    texts = _corpus()
    capacity = 1 << (N_DOCS - 1).bit_length()
    queries = [texts[3], texts[N_DOCS // 2], texts[-1]]

    backend = mesh_backend.activate(MeshSpec.parse(f"dp={DP},tp={TP}"))
    try:
        # warmup pays the packed-slab XLA compiles for both shapes
        _ingest_once(enc, texts[: N_DOCS // 4], capacity)
        ref, healthy_s = _ingest_once(enc, texts, capacity)
        ref_rows = ref.search_many(queries, [5] * len(queries), [None] * 3)

        # drain latency: mark the replica drained + barrier every live
        # pipeline over its in-flight dispatches (exactly what the
        # health controller's drain actuator does)
        t0 = time.perf_counter()
        assert backend.drain_replica(DRAIN_REPLICA, reason="bench")
        for p in list(_PIPELINES):
            p.barrier()
        drain_s = time.perf_counter() - t0

        impl, degraded_s = _ingest_once(enc, texts, capacity)
        rows = impl.search_many(queries, [5] * len(queries), [None] * 3)
        # retrieval stays ranking-exact while degraded: shard placement
        # is locality-only and search merges every shard
        parity_ok = [[k for k, _ in r] for r in rows] == [
            [k for k, _ in r] for r in ref_rows
        ]

        t0 = time.perf_counter()
        assert backend.readmit_replica(DRAIN_REPLICA)
        readmit_s = time.perf_counter() - t0
    finally:
        mesh_backend.deactivate()

    healthy_rate = N_DOCS / healthy_s
    degraded_rate = N_DOCS / degraded_s
    target_ratio = (DP - 1) / DP
    print(
        json.dumps(
            {
                "metric": "degraded_mode_ingest",
                "n_devices": N_DEVICES,
                "dp": DP,
                "tp": TP,
                "cpu_emulated": CPU_EMULATED,
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "n_docs": N_DOCS,
                "drained_replica": DRAIN_REPLICA,
                "healthy_docs_per_sec": round(healthy_rate, 1),
                "degraded_docs_per_sec": round(degraded_rate, 1),
                "degraded_ratio": round(degraded_rate / healthy_rate, 3),
                "target_ratio": round(target_ratio, 3),
                "target_met": (
                    None
                    if CPU_EMULATED
                    else degraded_rate / healthy_rate >= target_ratio
                ),
                "drain_latency_s": round(drain_s, 4),
                "readmit_latency_s": round(readmit_s, 4),
                "parity_ok": parity_ok,
            }
        )
    )


if __name__ == "__main__":
    main()
