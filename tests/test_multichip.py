"""Tier-1 multichip coverage on the conftest-emulated 8-device CPU mesh.

Two layers:

  * the driver's dryrun parity checks, promoted out of
    `__graft_entry__.dryrun_multichip` into
    `pathway_tpu.parallel.multichip_checks` so they run on every test
    pass (sp-ring logits, tp decode, sharded-retrieval parity vs the
    single-device reference);
  * the mesh execution BACKEND (internals/mesh_backend.py): activation
    and degradation rules, dp-grouped slab packing, end-to-end sharded
    ingest parity against the single-device pipeline, the /status
    `mesh` key, and the device_flap drain on an active mesh.

Everything here needs the 8 virtual CPU devices tests/conftest.py forces
before jax backend init — no 'slow' marks, no real chips.
"""

from __future__ import annotations

import contextlib
import os

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.analysis.mesh import MeshSpec
from pathway_tpu.internals import mesh_backend
from pathway_tpu.models.minilm import SentenceEncoder
from pathway_tpu.models.transformer import TransformerConfig
from pathway_tpu.parallel import multichip_checks

N_DEVICES = 8

TINY = TransformerConfig(
    vocab_size=512, hidden=32, layers=1, heads=2, mlp_dim=64, max_len=64
)


def _encoder(name: str, max_len: int = 32) -> SentenceEncoder:
    return SentenceEncoder(name, config=TINY, max_len=max_len)


@contextlib.contextmanager
def _env(**kv):
    saved = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _activated(spec: str):
    backend = mesh_backend.activate(MeshSpec.parse(spec))
    try:
        yield backend
    finally:
        mesh_backend.deactivate()


def _require_devices():
    import jax

    if len(jax.devices()) < N_DEVICES:
        pytest.skip(f"needs {N_DEVICES} devices (conftest emulates them)")


# -- promoted dryrun checks --------------------------------------------------


def test_dryrun_sharded_train_step():
    _require_devices()
    loss = multichip_checks.check_sharded_train_step(N_DEVICES)
    assert np.isfinite(loss)


def test_dryrun_sp_ring_logits():
    _require_devices()
    shape = multichip_checks.check_sp_ring(N_DEVICES)
    assert shape == (2, 8 * N_DEVICES, 512)


def test_dryrun_tp_decode():
    _require_devices()
    shape = multichip_checks.check_tp_decode(N_DEVICES)
    assert shape == (N_DEVICES, 4)  # dp*2 prompts, 4 new tokens


def test_dryrun_sharded_retrieval_parity():
    """The load-bearing acceptance check: retrieval THROUGH THE ENGINE
    over an 8-way 'knn' index shard returns exactly what the dense
    single-device path returns (embeddings identical, only the search
    is sharded — comparison is ==)."""
    _require_devices()
    results, n_docs = multichip_checks.check_sharded_retrieval_parity(
        N_DEVICES
    )
    assert n_docs == 3 * N_DEVICES
    assert len(results) == 2


# -- backend activation / degradation ----------------------------------------


def test_backend_activates_on_enough_devices():
    _require_devices()
    with _activated("dp=4,tp=2") as backend:
        assert backend is not None
        assert mesh_backend.active_backend() is backend
        assert (backend.dp, backend.tp) == (4, 2)
        assert backend.can_shard_ingest()
        assert tuple(backend.mesh.axis_names) == ("dp", "tp")
        assert backend.mesh.devices.size == 8
    assert mesh_backend.active_backend() is None


def test_activate_raises_when_too_few_devices():
    # a mesh that needs more devices than are attached is an error, not a
    # single-device run under a warning
    with pytest.raises(ValueError, match="needs 128 devices but only"):
        mesh_backend.activate(MeshSpec.parse("dp=64,tp=2"))
    assert mesh_backend.active_backend() is None
    # ... and pw.run(mesh=...) raises it before anything executes
    t = pw.debug.table_from_rows(pw.schema_from_types(k=str), [("a",)])
    pw.io.subscribe(t, on_change=lambda *a, **k: None)
    with pytest.raises(ValueError, match="needs 128 devices"):
        pw.run(mesh="dp=64,tp=2")
    assert mesh_backend.active_backend() is None


def test_backend_non_pow2_dp_keeps_single_device_ingest():
    # degradation rule 2: dp=3 can't divide the bucketed batch axes
    _require_devices()
    with _activated("dp=3,tp=2") as backend:
        assert backend is not None
        assert not backend.can_shard_ingest()
        # the fused impl therefore must NOT adopt the mesh
        from pathway_tpu.stdlib.indexing.nearest_neighbors import (
            _FusedKnnIndexImpl,
        )

        impl = _FusedKnnIndexImpl(_encoder("nonpow2-tiny"), "cos", 32)
        assert impl.knn.mesh is None


def test_dp_shard_of_matches_exchange_rule():
    _require_devices()
    with _activated("dp=4,tp=2") as backend:
        # ints route by value — the engine exchange's Pointer.shard % dp
        assert [backend.dp_shard_of(k) for k in range(8)] == [
            0, 1, 2, 3, 0, 1, 2, 3,
        ]

        class _Ptr:
            shard = 7

        assert backend.dp_shard_of(_Ptr()) == 3


def test_pack_batch_dp_routes_rows_to_replicas():
    _require_devices()
    tok = _encoder("packdp-tiny").tokenizer
    with _activated("dp=4,tp=2") as backend:
        keys = list(range(23))
        texts = [f"alpha doc{i} bravo " + "pad " * (i % 5) for i in keys]
        ids, seg, slots, replica_rows = mesh_backend.pack_batch_dp(
            tok, keys, texts, backend, max_len=32, token_budget=64
        )
        assert ids.shape == seg.shape
        assert ids.shape[0] % backend.dp == 0
        rows_per_replica = ids.shape[0] // backend.dp
        assert replica_rows == [
            sum(1 for k in keys if backend.dp_shard_of(k) == r)
            for r in range(backend.dp)
        ]
        assert sum(replica_rows) == len(keys)
        # every doc's packed row lies inside its OWN replica's block
        for k, (row, _s) in zip(keys, slots):
            assert row // rows_per_replica == backend.dp_shard_of(k)


# -- end-to-end sharded ingest parity ---------------------------------------


def test_mesh_backend_ingest_parity_vs_single_device():
    """The tentpole parity contract: a dp=4,tp=2 backend runs the whole
    ingest path sharded (dp-grouped packed slabs through the async
    pipeline, tp-sharded encoder matmuls, shard-routed index slots,
    all-gather+merge search) and returns the SAME ranking as the
    single-device pipeline; scores agree to packed-encoder tolerance
    (bf16 matmul reassociation under tp, repo precedent
    test_packed_vs_classic_encoder_parity)."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    _require_devices()
    texts = [
        f"alpha doc number {i} bravo charlie token{i % 7}" for i in range(40)
    ]
    keys = list(range(len(texts)))
    queries = [texts[3], texts[17], "token3 alpha"]
    enc = _encoder("mesh-parity-tiny", max_len=16)

    ref = _FusedKnnIndexImpl(enc, "cos", 64)
    ref.add_many(keys, texts, [None] * len(keys))
    ref.drain()
    ref_rows = ref.search_many(
        queries, [3] * len(queries), [None] * len(queries)
    )

    with _activated("dp=4,tp=2") as backend:
        impl = _FusedKnnIndexImpl(enc, "cos", 64)
        assert impl.knn.mesh is backend.mesh
        impl.add_many(keys, texts, [None] * len(keys))
        impl.drain()
        assert impl._pipeline is not None, "mesh backend must pipeline"
        assert impl._pipeline.replicas == backend.dp
        stats = impl._pipeline.stats()
        assert stats["rows"] == len(keys)
        per_replica = impl._pipeline.replica_stats()
        assert len(per_replica) == backend.dp
        assert sum(r["rows"] for r in per_replica) == len(keys)
        rows = impl.search_many(
            queries, [3] * len(queries), [None] * len(queries)
        )
    assert [[k for k, _ in r] for r in rows] == [
        [k for k, _ in r] for r in ref_rows
    ]
    np.testing.assert_allclose(
        np.array([[s for _, s in r] for r in rows]),
        np.array([[s for _, s in r] for r in ref_rows]),
        atol=2e-2,
        rtol=0,
    )


def test_pw_run_mesh_activates_backend_for_the_run():
    """pw.run(mesh=...) arms the backend for exactly the duration of the
    run (graph build + execution see it; it is gone afterwards), while
    engine.mesh stays the plain lint-facing spec dict."""
    from pathway_tpu.internals.runner import last_engine

    _require_devices()
    seen = []
    t = pw.debug.table_from_rows(pw.schema_from_types(k=str), [("a",)])
    pw.io.subscribe(
        t.select(k=t.k),
        on_change=lambda key, row, time, is_addition: seen.append(
            (row, mesh_backend.active_backend())
        ),
    )
    pw.run(mesh="dp=4,tp=2")
    assert [row for row, _ in seen] == [{"k": "a"}]
    backend = seen[0][1]
    assert backend is not None and (backend.dp, backend.tp) == (4, 2)
    assert last_engine().mesh == {"dp": 4, "tp": 2}
    assert mesh_backend.active_backend() is None


# -- /status mesh key --------------------------------------------------------


def test_status_mesh_key_live_and_lint_only():
    from pathway_tpu.internals.monitoring import PrometheusServer
    from pathway_tpu.internals.runner import last_engine

    _require_devices()
    t = pw.debug.table_from_rows(pw.schema_from_types(k=str), [("a",)])
    pw.io.subscribe(t, on_change=lambda *a, **k: None)
    pw.run(mesh="dp=4,tp=2")
    engine = last_engine()

    # after the run the backend is down: /status reports the lint-only
    # spec dict
    status = PrometheusServer(engine).status_json()
    assert status["mesh"] == {"active": False, "axes": {"dp": 4, "tp": 2}}

    # with the backend up, /status carries axes + per-replica gauges
    with _activated("dp=4,tp=2") as backend:
        backend.note_replica_degraded(2)
        live = PrometheusServer(engine).status_json()["mesh"]
        assert live["active"] is True
        assert live["axes"] == {"dp": 4, "tp": 2}
        assert live["device_count"] == 8
        assert live["sharded_ingest"] is True
        assert live["degraded_replicas"] == [2]
        assert len(live["replicas"]) == 4
        for r, gauges in enumerate(live["replicas"]):
            assert gauges["replica"] == r
            assert set(gauges) >= {"rows", "in_flight", "occupancy"}


# -- chaos: device_flap on an active mesh (satellite: degraded-mesh) ---------


def test_degraded_mesh_device_flap_drains_and_falls_back():
    """A device_flap while the dp=4 backend is mid-ingest must drain the
    per-replica in-flight window and route new ingest through the sync
    host path WITHOUT losing exactly-once semantics — every doc lands
    exactly once and stays searchable, same contract as the single-chip
    pipeline."""
    from pathway_tpu.internals import device_probe, faults
    from pathway_tpu.internals.device_probe import DeviceMonitor
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    _require_devices()
    texts = [f"alpha doc{i} bravo charlie" for i in range(24)]
    monitor = DeviceMonitor(interval_s=1.0, probe=lambda _t: (0.5, None))
    old = device_probe._monitor
    device_probe._monitor = monitor
    faults.install("device_flap@probes=1")
    try:
        with _activated("dp=4,tp=2") as backend, _env(
            PATHWAY_INGEST_CHUNK="8"
        ):
            impl = _FusedKnnIndexImpl(_encoder("mesh-flap-tiny"), "cos", 64)
            assert impl.knn.mesh is backend.mesh
            impl.add_many(range(12), texts[:12], [None] * 12)
            assert impl._pipeline is not None
            pipe = impl._pipeline
            # the flap fires between batches: monitor walks to DEGRADED
            assert monitor.probe_once()["state"] == "degraded"
            assert device_probe.device_degraded()
            backend.note_replica_degraded(1)
            assert backend.degraded_replicas() == [1]
            # new ingest bypasses the pipeline; in-flight work drains
            impl.add_many(range(12, 24), texts[12:], [None] * 12)
            stats = pipe.stats()
            assert stats["dispatched"] == stats["submitted"]
            assert stats["in_flight"] == 0
            assert not impl._pipeline_broken
            # exactly-once: all 24 docs landed, none duplicated
            assert len(impl.knn) == 24
            rows = impl.search_many(
                [texts[0], texts[23]], [1, 1], [None, None]
            )
            assert rows[0][0][0] == 0
            assert rows[1][0][0] == 23
            # budget exhausted: next probe re-promotes, mesh ingest resumes
            assert monitor.probe_once()["state"] == "healthy"
            backend.note_replicas_healthy()
            assert backend.degraded_replicas() == []
            assert impl._use_pipeline()
            assert backend.status()["degraded_replicas"] == []
    finally:
        device_probe._monitor = old
        faults.clear()
