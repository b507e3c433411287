"""Cost & efficiency observability (internals/costledger.py,
`pathway-tpu top`).

Covers the cost PR's acceptance contract: charges accumulate into
(workload, route, tenant) cells, batched searches split their device
time by the qtrace-carried attribution so cells SUM to real device time
(vs qtrace's full-batch latency charging), the conservation invariant
holds within 5% on the 8-device CPU mesh under concurrent ingest +
serving with two tenants, result-cache hits book a distinct "cache"
stage with zero device charge plus a computed savings gauge, the
DeviceTimePartitioner's binary burn heuristic is refined by the ledger's
serve share, and the `top` renderer works against /status JSON alone."""

from __future__ import annotations

import threading
import time

import pytest

from pathway_tpu.internals import (
    costledger,
    costmodel,
    mesh_backend,
    qtrace,
    serving,
    utilization,
)
from pathway_tpu.analysis import MeshSpec
from pathway_tpu.engine.index_node import ExternalIndexNode
from pathway_tpu.internals.device_pipeline import DevicePipeline


@pytest.fixture(autouse=True)
def _fresh_layers():
    """Fresh ledger, tracer, and utilization window on both sides —
    attribution tests must not see charges from neighboring tests."""
    costledger.reset_for_tests()
    qtrace.reset()
    utilization.reset_window()
    yield
    costledger.reset_for_tests()
    qtrace.reset()
    utilization.reset_window()


# ---------------------------------------------------------------------------
# cell accounting
# ---------------------------------------------------------------------------


def test_charge_accumulates_cells_totals_and_shares():
    if not costledger.ENABLED:
        pytest.skip("cost ledger disabled")
    led = costledger.ledger()
    led.charge("ingest", device_s=0.3, flops=9e9, bytes_moved=4096, docs=24)
    led.charge("ingest", device_s=0.1, flops=3e9, bytes_moved=1024, docs=8)
    led.charge("serve", "/search", "acme", device_s=0.2, queries=5)
    led.charge("maintenance", device_s=0.5)

    totals = led.totals()
    assert totals["ingest"]["device_s"] == pytest.approx(0.4)
    assert totals["ingest"]["flops"] == pytest.approx(12e9)
    assert totals["ingest"]["docs"] == 32
    assert totals["serve"]["queries"] == 5

    top = led.top_cells()
    # heaviest first, by device-seconds
    assert [c["workload"] for c in top] == ["maintenance", "ingest", "serve"]
    assert top[2] == {
        "workload": "serve", "route": "/search", "tenant": "acme",
        "device_s": 0.2, "flops": 0.0, "bytes": 0.0,
        "queries": 5, "docs": 0,
    }

    shares = led.workload_shares()
    assert shares["total_s"] == pytest.approx(1.1)
    assert shares["shares"]["ingest"] == pytest.approx(0.4 / 1.1, abs=1e-3)
    assert shares["shares"]["serve"] == pytest.approx(0.2 / 1.1, abs=1e-3)
    assert costledger.serve_device_share() == shares["shares"]["serve"]


def test_charge_search_splits_by_traced_attribution():
    """qtrace charges every traced query the FULL batch device time; the
    ledger splits it evenly so per-cell charges sum to real device time
    — the cross-check the two layers were built to support."""
    if not (costledger.ENABLED and qtrace.ENABLED):
        pytest.skip("needs both layers")
    tq = qtrace.tracker()
    assert tq.begin("q-a", route="/search", key=101, tenant="acme")
    assert tq.begin("q-b", route="/search", key=102, tenant="acme")
    assert tq.begin("q-c", route="/lookup", key=103, tenant="globex")
    # key 104 is untraced — the ("", "") bucket PWT801 warns about

    costledger.charge_search([101, 102, 103, 104], 0.4, tracer=tq)

    led = costledger.ledger()
    cells = {
        (c["route"], c["tenant"]): c
        for c in led.top_cells()
        if c["workload"] == "serve"
    }
    assert cells[("/search", "acme")]["device_s"] == pytest.approx(0.2)
    assert cells[("/search", "acme")]["queries"] == 2
    assert cells[("/lookup", "globex")]["device_s"] == pytest.approx(0.1)
    assert cells[("", "")]["device_s"] == pytest.approx(0.1)
    # the even split conserves: cells sum to the real batch wall time
    assert sum(c["device_s"] for c in cells.values()) == pytest.approx(0.4)
    # ... and the full elapsed fed the utilization window once
    assert utilization.device_window_seconds() == pytest.approx(0.4)
    # qtrace's convention for the SAME dispatch: full batch time each
    tq.note_device_keys([101, 102, 103, 104], 0.4)
    rec = tq.finish("q-a")
    assert rec["stages_ms"]["device"] == pytest.approx(400.0)


def test_status_shapes_and_disabled_guard(monkeypatch):
    monkeypatch.setattr(costledger, "ENABLED", False)
    assert costledger.cost_status() == {"enabled": False}
    assert costledger.cost_metrics() is None
    assert costledger.serve_device_share() is None
    # hook sugar is inert while disabled — no singleton materializes
    costledger.charge("ingest", device_s=1.0)
    costledger.charge_search([1], 1.0)
    costledger.note_cache_hits(["acme"])
    assert costledger._LEDGER is None

    monkeypatch.setattr(costledger, "ENABLED", True)
    assert costledger.cost_status() == {"enabled": True, "active": False}
    assert costledger.serve_device_share() is None  # never instantiated

    costledger.on_run_start()
    assert costledger.cost_metrics() is not None
    assert costledger.serve_device_share() is None  # empty window
    st = costledger.cost_status()
    assert st["active"] is True and st["enabled"] is True
    for key in (
        "totals", "top", "shares", "conservation", "efficiency_pct",
        "device_capacity_known", "cache_savings", "devices",
    ):
        assert key in st
    # CPU CI: peak unknown -> efficiency None (PWT802), never 0
    if not costmodel.device_capacity_known():
        costledger.charge("ingest", device_s=0.1, flops=1e9)
        assert costledger.ledger()._efficiency_pct() is None


# ---------------------------------------------------------------------------
# conservation on the 8-device CPU mesh, concurrent ingest + serving
# ---------------------------------------------------------------------------


class _FakeNode:
    """Exercises the REAL ExternalIndexNode._timed_search wrapper (marks,
    device charge, ledger split) over a host-only search."""

    _timed_search = ExternalIndexNode._timed_search

    def _search_many(self, values, ks, filters, q_keys=None):
        time.sleep(0.002)
        return [[] for _ in values]


def test_conservation_under_concurrent_ingest_and_serving():
    """The acceptance invariant: attributed device-seconds within 5% of
    the utilization window total, measured while an ingest pipeline and
    a two-tenant serving path charge concurrently on the dp=4,tp=2 CPU
    mesh."""
    import jax

    if not (costledger.ENABLED and utilization.ENABLED):
        pytest.skip("needs ledger + utilization")
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest emulates them)")
    backend = mesh_backend.activate(MeshSpec.parse("dp=4,tp=2"))
    assert backend is not None

    def prepare(item):
        rows = 8
        real, slab = 8 * 20, 8 * 32
        return item, {
            "rows": rows,
            "real_tokens": real,
            "slab_tokens": slab,
            "slab_bytes": slab * 4,
            "useful_flops": costmodel.encoder_useful_flops(real, rows),
        }

    def run_ingest():
        pipe = DevicePipeline(
            prepare,
            dispatch=lambda payload: payload,
            wait=lambda handle: time.sleep(0.002),
            name="cost-test",
            max_in_flight=2,
        )
        try:
            for i in range(16):
                pipe.submit(i)
            pipe.drain()
        finally:
            pipe.close()

    def run_serve():
        node = _FakeNode()
        tq = qtrace.tracker()
        tenants = ("acme", "globex")
        for i in range(12):
            qid = f"cq{i}"
            key = 1000 + i
            assert tq.begin(
                qid, route="/search", key=key,
                tenant=tenants[i % len(tenants)],
            )
            node._timed_search([key], [f"query {i}"], [3], [None])
            tq.finish(qid)

    ingest = threading.Thread(target=run_ingest)
    try:
        ingest.start()
        run_serve()
        ingest.join()

        led = costledger.ledger()
        cons = led.conservation()
        assert cons["attributed_s"] > 0
        assert cons["utilization_window_s"] > 0
        assert cons["ratio"] is not None
        assert 0.95 <= cons["ratio"] <= 1.05, cons

        # both workloads attributed, both tenants present
        shares = led.workload_shares()
        assert shares["seconds"]["ingest"] > 0
        assert shares["seconds"]["serve"] > 0
        serve_tenants = {
            c["tenant"] for c in led.top_cells(n=16)
            if c["workload"] == "serve"
        }
        assert {"acme", "globex"} <= serve_tenants
        queries = led.totals()["serve"]["queries"]
        assert queries == 12
        assert led.status()["devices"] == 8
    finally:
        mesh_backend.deactivate()


# ---------------------------------------------------------------------------
# result-cache hits: distinct "cache" stage, computed savings
# ---------------------------------------------------------------------------


def test_cache_hit_books_cache_stage_and_savings():
    if not (costledger.ENABLED and qtrace.ENABLED and serving.ENABLED):
        pytest.skip("needs ledger + qtrace + serving")
    tier = serving.reset_for_tests()
    try:
        # seed the uncached-query cost EWMA the savings gauge multiplies
        costledger.charge_search([1, 2], 0.2, tracer=None)

        calls = []

        def search_fn(values, ks, filters):
            calls.append(len(values))
            return [[(7, 0.9)] for _ in values]

        # miss fills the cache
        r1 = tier.cached_search(
            ["warm me"], [3], [None], search_fn, index_id=1, q_keys=[501]
        )
        tq = qtrace.tracker()
        assert tq.begin("q-hit", route="/search", key=502, tenant="acme")
        # hit: search_fn never called, span flagged cache_hit
        r2 = tier.cached_search(
            ["warm  ME"], [3], [None], search_fn, index_id=1, q_keys=[502]
        )
        assert r1 == r2 == [[(7, 0.9)]]
        assert calls == [1]

        rec = tq.finish("q-hit")
        # distinct "cache" stage, zero device charge — cached latency
        # stays out of the uncached device distribution
        assert rec["meta"]["cache_hit"] is True
        assert "cache" in rec["stages_ms"]
        assert "device" not in rec["stages_ms"]

        st = costledger.ledger().status()["cache_savings"]
        assert st["acme"]["hits"] == 1
        # computed, not inferred: hits x live EWMA uncached cost (0.1s)
        assert st["acme"]["saved_device_s"] == pytest.approx(0.1)
    finally:
        serving.shutdown()


# ---------------------------------------------------------------------------
# partitioner: the share signal refines the binary burn heuristic
# ---------------------------------------------------------------------------


def _burn_the_slo(tq):
    tq.set_slo(10.0)
    for i in range(32):
        assert tq.begin(f"burn{i}")
        tq._pending[f"burn{i}"]["marks"]["ingress"] -= 0.5
        tq.finish(f"burn{i}")
    assert (tq.burn_rate() or 0) >= 1.0


def test_partitioner_share_gates_engage_and_release():
    from pathway_tpu.internals import device_pipeline

    if not (costledger.ENABLED and qtrace.ENABLED):
        pytest.skip("needs ledger + qtrace")
    tier = serving.reset_for_tests()
    part = tier.partitioner
    led = costledger.ledger()
    try:
        _burn_the_slo(qtrace.tracker())

        # burning, but serving already holds >= its target share ->
        # priority must NOT engage (burn is not device starvation)
        led.charge("serve", "/search", "acme", device_s=0.9, queries=1)
        led.charge("ingest", device_s=0.1)
        part._next_tick = 0.0
        part.maybe_tick()
        assert part.priority is False
        assert part.serve_share == pytest.approx(0.9)
        assert part.status()["share_target"] == serving.SERVE_SHARE_TARGET

        # starve serving below the target -> the burn engages priority
        led.charge("ingest", device_s=9.0)
        part._next_tick = 0.0
        part.maybe_tick()
        assert part.priority is True
        assert device_pipeline.serving_scale() == serving.PRIORITY_SCALE
        assert "serve share" in (part.reason or "")

        # serving reaches its share while STILL burning -> release (the
        # binary heuristic alone would have held priority forever)
        led.charge("serve", "/search", "acme", device_s=30.0, queries=1)
        part._next_tick = 0.0
        part.maybe_tick()
        assert part.priority is False
        assert device_pipeline.serving_scale() == 1.0
    finally:
        part.release_for_tests()
        serving.shutdown()


# ---------------------------------------------------------------------------
# `pathway-tpu top`
# ---------------------------------------------------------------------------


def test_render_top_frames():
    from pathway_tpu.internals import trace_tool

    # disabled / idle frames degrade gracefully
    frame = trace_tool.render_top({"cost": {"enabled": False}})
    assert "cost ledger disabled" in frame
    frame = trace_tool.render_top(
        {"cost": {"enabled": True, "active": False}}
    )
    assert "cost ledger idle" in frame

    if not costledger.ENABLED:
        pytest.skip("cost ledger disabled")
    led = costledger.ledger()
    led.charge("ingest", device_s=0.3, flops=9e9, bytes_moved=4096, docs=24)
    led.charge("serve", "/search", "acme", device_s=0.1, queries=5)
    led.note_cache_hits(["acme"])
    status = {
        "worker_count": 1,
        "cost": costledger.cost_status(),
        "utilization": {"enabled": True, "bound_state": "compute"},
        "queries": {"slo": {"target_p99_ms": 50.0, "burn_rate": 0.1}},
        "memory": {"enabled": False},
    }
    frame = trace_tool.render_top(status)
    assert "pathway-tpu top" in frame and "bound=compute" in frame
    assert "device share" in frame
    assert "WORKLOAD" in frame and "TENANT" in frame
    assert "/search" in frame and "acme" in frame
    assert "cache savings [acme]: 1 hits" in frame
    if not costmodel.device_capacity_known():
        assert "PWT802" in frame  # efficiency n/a, says why


def test_main_top_once_against_live_status(monkeypatch, capsys):
    """--once fetches one /status frame and exits 0; a dead endpoint is
    a clean error, not a stack trace."""
    import argparse

    from pathway_tpu.internals import trace_tool

    if not costledger.ENABLED:
        pytest.skip("cost ledger disabled")
    costledger.ledger().charge("ingest", device_s=0.2, docs=8)
    served = {
        "worker_count": 1,
        "cost": costledger.cost_status(),
    }
    monkeypatch.setattr(
        trace_tool, "fetch_status", lambda url, timeout=5.0: served
    )
    args = argparse.Namespace(
        url=None, port=29999, interval=0.01, iterations=0, once=True
    )
    assert trace_tool.main_top(args) == 0
    out = capsys.readouterr().out
    assert "pathway-tpu top" in out and "ingest" in out

    def boom(url, timeout=5.0):
        raise OSError("connection refused")

    monkeypatch.setattr(trace_tool, "fetch_status", boom)
    assert trace_tool.main_top(args) == 1
    assert "could not fetch" in capsys.readouterr().err
