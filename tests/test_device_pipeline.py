"""Async device pipeline + packed ragged batching (tier-1).

Covers the PR's acceptance list: pack_batch packing invariants,
sync-vs-async EXACT ingest value parity, packed-vs-classic encoder
parity, the device_flap chaos drain (in-flight batches complete, new
work degrades to the sync path cleanly), and the pipeline-failure
synchronous replay.  Everything runs on the CPU backend with tiny
hash-tokenizer models — no 'slow' marks."""

from __future__ import annotations

import contextlib
import os
import threading

import numpy as np
import pytest

from pathway_tpu.models.minilm import SentenceEncoder
from pathway_tpu.models.tokenizer import (
    PACK_MAX_SEGMENTS,
    encode_batch,
    pack_batch,
)
from pathway_tpu.models.transformer import TransformerConfig

TINY = TransformerConfig(
    vocab_size=512, hidden=32, layers=1, heads=2, mlp_dim=64, max_len=64
)


def _encoder(name: str, max_len: int = 32) -> SentenceEncoder:
    # fresh (uncached) encoder; seed=0 default makes params deterministic,
    # so two constructions with the same name/config agree exactly
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


# -- packing ----------------------------------------------------------------


def _texts_by_path() -> tuple:
    from pathway_tpu.internals import tracing

    totals = tracing.spans_status()["totals"]
    return tuple(
        totals.get(f"prep.tokenize.{path}_texts", {"count": 0})["count"]
        for path in ("native", "python")
    )


@pytest.fixture(params=["native", "python"])
def pack(request):
    """`pack_batch` with its texts tokenised on one path: by the native
    library, or by `tokenizer.encode` as where no compiler is found.  On
    the native path every result is also held to the Python path's, array
    for array."""
    from pathway_tpu import native

    def on_python_path(*args, **kwargs):
        with _env(PATHWAY_DISABLE_NATIVE="1"):
            before = _texts_by_path()
            out = pack_batch(*args, **kwargs)
            assert _texts_by_path()[0] == before[0]
        return out

    if request.param == "python":
        return on_python_path
    if native.load() is None:
        pytest.skip("no native tokenizer: no compiler found")

    def on_native_path(tok, texts, **kwargs):
        before = _texts_by_path()
        ids, seg, slots = pack_batch(tok, texts, **kwargs)
        after = _texts_by_path()
        assert (after[0] - before[0], after[1] - before[1]) == (len(texts), 0)
        want_ids, want_seg, want_slots = on_python_path(tok, texts, **kwargs)
        assert ids.dtype == want_ids.dtype and seg.dtype == want_seg.dtype
        assert np.array_equal(ids, want_ids) and np.array_equal(seg, want_seg)
        assert slots == want_slots
        return ids, seg, slots

    return on_native_path


def test_pack_batch_slots_and_invariants(pack):
    tok = _encoder("pack-tiny").tokenizer
    texts = [
        f"alpha bravo charlie doc{i} " + "word " * (i % 7) for i in range(11)
    ]
    ids, seg, slots = pack(tok, texts, max_len=32, token_budget=64)
    ids, seg = np.asarray(ids), np.asarray(seg)
    assert ids.shape == seg.shape
    assert len(slots) == len(texts)
    rows, slab = ids.shape
    assert slab == 64  # short docs: the budget holds
    assert rows % 8 == 0  # bucketed row count
    # every doc's tokens land verbatim at its (row, segment) slot
    for (r, s), text in zip(slots, texts):
        want_ids, want_mask = encode_batch(tok, [text], max_len=32)
        want = np.asarray(want_ids)[0][np.asarray(want_mask)[0] > 0]
        got = ids[r][seg[r] == s + 1]
        assert np.array_equal(got.astype(np.int64), want.astype(np.int64))
    # segment ids are 1..k per row (0 = pad), non-decreasing runs
    for r in range(rows):
        nz = seg[r][seg[r] > 0]
        if nz.size:
            uniq = np.unique(nz)
            assert uniq[0] == 1
            assert np.array_equal(uniq, np.arange(1, uniq.size + 1))
            assert np.all(np.diff(nz) >= 0)
    assert seg.max() <= PACK_MAX_SEGMENTS


@pytest.mark.parametrize("size", ["small", "large"])
def test_pack_batch_small_batches_pack_longest_first(size, monkeypatch, pack):
    """A small batch takes the same rows whatever order its documents come
    in (longest first); a large one keeps first-fit in arrival order."""
    from pathway_tpu.models import tokenizer as tk

    tok = _encoder("pack-order", max_len=64).tokenizer
    rng = np.random.default_rng(7)
    texts = [" ".join(f"w{j}" for j in range(int(n))) for n in rng.integers(5, 60, size=40)]
    if size == "large":
        monkeypatch.setattr(tk, "PACK_SORT_ROWS", 4)  # the batch fills more rows
    shapes, first_rows = set(), set()
    for seed in range(6):
        order = np.random.default_rng(seed).permutation(len(texts))
        ids, seg, slots = pack(
            tok, [texts[i] for i in order], max_len=64, token_budget=64,
            row_bucket=False,
        )
        shapes.add(np.asarray(ids).shape)
        first_rows.add(slots[0][0])
        for (r, s), i in zip(slots, order):  # a slot still finds its document
            want_ids, want_mask = encode_batch(tok, [texts[i]], max_len=64)
            want = np.asarray(want_ids)[0][np.asarray(want_mask)[0] > 0]
            got = np.asarray(ids)[r][np.asarray(seg)[r] == s + 1]
            assert np.array_equal(got.astype(np.int64), want.astype(np.int64))
    if size == "small":
        assert len(shapes) == 1
    else:
        assert first_rows == {0}  # the first to arrive opens the first row


def test_pack_batch_budget_overflow_grows_slab(pack):
    tok = _encoder("pack-long", max_len=64).tokenizer
    long_doc = "stream table engine " * 20
    _ids1, mask1 = encode_batch(tok, [long_doc], max_len=64)
    need = int(np.asarray(mask1).sum())
    assert need > 16
    ids, seg, slots = pack(
        tok, [long_doc], max_len=64, token_budget=16
    )
    # a doc longer than the budget grows the slab instead of truncating
    assert np.asarray(ids).shape[1] >= need
    (r, s) = slots[0]
    assert int((np.asarray(seg)[r] == s + 1).sum()) == need


def test_pack_batch_max_segments_spill(pack):
    tok = _encoder("pack-many").tokenizer
    texts = [f"w{i}" for i in range(PACK_MAX_SEGMENTS + 8)]
    _ids, _seg, slots = pack(
        tok, texts, max_len=32, token_budget=4096
    )
    rows_used = {r for r, _s in slots}
    assert len(rows_used) >= 2  # spilled past one row's segment limit
    for r in rows_used:
        assert sum(1 for rr, _s in slots if rr == r) <= PACK_MAX_SEGMENTS


def test_packed_positions_restart_per_segment():
    import jax.numpy as jnp

    from pathway_tpu.models.trunk import packed_positions

    seg = jnp.asarray(
        [[1, 1, 1, 2, 2, 0, 0, 0], [1, 2, 2, 2, 3, 3, 0, 0]]
    )
    pos = np.asarray(packed_positions(seg))
    assert pos[0, :5].tolist() == [0, 1, 2, 0, 1]
    assert pos[1, :6].tolist() == [0, 0, 1, 2, 0, 1]


# -- value parity -----------------------------------------------------------


def test_sync_async_ingest_value_parity():
    """The pipelined route and the synchronous one (the recovery path
    after a failed batch, forced here through `_pipeline_broken`)
    produce byte-identical index buffers when packing is pinned off:
    identical chunk boundaries feed identical compiled dispatches, async
    only reorders WHEN they run."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    texts = [f"alpha bravo doc{i} charlie delta" for i in range(48)]
    keys = list(range(len(texts)))

    def ingest(synchronous: bool):
        with _env(PATHWAY_PACK_TOKEN_BUDGET="0", PATHWAY_INGEST_CHUNK="16"):
            impl = _FusedKnnIndexImpl(
                _encoder("parity-tiny"), "cos", len(texts)
            )
            impl._pipeline_broken = synchronous
            impl.add_many(keys, texts, [None] * len(keys))
            impl.drain()
            used_pipeline = impl._pipeline is not None
            return np.asarray(
                impl.knn._buffer.astype("float32")
            )[: len(keys)], used_pipeline

    sync_buf, sync_used = ingest(True)
    async_buf, async_used = ingest(False)
    assert not sync_used and async_used
    assert np.array_equal(sync_buf, async_buf)


def test_packed_vs_classic_encoder_parity():
    enc = _encoder("packed-parity")
    texts = [
        "alpha bravo charlie",
        "delta " * 12,
        "echo foxtrot golf hotel india juliet",
        "kilo",
    ]
    classic = enc.encode(texts)
    with _env(PATHWAY_PACK_TOKEN_BUDGET="64"):
        packed = enc.encode_packed(texts)
    assert packed.shape == classic.shape
    np.testing.assert_allclose(packed, classic, atol=2e-2, rtol=0)
    # both are L2-normalized
    np.testing.assert_allclose(
        np.linalg.norm(packed, axis=1), 1.0, atol=1e-3
    )


# -- chaos: device flap mid-pipeline ---------------------------------------


def test_device_flap_mid_pipeline_drains_and_degrades():
    """A device_flap firing mid-pipeline must drain the in-flight batches
    (nothing lost, nothing duplicated) and route new ingest through the
    classic sync path while DEGRADED — without marking the pipeline
    broken (it resumes after re-promotion)."""
    from pathway_tpu.internals import device_probe, faults
    from pathway_tpu.internals.device_probe import DeviceMonitor
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    impl = _FusedKnnIndexImpl(_encoder("flap-tiny"), "cos", 64)
    texts = [f"alpha doc{i} bravo charlie" for i in range(24)]
    monitor = DeviceMonitor(interval_s=1.0, probe=lambda _t: (0.5, None))
    old = device_probe._monitor
    device_probe._monitor = monitor
    faults.install("device_flap@probes=1")
    try:
        with _env(PATHWAY_INGEST_CHUNK="8"):
            impl.add_many(range(12), texts[:12], [None] * 12)
            assert impl._pipeline is not None
            pipe = impl._pipeline
            # the flap fires between batches: monitor walks to DEGRADED
            assert monitor.probe_once()["state"] == "degraded"
            assert device_probe.device_degraded()
            # new ingest bypasses the pipeline; in-flight work drains first
            impl.add_many(range(12, 24), texts[12:], [None] * 12)
            stats = pipe.stats()
            assert stats["dispatched"] == stats["submitted"]
            assert stats["in_flight"] == 0
            assert not impl._pipeline_broken
            assert len(impl.knn) == 24
            rows = impl.search_many(
                [texts[0], texts[23]], [1, 1], [None, None]
            )
            assert rows[0][0][0] == 0
            assert rows[1][0][0] == 23
            # budget exhausted: next probe re-promotes, pipeline resumes
            assert monitor.probe_once()["state"] == "healthy"
            assert impl._use_pipeline()
    finally:
        device_probe._monitor = old
        faults.clear()


# -- failure model ----------------------------------------------------------


def test_pipeline_error_parks_and_replays():
    """A dispatch failure parks the failing item AND everything still
    queued (in order), surfaces as DevicePipelineError, and take_failed
    resets the pipeline for further use."""
    from pathway_tpu.internals.device_pipeline import (
        DevicePipeline,
        DevicePipelineError,
    )

    gate = threading.Event()
    dispatched = []

    def prepare(item):
        return item, {"rows": 1}

    def dispatch(payload):
        gate.wait(10)
        if payload == "boom":
            raise RuntimeError("injected dispatch failure")
        dispatched.append(payload)
        return None

    pipe = DevicePipeline(
        prepare, dispatch, wait=lambda _h: None, name="test-pipe"
    )
    try:
        pipe.submit("a")
        pipe.submit("boom")
        pipe.submit("b")
        gate.set()
        with pytest.raises(DevicePipelineError):
            pipe.drain()
        assert pipe.take_failed() == ["boom", "b"]
        assert dispatched == ["a"]
        # error state cleared: the pipeline accepts work again
        pipe.submit("c")
        pipe.drain()
        assert dispatched == ["a", "c"]
    finally:
        pipe.close()


@pytest.mark.parametrize(
    "variable, attribute, depth",
    [
        ("PATHWAY_PIPELINE_QUEUE", "max_prepared", 4),
        ("PATHWAY_PIPELINE_IN_FLIGHT", "max_in_flight", 2),
        ("PATHWAY_PIPELINE_PREP_WORKERS", "prep_workers", 2),
    ],
)
def test_pipeline_depths_are_constants_not_environment(
    variable, attribute, depth
):
    """The three depths are the module's constants whatever the
    environment says; a caller that needs others passes them."""
    from pathway_tpu.internals.device_pipeline import DevicePipeline

    def build(**kwargs):
        return DevicePipeline(
            lambda item: (item, {}), lambda payload: None,
            wait=lambda _h: None, name="test-depths", **kwargs
        )

    with _env(**{variable: "7"}):
        pipe = build()
        try:
            assert getattr(pipe, attribute) == depth
        finally:
            pipe.close()
        pipe = build(**{attribute: 3})
        try:
            assert getattr(pipe, attribute) == 3
        finally:
            pipe.close()


def test_impl_pipeline_failure_replays_synchronously(caplog):
    """An impl-level dispatch failure downgrades to the classic path and
    replays the parked batches exactly once — every doc lands — and the
    downgrade is visible: counted, and logged with the cause's traceback."""
    from pathway_tpu.internals import device_pipeline
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    fallbacks_before = device_pipeline.pipeline_status()["fallbacks"]

    impl = _FusedKnnIndexImpl(_encoder("fallback-tiny"), "cos", 32)
    texts = [f"delta doc{i} echo foxtrot" for i in range(12)]
    orig = impl.fused.dispatch_batch
    state = {"failures": 1}

    def flaky(payload):
        if state["failures"]:
            state["failures"] -= 1
            raise RuntimeError("injected dispatch failure")
        return orig(payload)

    impl.fused.dispatch_batch = flaky
    with _env(PATHWAY_INGEST_CHUNK="4"):
        with caplog.at_level("ERROR"):
            impl.add_many(range(12), texts, [None] * 12)
            impl.drain()
        assert impl._pipeline_broken
        status = device_pipeline.pipeline_status()
        assert status["fallbacks"] == fallbacks_before + 1
        (record,) = [
            r for r in caplog.records if "device pipeline disabled" in r.message
        ]
        assert record.exc_info[0] is RuntimeError
        assert "injected dispatch failure" in caplog.text
        assert "in flaky" in caplog.text  # the traceback, not one line
        assert len(impl.knn) == 12
        rows = impl.search_many([texts[5]], [1], [None])
        assert rows[0][0][0] == 5
        # broken pipeline stays off: further ingest is classic and works
        impl.add_many([12], ["golf doc12 hotel"], [None])
        assert len(impl.knn) == 13


# -- observability ----------------------------------------------------------


def test_pipeline_status_and_gauges():
    from pathway_tpu.internals.device_pipeline import (
        pipeline_metrics,
        pipeline_status,
    )
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    from pathway_tpu.internals import tracing

    tracing.reset_spans()
    impl = _FusedKnnIndexImpl(_encoder("status-tiny"), "cos", 32)
    texts = [f"india doc{i} juliet kilo" for i in range(16)]
    with _env(PATHWAY_PACK_TOKEN_BUDGET="64", PATHWAY_INGEST_CHUNK="8"):
        impl.add_many(range(16), texts, [None] * 16)
        impl.drain()
        status = pipeline_status()
        assert status["active"] >= 1
        assert status["rows"] >= 16
        assert status["pad_waste_ratio"] is not None
        assert 0.0 <= status["pad_waste_ratio"] < 1.0
        rendered = pipeline_metrics().render()
        assert "pathway_device_pad_waste_ratio" in rendered
        assert "pathway_device_pipeline_queue_depth" in rendered
        assert "pathway_device_pipeline_occupancy" in rendered
        # the span record attributes host prep vs device launch, with the
        # rows and the submission number of each chunk
        spans = [
            ev for ev in tracing.export_span_events()
            if ev[3].startswith("knn-ingest")
        ]
        by_name = {}
        for _k, _w, name, _thread, _ts, _dur, seq, _ep, _parent, rows in spans:
            by_name.setdefault(name, []).append((seq, rows))
        assert sorted(by_name["pipeline.prep"]) == [(1, 8), (2, 8)]
        assert sorted(by_name["pipeline.launch"]) == [(1, 8), (2, 8)]
        assert {"launch.encode", "launch.scatter", "prep.tokenize",
                "prep.pack"} <= set(by_name)
        assert status["prep_workers"] >= 2
