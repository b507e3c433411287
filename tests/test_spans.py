"""The span record (internals/tracing.py): totals, ring, identifiers,
the profiler annotation, and the sites that feed it on the ingest path.
All on the CPU."""

from __future__ import annotations

import gc
import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from pathway_tpu.internals import tracing
from pathway_tpu.internals.device_pipeline import DevicePipeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def record():
    return tracing.reset_spans()


def _totals(name: str) -> dict:
    return tracing.spans_status()["totals"][name]


def _ring(rec, name: str | None = None) -> list:
    return [ev for ev in list(rec.ring) if name is None or ev[0] == name]


# -- arithmetic ---------------------------------------------------------------


def _nest(outer: str, children: tuple, pause: float = 0.01) -> None:
    with tracing.span(outer):
        time.sleep(pause)
        for child in children:
            with tracing.span(child):
                time.sleep(pause)
                with tracing.span(child + ".leaf"):
                    time.sleep(pause)


def test_self_time_is_duration_minus_children_on_each_thread(record):
    """Nested and sibling spans on two threads at once: a span's self time
    is its duration minus what its direct children cover on its own
    thread, and the other thread's spans take nothing from it."""
    threads = [
        threading.Thread(target=_nest, args=("t1", ("t1.a", "t1.b"))),
        threading.Thread(target=_nest, args=("t2", ("t2.a",))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    t1, a, b = _totals("t1"), _totals("t1.a"), _totals("t1.b")
    leaves = _totals("t1.a.leaf")["total_s"] + _totals("t1.b.leaf")["total_s"]
    assert t1["self_s"] == pytest.approx(
        t1["total_s"] - a["total_s"] - b["total_s"], abs=1e-9
    )
    assert a["self_s"] == pytest.approx(
        a["total_s"] - _totals("t1.a.leaf")["total_s"], abs=1e-9
    )
    assert leaves < a["total_s"] + b["total_s"] < t1["total_s"]
    # a leaf's self time is all of it; each slept about 10 ms
    assert _totals("t1.a.leaf")["self_s"] == _totals("t1.a.leaf")["total_s"]
    assert 0.009 < t1["self_s"] < t1["total_s"] - 0.035
    t2 = _totals("t2")
    assert t2["self_s"] == pytest.approx(
        t2["total_s"] - _totals("t2.a")["total_s"], abs=1e-9
    )
    # sleeping is not CPU: wall far above cpu, which is what shows a GIL wait
    assert t1["cpu_s"] < 0.5 * t1["total_s"]
    parents = {ev[0]: ev[6] for ev in _ring(record)}
    assert parents["t1"] is None and parents["t1.b"] == "t1"
    assert parents["t1.b.leaf"] == "t1.b" and parents["t2.a"] == "t2"


def test_children_inherit_seq_and_epoch(record):
    with tracing.span("outer", seq=7, epoch=12):
        assert tracing.current_epoch() == 12
        with tracing.span("inner"):
            pass
        with tracing.span("other", seq=8):
            pass
    assert tracing.current_epoch() is None
    got = {ev[0]: (ev[4], ev[5]) for ev in _ring(record)}
    assert got == {"outer": (7, 12), "inner": (7, 12), "other": (8, 12)}


def test_a_span_that_raises_still_closes_and_pops(record):
    with pytest.raises(ValueError):
        with tracing.span("outer"):
            with tracing.span("boom", rows=3):
                raise ValueError("x")
    assert record.here().stack == []
    assert _totals("boom")["count"] == 1 and _totals("boom")["rows"] == 3
    assert _totals("outer")["count"] == 1
    with tracing.span("after"):
        pass
    assert _ring(record, "after")[0][6] is None  # no stale parent


def test_cancel_record_and_add(record):
    with tracing.span("kept"):
        with tracing.span("dropped") as sp:
            sp.cancel()
    tracing.record("estimate", 10.0, 10.25, seq=3, rows=5)
    tracing.add("counter", 1.5)
    tracing.add("counter", 0.5, 2)
    totals = tracing.spans_status()["totals"]
    assert "dropped" not in totals and totals["kept"]["count"] == 1
    assert totals["estimate"]["total_s"] == 0.25
    assert totals["estimate"]["rows"] == 5 and totals["estimate"]["cpu_s"] == 0
    assert totals["counter"] == {
        "count": 3, "total_s": 2.0, "cpu_s": 0.0, "self_s": 0.0, "rows": 0,
        "max_s": 0.0, "open_s": 0.0,
    }
    assert _ring(record, "estimate")[0][4] == 3


def test_an_open_span_shows_its_seconds_so_far(record):
    """total_s + open_s differenced between two readings is the time
    inside the interval, whether the span closed in it or not."""
    entered, leave = threading.Event(), threading.Event()

    def hold():
        with tracing.span("held", seq=1):
            entered.set()
            leave.wait(timeout=30)

    thread = threading.Thread(target=hold, name="holder")
    thread.start()
    assert entered.wait(timeout=30)
    first = tracing.spans_status()["totals"]["held"]
    time.sleep(0.03)
    second = tracing.spans_status()["totals"]["held"]
    leave.set()
    thread.join(timeout=30)
    assert not thread.is_alive()
    third = tracing.spans_status()["totals"]["held"]
    assert first["count"] == second["count"] == 0 and first["total_s"] == 0
    assert 0.03 <= second["open_s"] - first["open_s"] < 0.5
    assert third["count"] == 1 and third["open_s"] == 0
    elapsed = [t["total_s"] + t["open_s"] for t in (first, second, third)]
    assert elapsed == sorted(elapsed) and elapsed[2] - elapsed[0] < 0.6


def test_sampled_cpu_time_of_short_spans_adds_up(record):
    """A name whose spans average under a millisecond has thread_time
    read on one span in 16, counted 16 times: the total stays an estimate
    of the same quantity."""
    def burn():
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.0002:
            pass

    for _ in range(161):
        with tracing.span("short"):
            burn()
    short = _totals("short")
    assert short["count"] == 161 and short["total_s"] < 161 * tracing.SHORT_SPAN_S
    assert 0.5 * short["total_s"] < short["cpu_s"] < 1.5 * short["total_s"]
    with tracing.span("long"):
        time.sleep(0.002)
        burn()
    assert 0.0002 <= _totals("long")["cpu_s"] < 0.002


def test_ring_is_bounded():
    rec = tracing.reset_spans(capacity=16)
    for i in range(100):
        with tracing.span("x", seq=i):
            pass
    assert len(rec.ring) == 16 and _ring(rec)[-1][4] == 99
    assert _totals("x")["count"] == 100
    tracing.reset_spans()


def test_subscription_gets_the_spans_own_duration(record):
    got = []
    tracing.subscribe("subscribed.name", got.append)
    try:
        with tracing.span("subscribed.name") as sp:
            time.sleep(0.002)
        with tracing.span("another.name"):
            pass
    finally:
        tracing._SUBSCRIBERS.pop("subscribed.name")
    assert got == [sp.dur] and sp.dur == sp.t1 - sp.t0


# -- the pipeline ---------------------------------------------------------------


def _pipeline(name: str, prep_s=0.0, launch_s=0.0, wait_s=0.0, **kwargs):
    def prepare(item):
        time.sleep(prep_s)
        return item, {"rows": 4, "real_tokens": 8, "slab_tokens": 16}

    def dispatch(payload):
        time.sleep(launch_s)
        return payload

    def wait(handle):
        time.sleep(wait_s)

    return DevicePipeline(prepare, dispatch, wait=wait, name=name, **kwargs)


def test_one_batch_carries_epoch_and_seq_from_submit_to_window_wait(record):
    """Every span of one ingested batch has the (epoch, seq) of the tick
    and the submission that brought it: blocked submit, prep, the dispatch
    thread's prep_wait and launch, and the window_wait that retires it."""
    pipe = _pipeline("ids", prep_s=0.02, launch_s=0.002, wait_s=0.002,
                     max_prepared=1, max_in_flight=1, prep_workers=1)
    try:
        for tick in (10, 12, 14, 16):
            with tracing.span("engine.tick", epoch=tick):
                pipe.submit(tick)
        pipe.drain()
    finally:
        pipe.close()
    by_seq: dict = {}
    for name, thread, _t0, _t1, seq, epoch, _parent, _rows in _ring(record):
        if name.startswith("pipeline.") and seq is not None:
            by_seq.setdefault(seq, {})[name] = (epoch, thread)
    # batch 2 (tick 12) found the queue of one full, so its submit blocked
    assert set(by_seq[2]) >= {
        "pipeline.submit_blocked", "pipeline.prep", "pipeline.prep_wait",
        "pipeline.launch", "pipeline.window_wait", "pipeline.device",
    }
    for seq, tick in ((1, 10), (2, 12), (3, 14), (4, 16)):
        assert {e for e, _ in by_seq[seq].values()} == {tick}, by_seq[seq]
    assert by_seq[2]["pipeline.prep"][1].startswith("ids-prep")
    assert by_seq[2]["pipeline.launch"][1] == "ids-dispatch"
    assert by_seq[2]["pipeline.window_wait"][1] == "ids-dispatch"
    # the first batch went straight in: its submit never waited
    assert "pipeline.submit_blocked" not in by_seq[1]
    # the blocked submit is the tick's child, so the tick's self time
    # excludes it
    blocked = _ring(record, "pipeline.submit_blocked")
    assert all(ev[6] == "engine.tick" for ev in blocked)
    tick = _totals("engine.tick")
    assert tick["self_s"] == pytest.approx(
        tick["total_s"] - _totals("pipeline.submit_blocked")["total_s"],
        abs=1e-9,
    )


def test_the_dispatch_threads_four_spans_cover_its_life(record):
    """starved + prep_wait + window_wait + launch within 2% of the time
    the dispatch thread lived."""
    born = time.perf_counter()
    # batches of tens of milliseconds, as real ones are: the accounting
    # between two spans (a few hundred microseconds a batch) is then the
    # fraction of a percent it is in a deployment
    pipe = _pipeline("cover", prep_s=0.06, launch_s=0.02, wait_s=0.03)
    try:
        time.sleep(0.05)  # starved before the first batch
        for i in range(12):
            pipe.submit(i)
        pipe.drain()
        time.sleep(0.03)  # and after the last
    finally:
        pipe.close()
    assert not pipe._thread.is_alive()
    lived = time.perf_counter() - born
    parts = {
        "pipeline.starved": 0.0, "pipeline.prep_wait": 0.0,
        "pipeline.window_wait": 0.0, "pipeline.launch": 0.0,
    }
    for name, thread, t0, t1, *_ in _ring(record):
        if thread == "cover-dispatch" and name != "pipeline.device":
            # (pipeline.device is the estimate filed from this thread, not
            # time of its own)
            assert name in parts, name
            parts[name] += t1 - t0
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(lived, rel=0.02), parts
    assert _totals("pipeline.launch")["rows"] == 48
    # drain ran on this thread and waited for the last handles
    assert _totals("pipeline.drain")["count"] == 1


def test_a_drain_with_nothing_in_flight_is_not_recorded(record):
    pipe = _pipeline("idle")
    try:
        pipe.drain()
    finally:
        pipe.close()
    assert "pipeline.drain" not in tracing.spans_status()["totals"]


def test_utilization_window_gets_what_the_spans_measured(record):
    """One subscription instead of a note_span beside every site: the
    window's span seconds equal the record's totals, name for name."""
    from pathway_tpu.internals import utilization

    utilization.reset_window()
    pipe = _pipeline("feed", prep_s=0.003, launch_s=0.002, wait_s=0.002,
                     max_in_flight=1)
    try:
        for i in range(6):
            pipe.submit(i)
        pipe.drain()
    finally:
        pipe.close()
    seconds = utilization.tracker().snapshot()["span_seconds"]
    for span_name, kind in (
        ("pipeline.prep", "prep"), ("pipeline.launch", "dispatch"),
        ("pipeline.window_wait", "wait"), ("pipeline.drain", "drain"),
        ("pipeline.device", "device"),
    ):
        assert seconds[kind] == pytest.approx(
            _totals(span_name)["total_s"], abs=1e-5
        ), kind
    assert utilization.tracker().snapshot()["dispatches"] == 6
    utilization.reset_window()


def test_launch_children_come_from_the_index_path(record):
    """ops/knn.py: launch.encode and launch.scatter are children of
    pipeline.launch with its seq; models/tokenizer.py: two spans a batch
    under pipeline.prep."""
    from tests.test_device_pipeline import _encoder, _env
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    impl = _FusedKnnIndexImpl(_encoder("spans-tiny"), "cos", 32)
    texts = [f"lima doc{i} mike november" for i in range(12)]
    with _env(PATHWAY_PACK_TOKEN_BUDGET="64", PATHWAY_INGEST_CHUNK="4"):
        with tracing.span("engine.tick", epoch=6):
            impl.add_many(range(12), texts, [None] * 12)
        impl.drain()
    mine = [ev for ev in _ring(record) if ev[1].startswith("knn-ingest")]
    parents = {}
    for name, _thread, _t0, _t1, seq, epoch, parent, rows in mine:
        parents.setdefault(name, set()).add(parent)
        if name != "pipeline.starved":
            assert epoch == 6 and seq in (1, 2, 3), (name, seq, epoch)
    assert parents["launch.encode"] == {"pipeline.launch"}
    assert parents["launch.scatter"] == {"pipeline.launch"}
    assert parents["prep.tokenize"] == {"pipeline.prep"}
    assert parents["prep.pack"] == {"pipeline.prep"}
    # per batch, never per text: 3 chunks of 4 texts, one span each
    assert _totals("prep.tokenize")["count"] == 3
    assert _totals("prep.tokenize")["rows"] == 12
    assert _totals("launch.scatter")["rows"] == 12
    launch = _totals("pipeline.launch")
    assert 0 < launch["self_s"] < launch["total_s"]


def test_prep_counts_a_batchs_texts_by_the_path_that_tokenised_them(record):
    """models/tokenizer.py: after a packed ingest batch
    prep.tokenize.native_texts + .python_texts have risen by the batch's
    texts, split by the path each took (a text that is not ASCII goes
    through `tokenizer.encode`; all of them where the library is not
    built), with the two spans still under pipeline.prep."""
    from pathway_tpu import native
    from tests.test_device_pipeline import _encoder, _env
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    impl = _FusedKnnIndexImpl(_encoder("spans-paths"), "cos", 32)
    texts = [f"sierra doc{i} tango uniform" for i in range(12)]
    texts[3] = "sierra doc3 “tango” uniform"
    texts[7] = "sierra doc7 tangö uniform"
    with _env(PATHWAY_PACK_TOKEN_BUDGET="64", PATHWAY_INGEST_CHUNK="4"):
        impl.add_many(range(12), texts, [None] * 12)
        impl.drain()
    by_native = 10 if native.load() is not None else 0
    assert _totals("prep.tokenize.native_texts")["count"] == by_native
    assert _totals("prep.tokenize.python_texts")["count"] == 12 - by_native
    assert _totals("prep.tokenize")["rows"] == 12
    with _env(PATHWAY_PACK_TOKEN_BUDGET="64", PATHWAY_DISABLE_NATIVE="1"):
        impl.add_many(range(12, 16), texts[:4], [None] * 4)
        impl.drain()
    assert _totals("prep.tokenize.native_texts")["count"] == by_native
    assert _totals("prep.tokenize.python_texts")["count"] == 16 - by_native
    for name in ("prep.tokenize", "prep.pack"):
        parents = {ev[6] for ev in _ring(record, name)}
        assert parents == {"pipeline.prep"}, (name, parents)
        assert _totals(name)["count"] == 4


def test_packed_batches_are_counted_by_the_attention_they_ran(
    record, monkeypatch
):
    """ops/knn.py counts every packed batch under the attention its
    compiled slab shape runs (`packed_attention_fused`: backend and
    static shape): the dense path off the TPU; the fused kernel where the
    choice says so (forced here, interpreted on the CPU).  Both names are
    in /status "spans", and both paths fill the index alike."""
    import numpy as np

    from tests.test_device_pipeline import _env
    from pathway_tpu.models import transformer
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    config = transformer.TransformerConfig(
        vocab_size=512, hidden=128, layers=1, heads=4, mlp_dim=128, max_len=64
    )
    texts = [f"oscar doc{i} papa quebec romeo" for i in range(12)]

    def ingest(name: str):
        impl = _FusedKnnIndexImpl(
            SentenceEncoder(name, config=config, max_len=32), "cos", 32
        )
        with _env(PATHWAY_PACK_TOKEN_BUDGET="64", PATHWAY_INGEST_CHUNK="4"):
            impl.add_many(range(12), texts, [None] * 12)
            impl.drain()
        return np.asarray(impl.knn._buffer.astype("float32"))[:12]

    assert not transformer.packed_attention_fused(config, 64)  # no TPU here
    dense = ingest("attn-dense")
    assert _totals("launch.encode.attn_dense")["count"] == 3
    assert "launch.encode.attn_fused" not in tracing.spans_status()["totals"]

    monkeypatch.setattr(
        transformer, "packed_attention_fused",
        lambda config, length, use_flash=None: True,
    )
    fused = ingest("attn-fused")
    assert _totals("launch.encode.attn_fused")["count"] == 3
    assert _totals("launch.encode.attn_dense")["count"] == 3
    np.testing.assert_allclose(fused, dense, atol=2e-2, rtol=0)
    status = tracing.spans_status()["totals"]
    assert status["launch.encode.attn_fused"]["count"] == 3
    assert status["launch.encode.attn_dense"]["count"] == 3
    assert status["launch.encode"]["count"] == 6


# -- /status --------------------------------------------------------------------


def test_status_spans_schema_and_monotone_totals(record):
    from pathway_tpu.engine.engine import Engine, InputQueueSource
    from pathway_tpu.engine.value import ref_scalar
    from pathway_tpu.internals.monitoring import PrometheusServer

    eng = Engine()
    src = InputQueueSource(eng)
    server = PrometheusServer(eng)
    first = None
    for tick in (2, 4, 6):
        src.push(tick, [(ref_scalar("k", tick), (tick,), 1)])
        eng.process_time(tick)
        spans = server.status_json()["spans"]
        assert set(spans) == {"monotonic_s", "totals", "gc_recent"}
        entry = spans["totals"]["engine.tick"]
        assert set(entry) == {
            "count", "total_s", "cpu_s", "self_s", "rows", "max_s", "open_s",
        }
        if first is not None:
            assert spans["monotonic_s"] > first["monotonic_s"]
            before = first["totals"]["engine.tick"]
            assert entry["count"] == before["count"] + 1
            assert all(entry[k] >= before[k] for k in entry if k != "open_s")
        first = spans
    assert first["totals"]["engine.tick"]["count"] == 3
    assert first["totals"]["engine.tick"]["rows"] == 3
    assert eng.metrics.tick_hist.count == 3
    # the tick is timed once: the histogram's sum is the span's total
    assert eng.metrics.tick_hist.sum == pytest.approx(
        first["totals"]["engine.tick"]["total_s"], abs=1e-9
    )
    assert [ev[5] for ev in _ring(record, "engine.tick")] == [2, 4, 6]
    eng._gc_unfreeze()


def test_health_pressure_counts_changes_and_time_under_pressure(record):
    from pathway_tpu.internals import device_pipeline, health

    ctl = health.reset_for_tests()
    try:
        ctl._on_pressure("test")  # 1.0 -> 0.5
        ctl._on_pressure("test")  # 0.5 -> 0.25
        time.sleep(0.05)
        mid = tracing.spans_status()["totals"]["health.pressure"]
        assert mid["count"] == 2 and 0.05 <= mid["total_s"] < 0.5
        time.sleep(0.02)
        # still held at the next reading: the total has been brought up
        later = tracing.spans_status()["totals"]["health.pressure"]
        assert later["total_s"] >= mid["total_s"] + 0.02
        for _ in range(3):
            ctl._on_pressure_clear()  # 0.25 -> 0.5 -> 0.75 -> 1.0
        done = tracing.spans_status()["totals"]["health.pressure"]
        assert done["count"] == 5 and device_pipeline.backpressure_scale() == 1.0
        time.sleep(0.02)
        assert tracing.spans_status()["totals"]["health.pressure"] == done
    finally:
        ctl.on_run_end()
        health.reset_for_tests()


# -- garbage collection ---------------------------------------------------------


@pytest.mark.parametrize("generation", [1, 2])
def test_host_gc_records_a_forced_collection(record, generation):
    tracing.install_gc_hook()
    tracing.install_gc_hook()  # once, however often it is asked for
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = time.monotonic()
    with tracing.span("around"):
        gc.collect(generation)
    spans = tracing.spans_status()
    assert spans["totals"]["host.gc"]["count"] >= 1
    recent = [r for r in spans["gc_recent"] if r[0] >= before]
    assert recent and recent[-1][2] == generation
    assert recent[-1][1] == pytest.approx(
        max(ev[3] - ev[2] for ev in _ring(record, "host.gc")), abs=1e-9
    )
    # it ran on this thread inside `around`, and is taken off its self time
    assert _ring(record, "host.gc")[-1][6] == "around"
    around = _totals("around")
    assert around["self_s"] < around["total_s"]


def test_young_collections_are_counted_without_a_span(record):
    tracing.install_gc_hook()
    gc.collect(0)
    assert _totals("host.gc")["count"] >= 1
    assert not _ring(record, "host.gc")
    # but it is among the recent maxima, one entry a second
    recent = tracing.spans_status()["gc_recent"]
    assert recent and recent[-1][2] == 0 and recent[-1][1] > 0
    assert len({int(t_end) for t_end, _d, _g in recent}) == len(recent)


def test_a_quiesced_run_with_no_data_still_shows_recent_collections(record):
    """`host.gc_pause_max_ms` reads `gc_recent`: six seconds of a streaming
    run that ingests nothing, with the automatic collector off, must leave
    collections there — the policy's pulses on their floor in time."""
    import pathway_tpu as pw

    started = []

    class Idle(pw.io.python.ConnectorSubject):
        def run(self):
            # the run has switched the automatic collector off by now: a
            # young collection between `pw.run`'s entry and that moment is
            # not the policy's
            started.append(time.monotonic())
            time.sleep(6.0)

    class Schema(pw.Schema):
        x: int

    seen = []
    table = pw.io.python.read(Idle(), schema=Schema)
    pw.io.subscribe(table, on_change=lambda *a, **k: seen.append(1))
    pw.run(monitoring_level=None)
    spans = tracing.spans_status()
    (before,) = started
    assert not seen
    assert spans["totals"]["gc.automatic"]["count"] == 0
    assert spans["totals"]["gc.pulses"]["count"] >= 5
    recent = [r for r in spans["gc_recent"] if r[0] >= before]
    assert len(recent) >= 5 and all(r[1] > 0 and r[2] in (1, 2) for r in recent)


# -- the profiler's clock -------------------------------------------------------


def test_a_capture_holds_pipeline_launch_with_its_seq(record, tmp_path):
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the benchmark harness's options
    pipe = _pipeline("cap", launch_s=0.001)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.span("engine.tick", epoch=8):
            for i in range(3):
                pipe.submit(i)
        pipe.drain()
    finally:
        jax.profiler.stop_trace()
        pipe.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    launches, names = [], set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "pipeline.launch":
                    launches.append(dict(ev.stats))
    assert sorted(s["seq"] for s in launches) == [1, 2, 3]
    assert {s["epoch"] for s in launches} == {8}
    # the estimate is kept out of the capture: the device plane has the truth
    assert "pipeline.device" not in names
    assert {"pipeline.prep", "pipeline.prep_wait", "engine.tick"} <= names


def test_connector_read_imports_no_jax(tmp_path):
    """The connector and the engine stay jax-free: a span in a process
    without jax is totals and ring only."""
    (tmp_path / "a.jsonl").write_text('{"data": "x"}\n{"data": "y"}\n')
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
from pathway_tpu.internals import schema, tracing
from pathway_tpu.io import fs

rows = []
class Sink:
    def push_rows(self, batch): rows.extend(batch)
    def push_tuples(self, batch): rows.extend(batch)
    def push_row(self, row): rows.append(row)
    def commit(self, **kw): pass

subject = fs._FsSubject(
    {str(tmp_path)!r}, "jsonlines", schema.schema_from_types(data=str),
    "static", False,
)
subject._bind(Sink())
subject.run()
read = tracing.spans_status()["totals"]["connector.read"]
assert read["count"] == 1 and read["rows"] == 2 == len(rows), (read, rows)
assert "jax" not in sys.modules, "a span imported jax"
print("ok")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


# -- export ---------------------------------------------------------------------


def test_dump_trace_carries_the_ring_and_critical_path_takes_the_layer(record):
    from pathway_tpu.engine.engine import Engine, InputQueueSource
    from pathway_tpu.engine.value import ref_scalar
    from pathway_tpu.internals.tracing import (
        critical_path_from_events,
        validate_chrome_trace,
    )

    eng = Engine()
    src = InputQueueSource(eng)
    src.push(16, [(ref_scalar("k", 1), (1,), 1)])
    eng.process_time(16)  # epoch 16 is sampled at the default 1 in 16
    tracing.record("pipeline.device", 1.0, 1.5, seq=1, epoch=16, rows=4)
    with tracing.span("pipeline.prep", seq=1, epoch=16):
        pass
    trace = eng.dump_trace()
    validate_chrome_trace(trace)
    spans = [e for e in trace["traceEvents"] if e.get("cat") == "pipeline"]
    assert {e["name"] for e in spans} == {"pipeline.device", "pipeline.prep"}
    assert all(e["args"]["epoch"] == 16 and e["args"]["seq"] == 1 for e in spans)
    threads = [
        e for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert threads and all(e["tid"] >= 2 for e in threads)
    events = eng.metrics.trace.export_events() + tracing.export_span_events()
    cp = critical_path_from_events(events, epoch=16)
    kinds = {e["name"]: e["kind"] for e in cp["entries"]}
    assert kinds["pipeline.device"] == "pipeline"
    assert "engine.tick" not in kinds  # the tick itself is the total
    eng._gc_unfreeze()
