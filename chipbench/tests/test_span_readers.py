"""The readers of the program's span record: `span_share` on two hand-made
/status snapshots, `idle_by_span` on a hand-made plane set (its arithmetic
can be worked out by eye) and on a capture made here on the CPU."""

import threading

import pytest

from chipbench import spec
from chipbench.readers import idle_by_span, span_share

M = "jit_chipbench_marker(1)"
ENC = "jit__fwd_packed(77)"


def _entry(count=0, total_s=0.0, cpu_s=0.0, self_s=0.0, rows=0, max_s=0.0, open_s=0.0):
    return {"count": count, "total_s": total_s, "cpu_s": cpu_s, "self_s": self_s,
            "rows": rows, "max_s": max_s, "open_s": open_s}


def _status(at, totals, node_total, gc_recent=(), prep_workers=2):
    return {
        "workers": [{"nodes": [
            {"type": "ExternalIndexNode", "total_s": node_total},
            {"type": "RowwiseNode", "total_s": 99.0},
        ]}],
        "device_pipeline": {"prep_workers": prep_workers},
        "spans": {"monotonic_s": at, "totals": totals,
                  "gc_recent": [list(g) for g in gc_recent]},
    }


def _ctx():
    opened = _status(
        100.0,
        {"pipeline.prep": _entry(10, 4.0, 3.0), "prep.tokenize": _entry(10, 3.0),
         "pipeline.starved": _entry(5, 1.0, open_s=1.0),
         "pipeline.launch": _entry(10, 2.0),
         "health.pressure": _entry(2, 0.5), "host.gc": _entry(7, 0.7, max_s=0.6),
         "engine.tick": _entry(40, 2.0)},
        node_total=5.0,
        gc_recent=[(90.0, 0.6, 2), (99.5, 0.01, 1)],
    )
    closed = _status(
        120.0,
        {"pipeline.prep": _entry(30, 28.0, 15.0), "prep.tokenize": _entry(30, 21.0),
         "pipeline.starved": _entry(9, 5.0), "pipeline.prep_wait": _entry(20, 6.0),
         "pipeline.launch": _entry(30, 5.0),
         # one wait closed (1 s) and one 1.5 s old still open at the reading
         "pipeline.window_wait": _entry(3, 1.0, open_s=1.5),
         "pipeline.submit_blocked": _entry(4, 9.0),
         "health.pressure": _entry(3, 10.5), "host.gc": _entry(40, 1.2, max_s=0.6),
         "engine.tick": _entry(90, 16.0, open_s=1.0)},
        node_total=17.0,
        gc_recent=[(90.0, 0.6, 2), (99.5, 0.01, 1), (104.0, 0.02, 1),
                   (111.0, 0.25, 2), (119.0, 0.004, 0)],
    )
    return {"status_open": opened, "status_close": closed, "status_interval_s": 20.0,
            "trace": {"busy_s": 1.0}}


@pytest.mark.parametrize("args,expected", [
    # 4 s of starved + 6 s of prep_wait (absent at the open: counts from 0) in 20 s
    ({"spans": ["pipeline.starved", "pipeline.prep_wait"]}, 50.0),
    ({"spans": ["pipeline.launch"]}, 15.0),
    ({"spans": ["pipeline.window_wait"]}, 5.0),  # closed spans only
    ({"spans": ["pipeline.window_wait"], "field": "elapsed_s"}, 12.5),
    # 1.0 s of the open starved span lay before the first reading
    ({"spans": ["pipeline.starved"], "field": "elapsed_s"}, 15.0),
    # 24 s of prep over 20 s x the pipeline's two prep workers
    ({"spans": ["pipeline.prep"], "threads": "prep_workers"}, 60.0),
    ({"spans": ["pipeline.prep"], "threads": 4}, 30.0),
    # wall 24 s, cpu 12 s: half of prep waited for the interpreter
    ({"spans": ["pipeline.prep"], "field": "wait_s", "over": "pipeline.prep"}, 50.0),
    ({"spans": ["prep.tokenize"], "over": "pipeline.prep"}, 75.0),
    # the node's 12 s, 9 of them blocked in submit
    ({"spans": ["node:ExternalIndexNode"], "minus": ["pipeline.submit_blocked"]}, 15.0),
    ({"spans": ["pipeline.submit_blocked"]}, 45.0),
    ({"spans": ["health.pressure"]}, 50.0),
    ({"spans": ["pipeline.prep"], "field": "count"}, 100.0),
    # the longest collection that ended after the first snapshot: not the
    # 0.6 s one before it, which the cumulative max_s still shows
    ({"spans": ["host.gc"], "field": "recent_max_ms"}, 250.0),
])
def test_span_share_on_two_snapshots(args, expected):
    assert span_share.read(_ctx(), **args) == pytest.approx(expected)


def test_span_share_is_silent_where_there_is_nothing_to_read():
    args = {"spans": ["pipeline.launch"]}
    for cut in ("status_open", "status_close", "trace"):  # --trace 0; --dry
        assert span_share.read(dict(_ctx(), **{cut: None}), **args) is None
    parent = _ctx()
    del parent["status_open"]["spans"], parent["status_close"]["spans"]
    assert span_share.read(parent, **args) is None  # the program before the record
    ctx = _ctx()
    assert span_share.read(ctx, spans=["prep.tokenize"], over="never.seen") is None
    ctx["status_close"]["spans"]["gc_recent"] = [[90.0, 0.6, 2]]
    assert span_share.read(ctx, spans=["host.gc"], field="recent_max_ms") is None


def test_every_new_metric_file_reads_the_snapshots():
    """The metric files of the span readers, as the harness calls them."""
    cell = spec.cell("minilm-l6.ingest-passages")
    mine = [m for m in cell.per_layer if m.reader == "span_share"]
    assert len(mine) == 13
    ctx = _ctx()
    values = {m.name: m.read(ctx) for m in mine}
    assert all(v is not None for v in values.values()), values
    three = (values["pipeline.dispatch_starved_share"]
             + values["pipeline.dispatch_launch_share"]
             + values["pipeline.dispatch_window_wait_share"])
    # by elapsed seconds: starved 4 - 1 of its open span, prep_wait 6,
    # launch 3, window_wait 1 + 1.5 still open: 14.5 of 20 s
    assert three == pytest.approx(72.5)
    assert values["engine.index_node_working_share.ingest"] + values[
        "engine.submit_blocked_share.ingest"] == pytest.approx(60.0)
    assert values["host.gc_pause_max_ms.ingest"] == pytest.approx(250.0)
    assert values["host.gc_share.ingest"] == pytest.approx(2.5)  # 0.5 of 20 s
    assert values["engine.tick_share.ingest"] == pytest.approx(75.0)  # 14 + 1 open
    e5 = {m.name for m in spec.cell("e5-large.ingest-chunks").per_layer}
    assert {m.name for m in mine} <= e5
    # the chip-bound cell has no idle seconds to attribute: not read there
    assert not [n for n in e5 if n.startswith("device.idle_under_")]
    assert len([m for m in cell.per_layer if m.reader == "idle_by_span"]) == 2


# -- idle_by_span ---------------------------------------------------------------


U = 100_000_000  # the hand-made planes' unit: 0.1 s in ns


def _planes(host_shift=0):
    """One chip, window 1..11 units of 0.1 s.  Busy 2-3 (the first encoder
    run), 5-6 (the second) and 9-10.5; idle gaps 1-2, 3-5, 6-9 and 10.5-11:
    6.5 units in all.  The dispatch thread: starved to 1.4, prep_wait to
    1.8, launch 1.8-2.4 with its encode child 1.9-2.1; window_wait 2.4-4.5;
    launch 4.5-5.2 (encode 4.8-5.0); starved 5.2-10.0 with a collection
    7.0-8.0 inside; nothing after 10.0."""
    devices = {"/device:TPU:0": {
        "ops": [["%fusion.1 = f32[8]{0} fusion(x)", 2 * U, U],
                ["%fusion.1 = f32[8]{0} fusion(x)", 5 * U, U],
                ["%copy.2 = f32[8]{0} copy(y)", 9 * U, int(1.5 * U)]],
        "programs": [[M, U - 100, 100], [ENC, 2 * U, U], [ENC, 5 * U, U],
                     [M, 11 * U - 100, 100]],
    }}
    spans = [
        ["pipeline.starved", 0, int(1.4 * U)],
        ["pipeline.prep_wait", int(1.4 * U), int(0.4 * U)],
        ["pipeline.launch", int(1.8 * U), int(0.6 * U)],
        ["launch.encode", int(1.9 * U), int(0.2 * U)],
        ["pipeline.window_wait", int(2.4 * U), int(2.1 * U)],
        ["pipeline.launch", int(4.5 * U), int(0.7 * U)],
        ["launch.encode", int(4.8 * U), int(0.2 * U)],
        ["pipeline.starved", int(5.2 * U), int(4.8 * U)],
        ["host.gc", 7 * U, U],
    ]
    return devices, [[n, s + host_shift, d] for n, s, d in spans]


def test_idle_gaps_go_to_the_innermost_span_of_the_dispatch_thread():
    devices, spans = _planes()
    table = idle_by_span.attribute(devices, spans)
    assert table["idle_s"] == pytest.approx(0.65)
    assert idle_by_span.by_innermost(table["gaps"]) == pytest.approx({
        "pipeline.prep_wait": 0.1,    # 1-2: its middle, 1.5, is in prep_wait
        "pipeline.window_wait": 0.2,  # 3-5: middle 4.0
        "host.gc": 0.3,               # 6-9: middle 7.5, the collection
        "unattributed": 0.05,         # 10.5-11: middle 10.75, no span open
    })
    # encoder runs at 2.0 and 5.0, their launch.encode spans at 1.9 and 4.8
    assert table["clock_ns"] == [U // 10, U // 5]
    # under starved or prep_wait, children included: 0.1 + the collection's 0.3
    assert idle_by_span.share(table, {"pipeline.starved", "pipeline.prep_wait"}) == (
        pytest.approx(100.0 * 0.4 / 0.65))
    assert idle_by_span.share(table, {"pipeline.launch"}) == pytest.approx(0.0)
    assert idle_by_span.share(table, {"pipeline.window_wait"}) == pytest.approx(
        100.0 * 0.2 / 0.65)


def test_launch_self_and_children_are_told_apart():
    devices, spans = _planes()
    # idle only 1.85-1.95 (middle 1.9, the encode child's first instant)
    # and 2.2-2.3 (middle 2.25: in launch, after its child)
    devices["/device:TPU:0"]["ops"] = [
        ["%a.1 = f32[8]{0} a()", U, int(0.85 * U)],
        ["%a.1 = f32[8]{0} a()", int(1.95 * U), int(0.25 * U)],
        ["%a.1 = f32[8]{0} a()", int(2.3 * U), int(8.7 * U)],
    ]
    table = idle_by_span.attribute(devices, spans)
    assert sorted(table["gaps"]) == [
        (pytest.approx(0.01), ("pipeline.launch",)),
        (pytest.approx(0.01), ("pipeline.launch", "launch.encode")),
    ]
    assert idle_by_span.by_innermost(table["gaps"]) == pytest.approx(
        {"pipeline.launch self": 0.01, "launch.encode": 0.01})
    # `spans: [pipeline.launch]` takes its children with it
    assert idle_by_span.share(dict(table, idle_s=0.2, gaps=table["gaps"] + [
        (0.18, ("pipeline.starved",))]), {"pipeline.launch"}) == pytest.approx(10.0)


def test_a_clock_that_is_not_shared_gives_none(capsys):
    # the host plane 0.03 s late: the first encoder run starts before the
    # span that enqueued it
    devices, spans = _planes(host_shift=3 * U // 10)
    table = idle_by_span.attribute(devices, spans)
    assert min(table["clock_ns"]) == -U // 5
    assert idle_by_span.share(table, {"pipeline.starved"}) is None
    assert "clock check failed" in capsys.readouterr().err
    # more encoder runs than launch spans cannot be paired at all
    devices, spans = _planes()
    one_encode = [s for s in spans if s[0] != "launch.encode"] + [spans[3]]
    table = idle_by_span.attribute(devices, one_encode)
    assert table["clock_ns"] is None
    assert idle_by_span.share(table, {"pipeline.starved"}) is None
    assert "could be paired" in capsys.readouterr().err


def test_a_device_plane_stamped_a_little_early_is_read_that_much_later(capsys):
    """What the v5e's captures show: runs stamped up to a millisecond
    before their launch.  The device plane is shifted by the least amount
    that restores the order, and the share must not hang on that shift."""
    early = 500_000  # the first run 0.5 ms before its launch.encode
    devices, spans = _planes(host_shift=U // 10 + early)
    table = idle_by_span.attribute(devices, spans)
    assert min(table["clock_ns"]) == -early and table["shift_ns"] == early
    assert table["idle_s"] == pytest.approx(0.65)
    # the host plane 0.1005 s late: the 1-2 gap's middle is now in starved,
    # 3-5 still in window_wait, 6-9 in starved before the collection
    assert idle_by_span.share(table, {"pipeline.window_wait"}) == pytest.approx(
        100.0 * 0.2 / 0.65)
    idle_by_span.report(table)
    assert "device plane read 500.0 us later" in capsys.readouterr().err
    # a share that reads otherwise on the capture's own clock is not given
    moved = dict(table, raw_gaps=[(0.65, ("pipeline.starved",))])
    assert idle_by_span.share(moved, {"pipeline.window_wait"}) is None
    assert "on the shifted one" in capsys.readouterr().err


def test_the_gates_on_idle_seconds_and_on_coverage(capsys):
    devices, spans = _planes()
    table = idle_by_span.attribute(devices, spans)
    brief = dict(table, idle_s=0.065, gaps=[(s / 10, c) for s, c in table["gaps"]])
    assert idle_by_span.share(brief, {"host.gc"}) is None  # under 0.1 s of gaps
    assert "nothing to attribute" in capsys.readouterr().err
    # without the thread's first spans the 1-2 gap is nobody's: 0.15 of
    # 0.65 s unattributed, 77% covered, under the 90% the gate wants
    table = idle_by_span.attribute(devices, spans[2:])
    assert idle_by_span.share(table, {"host.gc"}) is None
    assert "fall under a span" in capsys.readouterr().err
    idle_by_span.report(idle_by_span.attribute(devices, spans))
    err = capsys.readouterr().err
    assert "clock check: 2 encoder runs" in err and "min 10000.0 us" in err
    assert "host.gc" in err and "pipeline.window_wait" in err and "unattributed" in err


def test_no_capture_no_number():
    class Cell:
        name = "no-such-cell"

    assert idle_by_span.read({"trace": None, "cell": Cell}, ["pipeline.launch"]) is None
    assert idle_by_span.read({"trace": {}, "cell": Cell}, ["pipeline.launch"]) is None


def test_the_dispatch_line_is_found_in_a_capture(tmp_path):
    """A capture made here with the harness's options: the line that holds
    `pipeline.launch` is the dispatch thread's, jax's own events of that
    line are left out, and a capture without launches gives []."""
    import jax
    from jax.profiler import TraceAnnotation

    def capture(into, body):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(into), profiler_options=options)
        try:
            thread = threading.Thread(target=body)
            thread.start()
            thread.join(timeout=60)
            with TraceAnnotation("engine.tick", epoch=2):
                pass  # another thread's line: not the dispatch line
        finally:
            jax.profiler.stop_trace()
        return idle_by_span.capture_path(str(into))

    def dispatching():
        for seq in (1, 2):
            with TraceAnnotation("pipeline.launch", seq=seq):
                with TraceAnnotation("launch.encode", seq=seq):
                    jax.block_until_ready(jax.numpy.zeros(8) + 1)
            with TraceAnnotation("pipeline.starved"):
                pass

    spans = idle_by_span.dispatch_line(capture(tmp_path / "a", dispatching))
    assert [name for name, _, _ in spans] == [
        "pipeline.launch", "launch.encode", "pipeline.starved"] * 2
    starts, chains = idle_by_span.open_spans(spans)
    assert ("pipeline.launch", "launch.encode") in chains and chains[-1] == ()
    assert starts == sorted(starts)
    assert idle_by_span.dispatch_line(capture(tmp_path / "b", lambda: None)) == []
    assert idle_by_span.capture_path(str(tmp_path / "none")) is None
