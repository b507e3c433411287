"""End-to-end epoch tracing: span capture, Chrome trace export and
schema, cross-worker exchange stamps (thread and TCP meshes), critical
path, sink freshness, slow-tick sampler, and the device monitor
(internals/tracing.py, internals/device_probe.py)."""

from __future__ import annotations

import json
import time as time_mod

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.config import pathway_config
from pathway_tpu.internals.runner import last_engine, run_tables
from pathway_tpu.internals.tracing import (
    TraceStore,
    build_chrome_trace,
    critical_path_from_events,
    merge_flight_tails,
    validate_chrome_trace,
)

from test_multiprocess import run_workers


@pytest.fixture
def threads2():
    old = pathway_config.threads
    pathway_config.threads = 2
    try:
        yield
    finally:
        pathway_config.threads = old


# ---------------------------------------------------------------------------
# TraceStore unit behaviour
# ---------------------------------------------------------------------------


def test_sampling_rules(monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    tr = TraceStore(0)  # default: on, every 16th epoch
    assert tr.enabled and tr.sample_every == 16
    assert tr.should_sample(0) and tr.should_sample(32)
    assert not tr.should_sample(2)

    monkeypatch.setenv("PATHWAY_TRACE", "1")
    assert TraceStore(0).sample_every == 1

    monkeypatch.setenv("PATHWAY_TRACE", "0")
    tr_off = TraceStore(0)
    assert not tr_off.enabled and not tr_off.should_sample(0)

    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "4")
    assert TraceStore(0).sample_every == 4


def test_ring_capacity_bounds_epochs():
    tr = TraceStore(0, sample_every=1, capacity=3)
    for t in range(0, 20, 2):
        tr.begin_epoch(t, float(t))
        tr.end_epoch(float(t), float(t) + 0.5)
    assert len(tr.epochs) == 3
    assert [ep.epoch for ep in tr.epochs] == [14, 16, 18]


def test_export_event_shapes():
    tr = TraceStore(worker_id=3, sample_every=1)
    ep = tr.begin_epoch(2, 10.0)
    ep.spans.append((0, "rowwise", 10.0, 0.25, 42))
    tr.note_edge(2, 7, 1, 100.0, 100.5)
    tr.end_epoch(10.5, 10.75)
    kinds = {e[0] for e in tr.export_events()}
    assert kinds == {"tick", "span", "wm", "edge"}
    (edge,) = [e for e in tr.export_events() if e[0] == "edge"]
    assert edge == ("edge", 3, 1, 2, 7, 100.0, 100.5)


# ---------------------------------------------------------------------------
# engine integration: spans captured during a run
# ---------------------------------------------------------------------------


def _small_graph():
    t = pw.debug.table_from_markdown(
        """
        k | v
        a | 1
        a | 2
        b | 5
        """
    )
    return t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))


def test_traced_run_captures_spans(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "1")
    (cap,) = run_tables(_small_graph())
    tr = cap.engine.metrics.trace
    assert tr.epochs, "no epochs sampled with PATHWAY_TRACE=1"
    ep = tr.epochs[-1]
    assert ep.spans, "no node spans recorded"
    assert ep.wm is not None and ep.wm[1] >= 0
    cp = tr.critical_path()
    assert cp is not None and cp["entries"]
    assert all(
        {"kind", "worker", "name", "duration_ms", "share_pct"} <= set(e)
        for e in cp["entries"]
    )
    assert len(cp["entries"]) <= 5


def test_trace_off_records_nothing(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "0")
    (cap,) = run_tables(_small_graph())
    tr = cap.engine.metrics.trace
    assert not tr.epochs and tr.current is None


def test_dump_trace_single_worker(monkeypatch, tmp_path):
    monkeypatch.setenv("PATHWAY_TRACE", "1")
    (cap,) = run_tables(_small_graph())
    out = tmp_path / "trace.json"
    trace = cap.engine.dump_trace(str(out))
    validate_chrome_trace(trace)
    assert out.exists()
    disk = json.loads(out.read_text())
    assert disk["traceEvents"]
    names = {e["name"] for e in trace["traceEvents"]}
    assert "reduce" in names, names


# ---------------------------------------------------------------------------
# two thread workers: both pids + cross-worker flow edges
# ---------------------------------------------------------------------------


def test_dump_trace_two_thread_wordcount(monkeypatch, threads2, tmp_path):
    monkeypatch.setenv("PATHWAY_TRACE", "1")
    t = pw.debug.table_from_markdown(
        """
        word
        the
        quick
        the
        fox
        quick
        the
        """
    )
    counts = t.groupby(pw.this.word).reduce(
        pw.this.word, n=pw.reducers.count()
    )
    pw.io.fs.write(counts, str(tmp_path / "out.jsonl"), format="json")
    pw.run(monitoring_level=None)
    trace = last_engine().dump_trace(str(tmp_path / "trace.json"))
    validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    span_pids = {e["pid"] for e in evs if e.get("cat") == "node"}
    assert span_pids == {0, 1}, f"spans missing a worker: {span_pids}"
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert flows, "no cross-worker exchange edges"
    starts = {e["id"] for e in evs if e["ph"] == "s"}
    finishes = {e["id"] for e in evs if e["ph"] == "f"}
    assert starts == finishes, "unpaired flow events"
    # transit must be non-negative: the finish never precedes its start
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], {})[e["ph"]] = e["ts"]
    for fid, pair in by_id.items():
        assert pair["f"] >= pair["s"], f"flow {fid} goes backwards"


# ---------------------------------------------------------------------------
# two processes over TCP: dump_trace as an SPMD collective
# ---------------------------------------------------------------------------

TRACE_TCP_SCRIPT = """
    import os
    os.environ["PATHWAY_TRACE"] = "1"
    import json
    import sys
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_markdown
    from pathway_tpu.internals.runner import last_engine

    out_dir = sys.argv[1]
    t = table_from_markdown(
        '''
        word
        the
        quick
        the
        fox
        quick
        the
        '''
    )
    counts = t.groupby(pw.this.word).reduce(
        pw.this.word, n=pw.reducers.count()
    )
    pw.io.fs.write(counts, out_dir + "/out.jsonl", format="json")
    pw.run(monitoring_level=None)
    # SPMD collective: every worker calls dump_trace at the same point
    trace = last_engine().dump_trace()
    if int(os.environ["PATHWAY_PROCESS_ID"]) == 0:
        with open(out_dir + "/trace.json", "w") as f:
            json.dump(trace, f)
"""


def test_dump_trace_tcp_two_process(tmp_path):
    run_workers(TRACE_TCP_SCRIPT, 2, tmp_path)
    trace = json.loads((tmp_path / "trace.json").read_text())
    validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    span_pids = {e["pid"] for e in evs if e.get("cat") == "node"}
    assert span_pids == {0, 1}, f"spans missing a worker: {span_pids}"
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert flows, "no cross-worker edges across the TCP mesh"


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------


def test_critical_path_from_synthetic_events():
    events = [
        ("tick", 0, 4, 100.0, 0.010),
        ("tick", 1, 4, 100.0, 0.002),
        ("span", 0, 4, 2, "join", 100.0, 0.008, 500),
        ("span", 1, 4, 2, "join", 100.0, 0.001, 20),
        ("wm", 0, 4, 100.008, 0.001),
        ("edge", 1, 0, 4, 3, 100.0, 100.004),
        # an older epoch that must not leak into the default (latest)
        ("tick", 0, 2, 90.0, 0.5),
        ("span", 0, 2, 1, "old", 90.0, 0.5, 1),
    ]
    cp = critical_path_from_events(events)
    assert cp["epoch"] == 4
    assert cp["entries"][0]["name"] == "join"
    assert cp["entries"][0]["duration_ms"] == pytest.approx(8.0)
    kinds = {e["kind"] for e in cp["entries"]}
    assert kinds == {"node", "watermark", "exchange"}
    for e in cp["entries"]:
        assert 0 <= e["share_pct"] <= 100
    assert critical_path_from_events(events, epoch=2)["entries"][0][
        "name"
    ] == "old"
    assert critical_path_from_events([]) is None


# ---------------------------------------------------------------------------
# Chrome trace schema checker
# ---------------------------------------------------------------------------


def test_validate_chrome_trace_rejects_garbage():
    with pytest.raises(ValueError):
        validate_chrome_trace([])
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "Z", "pid": 0}]})
    with pytest.raises(ValueError):  # X without dur
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "pid": 0, "ts": 1, "name": "x"}]}
        )
    with pytest.raises(ValueError):  # flow event without id
        validate_chrome_trace(
            {"traceEvents": [{"ph": "s", "pid": 0, "ts": 1, "name": "x"}]}
        )
    with pytest.raises(ValueError):  # non-serializable args
        validate_chrome_trace(
            {
                "traceEvents": [
                    {
                        "ph": "i",
                        "pid": 0,
                        "ts": 1,
                        "name": "x",
                        "args": {"bad": object()},
                    }
                ]
            }
        )


def test_build_chrome_trace_metadata_and_flows():
    events = [
        ("tick", 0, 2, 100.0, 0.01),
        ("edge", 1, 0, 2, 5, 100.0, 100.002),
    ]
    trace = build_chrome_trace(events)
    validate_chrome_trace(trace)
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert {e["pid"] for e in meta} == {0, 1}


# ---------------------------------------------------------------------------
# sink freshness (streaming only: ingest stamps come from the driver)
# ---------------------------------------------------------------------------


def test_sink_freshness_streaming():
    class Subject(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(5):
                self.next(value=i)
                self.commit()

    class InSchema(pw.Schema):
        value: int

    t = pw.io.python.read(Subject(), schema=InSchema, name="fresh_src")
    doubled = t.select(d=pw.this.value * 2)
    seen = []
    pw.io.subscribe(
        doubled,
        on_change=lambda key, row, time, is_addition: seen.append(row["d"]),
        name="fresh_sink",
    )
    pw.run(monitoring_level=None, autocommit_duration_ms=20)
    assert sorted(seen) == [0, 2, 4, 6, 8]
    m = last_engine().metrics
    stats = m.sink_freshness_stats()
    assert stats, "no freshness recorded for a streaming run"
    (s,) = [x for x in stats if x["sink"] == "fresh_sink"]
    assert s["count"] >= 1
    assert s["p50_ms"] is not None and s["p50_ms"] >= 0
    assert s["p99_ms"] >= s["p50_ms"] - 1e-9
    assert s["last_ms"] is not None and s["last_ms"] >= 0


def test_static_run_has_no_freshness():
    (cap,) = run_tables(_small_graph())
    assert cap.engine.metrics.sink_freshness_stats() == []


# ---------------------------------------------------------------------------
# slow-tick stack sampler
# ---------------------------------------------------------------------------


def test_slow_tick_watchdog_captures_stacks():
    from pathway_tpu.internals.metrics import FlightRecorder
    from pathway_tpu.internals.tracing import SlowTickWatchdog

    class _Eng:  # SimpleNamespace is not weakref-able
        current_node = None

    rec = FlightRecorder(capacity=16, worker=0)
    eng = _Eng()
    wd = SlowTickWatchdog(eng, rec, threshold_ms=10)
    try:
        wd.begin(2)
        deadline = time_mod.monotonic() + 2.0
        while time_mod.monotonic() < deadline:
            if any(e[2] == "slow_tick" for e in rec.events):
                break
            time_mod.sleep(0.005)
        wd.end()
        slow = [e for e in rec.tail() if e["kind"] == "slow_tick"]
        assert slow, "watchdog never fired on a 10ms threshold"
        assert slow[0]["time"] == 2
        assert slow[0]["duration_s"] >= 0.01
        # stacks from other threads, never its own sampler thread
        assert "pw-slow-tick" not in slow[0]["name"]
        # one capture per offending tick, even though it kept polling
        assert len(slow) == 1
    finally:
        wd.stop()


def test_engine_arms_watchdog_from_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_SLOW_TICK_MS", "250")
    (cap,) = run_tables(_small_graph())
    m = cap.engine.metrics
    assert m.slow_watch is not None
    assert m.slow_watch.threshold_s == pytest.approx(0.25)
    monkeypatch.delenv("PATHWAY_SLOW_TICK_MS")
    (cap2,) = run_tables(_small_graph())
    assert cap2.engine.metrics.slow_watch is None


# ---------------------------------------------------------------------------
# exchange stamp wire codec
# ---------------------------------------------------------------------------


def test_stamp_codec_round_trip():
    from pathway_tpu.engine.wire import (
        MSG_STAMP,
        decode_message,
        encode_message,
    )

    msg = ("stamp", 7, 42, 3, 1722945600.123456)
    blob = encode_message(msg)
    assert blob[0] == MSG_STAMP
    kind, channel, t, origin, wall = decode_message(blob)
    assert (kind, channel, t, origin) == ("stamp", 7, 42, 3)
    assert wall == pytest.approx(1722945600.123456, abs=1e-6)


def test_stamp_frame_is_length_prefixed():
    import struct

    from pathway_tpu.engine.wire import decode_message, encode_frame

    frame = encode_frame(("stamp", 1, 2, 0, 123.5))
    (length,) = struct.unpack("!I", frame[:4])
    assert length == len(frame) - 4
    msg = decode_message(frame[4:])
    assert msg[:4] == ("stamp", 1, 2, 0)
    assert msg[4] == pytest.approx(123.5)


# ---------------------------------------------------------------------------
# flight-recorder causal merge
# ---------------------------------------------------------------------------


def test_merge_flight_tails_causal_order():
    w0 = [
        {"time": 2, "seq": 1, "worker": 0, "kind": "node"},
        {"time": 4, "seq": 2, "worker": 0, "kind": "node"},
    ]
    w1 = [
        {"time": 2, "seq": 1, "worker": 1, "kind": "node"},
        {"time": 2, "seq": 2, "worker": 1, "kind": "node"},
        {"time": 4, "seq": 3, "worker": 1, "kind": "node"},
    ]
    merged = merge_flight_tails([w1, w0])
    assert [(e["time"], e["seq"], e["worker"]) for e in merged] == [
        (2, 1, 0),
        (2, 1, 1),
        (2, 2, 1),
        (4, 2, 0),
        (4, 3, 1),
    ]


def test_flight_recorder_entries_carry_seq_and_worker():
    (cap,) = run_tables(_small_graph())
    tail = cap.engine.metrics.recorder.tail()
    assert tail
    seqs = [e["seq"] for e in tail]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(e["worker"] == 0 for e in tail)


# ---------------------------------------------------------------------------
# device monitor (injected probe — no subprocess in tests)
# ---------------------------------------------------------------------------


def test_device_monitor_healthy_and_down():
    from pathway_tpu.internals.device_probe import DeviceMonitor
    from pathway_tpu.internals.metrics import render_registries

    from test_observability import check_exposition

    mon = DeviceMonitor(
        interval_s=3600, probe=lambda timeout_s: (1.5, None)
    )
    mon.probe_once()
    assert mon.last["healthy"] and mon.last["rtt_ms"] == 1.5
    text = render_registries([mon.metrics])
    samples = check_exposition(text)
    assert samples["pathway_device_rtt_ms"][0][1] == 1.5
    assert samples["pathway_device_healthy"][0][1] == 1.0

    mon.probe = lambda timeout_s: (None, "device lost")
    mon.probe_once()
    assert not mon.last["healthy"] and mon.last["error"] == "device lost"
    samples = check_exposition(render_registries([mon.metrics]))
    assert samples["pathway_device_healthy"][0][1] == 0.0
    # rtt gauge goes absent rather than lying with a stale number
    assert "pathway_device_rtt_ms" not in samples


def test_device_status_disabled_in_tests():
    from pathway_tpu.internals.device_probe import device_status

    # conftest pins PATHWAY_DEVICE_PROBE=0 for hermeticity
    assert device_status() == {"status": "disabled"}


def test_cli_trace_subcommand(tmp_path, monkeypatch):
    # the tool sets these itself; monkeypatch restores them after
    monkeypatch.setenv("PATHWAY_TRACE", "1")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1")
    script = tmp_path / "wc.py"
    script.write_text(
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_markdown('''\n"
        "word\n"
        "the\n"
        "quick\n"
        "the\n"
        "''')\n"
        "c = t.groupby(pw.this.word).reduce(\n"
        "    pw.this.word, n=pw.reducers.count())\n"
        f"pw.io.fs.write(c, r'{tmp_path / 'out.jsonl'}', format='json')\n"
        "pw.run(monitoring_level=None)\n"
    )
    out = tmp_path / "trace.json"
    from pathway_tpu.cli import main

    rc = main(
        ["trace", str(script), "--out", str(out), "--duration", "30"]
    )
    assert rc == 0
    trace = json.loads(out.read_text())
    validate_chrome_trace(trace)
    assert any(e["ph"] == "X" for e in trace["traceEvents"])


def test_cli_trace_rejects_runless_script(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "1")
    script = tmp_path / "norun.py"
    script.write_text("x = 1\n")
    from pathway_tpu.cli import main

    rc = main(["trace", str(script), "--out", str(tmp_path / "t.json")])
    assert rc == 2


def test_cli_status_subcommand(capsys):
    import socket

    from pathway_tpu.cli import main
    from pathway_tpu.internals.monitoring import PrometheusServer

    (cap,) = run_tables(_small_graph())
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    server = PrometheusServer(cap.engine, port=port)
    server.start()
    try:
        url = f"http://127.0.0.1:{port}/status"
        assert main(["status", "--url", url]) == 0
        text = capsys.readouterr().out
        assert "workers: 1" in text and "worker 0:" in text
        assert main(["status", "--url", url, "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["worker_count"] == 1
    finally:
        server.stop()
    # connection refused -> clean error, not a traceback
    assert main(["status", "--url", f"http://127.0.0.1:{port}/status"]) == 1


def test_status_json_has_tracing_surfaces(monkeypatch):
    from pathway_tpu.internals.monitoring import PrometheusServer

    monkeypatch.setenv("PATHWAY_TRACE", "1")
    (cap,) = run_tables(_small_graph())
    status = PrometheusServer(cap.engine).status_json()
    assert "sinks" in status and "device" in status
    assert status["device"]["status"] == "disabled"
    cp = status["critical_path"]
    assert cp is not None and cp["entries"], "critical path missing"
