"""Live device-utilization accounting (internals/costmodel.py,
internals/utilization.py, internals/profiler.py) plus the mesh
straggler detector (internals/mesh_backend.py).

Covers the utilization PR's acceptance contract: the shared FLOPs model
is pinned against its closed form (so bench/roofline/live gauges cannot
silently drift apart), the bound-state classifier is exercised on
synthetic span mixes, the DevicePipeline hook sites feed the rolling
window, /profile captures a readable trace dir and rejects a concurrent
second request with 409, and an injected slow dp replica (faults.py
`slow_replica`) trips the skew gauge and the flight-recorder event."""

from __future__ import annotations

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pathway_tpu.internals import costmodel, faults, profiler, utilization
from pathway_tpu.internals.device_pipeline import DevicePipeline


@pytest.fixture
def fresh_window():
    """Fresh process tracker for the test, restored afterwards."""
    utilization.reset_window()
    try:
        yield utilization.tracker()
    finally:
        utilization.reset_window()


# ---------------------------------------------------------------------------
# cost model — one source of truth, pinned
# ---------------------------------------------------------------------------


def test_encoder_flops_per_token_pinned_to_closed_form():
    """The MiniLM per-token formula, written out long-hand.  If the
    shared model changes shape, every MFU number in the repo changes
    meaning — this pin forces that to be a deliberate edit."""
    h, ffn, layers = 384, 1536, 6
    for seq in (1.0, 17.5, 64.0):
        expected = layers * (2 * (4 * h * h + 2 * h * ffn) + 4 * seq * h)
        assert costmodel.encoder_flops_per_token(seq) == expected
        assert (
            costmodel.encoder_flops_per_token(
                seq, hidden=h, mlp_dim=ffn, layers=layers
            )
            == expected
        )
    # one layer of a tiny config, by hand
    assert costmodel.encoder_flops_per_token(
        8, hidden=4, mlp_dim=16, layers=1
    ) == 2 * (4 * 16 + 2 * 4 * 16) + 4 * 8 * 4
    # a document of t tokens is t tokens at sequence length t; a decoder
    # token is two FLOPs a parameter
    t = 23.7
    assert costmodel.encoder_flops_per_doc(t) == (
        t * costmodel.encoder_flops_per_token(t)
    )
    assert costmodel.decoder_flops_per_token(22_700_000) == 2.0 * 22_700_000


def test_batch_useful_flops_uses_average_real_seq():
    # 100 real tokens over 4 rows -> attention charged at seq=25
    got = costmodel.encoder_useful_flops(100, 4)
    assert got == 100 * costmodel.encoder_flops_per_token(25.0)
    assert costmodel.encoder_useful_flops(0, 4) == 0.0


def test_peaks_resolve_by_device_kind():
    # the CPU backend has no peak: 0.0 -> MFU None, tier-1 unchanged
    assert costmodel.device_kind() == "cpu"
    assert costmodel.device_peak_flops() == 0.0
    assert costmodel.device_peak_flops("cpu") == 0.0
    assert not costmodel.device_capacity_known()
    assert costmodel.mfu_pct(1e12, peak=0.0) is None
    assert costmodel.mfu_pct(197e12 / 2, peak=197e12) == pytest.approx(50.0)
    # the local backend's device_kind strings, exactly
    assert costmodel.device_peak_flops("TPU v5 lite") == 197e12
    assert costmodel.device_hbm_bytes_per_sec("TPU v5 lite") == 819e9
    assert costmodel.device_hbm_bytes("TPU v5 lite") == 16e9
    assert costmodel.device_hbm_bytes_per_sec("TPU v5") == 2765e9
    assert costmodel.device_capacity_known("TPU v5 lite")
    assert set(costmodel.DEVICE_PEAK_BF16_FLOPS) == set(
        costmodel.DEVICE_HBM_BYTES_PER_SEC
    ) == set(costmodel.DEVICE_HBM_BYTES)


def test_unknown_accelerator_is_an_error_on_the_measuring_path(monkeypatch):
    # str(jax.devices()[0]) — the id form — is not a device_kind
    for name in ("TPU_0(process=0,(0,0,0,0))", "TPU v5 lite0", "NVIDIA H100"):
        with pytest.raises(costmodel.UnknownDeviceError, match="device_kind"):
            costmodel.device_peak_flops(name)
        assert not costmodel.device_capacity_known(name)
    # an attached accelerator the table does not list fails every consumer
    # that would print MFU or efficiency, instead of turning them into None
    monkeypatch.setattr(costmodel, "_cached_kind", "TPU v9 hypothetical")
    with pytest.raises(costmodel.UnknownDeviceError):
        costmodel.mfu_pct(1e12)
    with pytest.raises(costmodel.UnknownDeviceError):
        utilization.tracker().snapshot()
    with pytest.raises(costmodel.UnknownDeviceError):
        costmodel.device_hbm_bytes()


# ---------------------------------------------------------------------------
# bound-state classification on synthetic span mixes
# ---------------------------------------------------------------------------


def test_classify_bound_state_rules():
    W = 10.0
    # no dispatches -> idle regardless of spans
    assert utilization.classify_bound_state(W, 9, 9, 9, 0) == "idle"
    assert utilization.classify_bound_state(0.0, 0, 0, 0, 5) == "idle"
    # dispatcher blocked on the in-flight window -> device saturated
    assert (
        utilization.classify_bound_state(W, 1.0, 0.5, 3.0, 5)
        == "compute-bound"
    )
    # wait takes precedence over dispatch when both exceed their share
    assert (
        utilization.classify_bound_state(W, 0.0, 4.0, 4.0, 5)
        == "compute-bound"
    )
    # synchronous enqueue dominates
    assert (
        utilization.classify_bound_state(W, 1.0, 3.0, 0.5, 5)
        == "dispatch-bound"
    )
    # neither -> the device starves behind host prep (the bench r04
    # regime)
    assert (
        utilization.classify_bound_state(W, 6.0, 1.0, 1.0, 5)
        == "host-bound"
    )
    # thresholds are inclusive at exactly 25%
    assert (
        utilization.classify_bound_state(W, 0, 0, W * 0.25, 1)
        == "compute-bound"
    )
    assert (
        utilization.classify_bound_state(W, 0, W * 0.25, 0, 1)
        == "dispatch-bound"
    )


# ---------------------------------------------------------------------------
# rolling-window tracker
# ---------------------------------------------------------------------------


def test_tracker_snapshot_accounting(fresh_window, monkeypatch):
    tr = fresh_window
    tr.note_batch(rows=8, real_tokens=200, slab_tokens=512, useful_flops=1e9)
    tr.note_batch(rows=8, real_tokens=300, slab_tokens=512, useful_flops=3e9)
    tr.note_span("dispatch", 0.004)
    tr.note_span("wait", 0.001)
    snap = tr.snapshot()
    assert snap["dispatches"] == 2
    assert snap["rows"] == 16
    assert snap["real_tokens"] == 500
    assert snap["slab_tokens"] == 1024
    assert snap["pad_waste_ratio"] == pytest.approx(1 - 500 / 1024)
    assert snap["span_seconds"]["dispatch"] == pytest.approx(0.004)
    # internal consistency: tokens/s and TFLOP/s share one denominator
    # (the reported window_s is rounded, so compare ratios — the window
    # cancels out)
    assert snap["tokens_per_sec"] > 0
    assert snap["useful_tflops_per_sec"] * 1e12 / snap[
        "tokens_per_sec"
    ] == pytest.approx(4e9 / 500)
    assert snap["docs_per_sec"] / snap["tokens_per_sec"] == pytest.approx(
        16 / 500
    )
    # CPU CI: unknown device peak -> MFU must be None, never a division
    monkeypatch.setattr(costmodel, "device_peak_flops", lambda name=None: 0.0)
    assert tr.snapshot()["mfu_pct"] is None
    # known peak -> the gauge's number follows the cost model exactly
    monkeypatch.setattr(
        costmodel, "device_peak_flops", lambda name=None: 197e12
    )
    snap = tr.snapshot()
    assert snap["mfu_pct"] == pytest.approx(
        100.0 * snap["useful_tflops_per_sec"] * 1e12 / 197e12
    )
    assert snap["device_peak_tflops_bf16"] == 197.0


def test_tracker_window_expires_old_batches(fresh_window):
    tr = utilization.UtilizationTracker(window_s=0.05)
    tr.note_batch(4, 10, 16, 1e6)
    assert tr.snapshot()["dispatches"] == 1
    time.sleep(0.08)
    snap = tr.snapshot()
    assert snap["dispatches"] == 0
    assert snap["bound_state"] == "idle"
    assert snap["mfu_pct"] is None


def test_empty_window_reports_idle_not_nan(fresh_window):
    snap = fresh_window.snapshot()
    assert snap["bound_state"] == "idle"
    assert snap["dispatches"] == 0
    assert snap["tokens_per_sec"] == 0.0
    assert snap["pad_waste_ratio"] is None
    assert snap["mfu_pct"] is None


# ---------------------------------------------------------------------------
# DevicePipeline hook sites feed the window
# ---------------------------------------------------------------------------


def _run_fake_pipeline(batches: int = 4) -> None:
    """Drive a DevicePipeline with host-only prepare/dispatch/wait; meta
    carries the same keys ops/knn.py produces."""

    def prepare(item):
        rows = 8
        real = 8 * 20
        slab = 8 * 32
        return item, {
            "rows": rows,
            "real_tokens": real,
            "slab_tokens": slab,
            "useful_flops": costmodel.encoder_useful_flops(real, rows),
        }

    pipe = DevicePipeline(
        prepare,
        dispatch=lambda payload: payload,
        wait=lambda handle: time.sleep(0.001),
        name="util-test",
        max_in_flight=2,
    )
    try:
        for i in range(batches):
            pipe.submit(i)
        pipe.drain()
    finally:
        pipe.close()


def test_pipeline_feeds_utilization_window(fresh_window):
    _run_fake_pipeline()
    snap = utilization.tracker().snapshot()
    assert snap["dispatches"] == 4
    assert snap["rows"] == 32
    assert snap["real_tokens"] == 4 * 160
    assert snap["slab_tokens"] == 4 * 256
    assert snap["useful_tflops_per_sec"] > 0
    assert snap["bound_state"] != "idle"
    spans = snap["span_seconds"]
    assert spans["prep"] > 0 and spans["dispatch"] >= 0
    assert spans["wait"] > 0 or spans["drain"] > 0  # waits hit somewhere
    assert spans["device"] > 0  # completion-to-completion estimate


def test_utilization_gauges_render(fresh_window):
    from pathway_tpu.internals.metrics import render_registries

    _run_fake_pipeline(batches=2)
    text = render_registries([utilization.utilization_metrics()])
    assert "pathway_device_tokens_per_sec" in text
    # one-hot state set: exactly one of the four states at 1.0
    states = [
        line
        for line in text.splitlines()
        if line.startswith("pathway_device_bound_state{")
    ]
    assert len(states) == len(utilization.BOUND_STATES)
    assert sum(float(line.rsplit(" ", 1)[1]) for line in states) == 1.0
    # CPU CI: no peak -> mfu series absent rather than 0/NaN
    assert (
        "pathway_device_mfu_pct{" not in text
        or costmodel.device_peak_flops() > 0
    )


def test_disabled_guard_is_inert(fresh_window, monkeypatch):
    """PATHWAY_DEVICE_UTIL=0 semantics: hook sites see ENABLED False and
    the tracker window stays empty through real pipeline activity."""
    monkeypatch.setattr(utilization, "ENABLED", False)
    _run_fake_pipeline()
    snap = utilization.tracker().snapshot()
    assert snap["dispatches"] == 0
    assert all(v == 0 for v in snap["span_seconds"].values())
    from pathway_tpu.internals.metrics import render_registries

    # HELP/TYPE headers remain but no sample series are emitted
    text = render_registries([utilization.utilization_metrics()])
    assert "pathway_device_bound_state{" not in text
    assert utilization.utilization_status()["enabled"] is False


def test_status_payload_shape(fresh_window):
    status = utilization.utilization_status()
    assert status["enabled"] is True
    assert status["bound_state"] == "idle"
    assert status["profiler"] == profiler.profiler_status()
    json.dumps(status)  # must be JSON-serializable for /status


# ---------------------------------------------------------------------------
# per-replica pipeline gauges (satellite: replica labels)
# ---------------------------------------------------------------------------


def test_per_replica_pad_waste_and_occupancy_labels():
    from pathway_tpu.internals.device_pipeline import pipeline_metrics
    from pathway_tpu.internals.metrics import render_registries

    def prepare(item):
        return item, {
            "rows": 4,
            "real_tokens": 40,
            "slab_tokens": 128,
            "replica_rows": [3, 1],
            "replica_real_tokens": [30, 10],
            "replica_slab_tokens": [64, 64],
        }

    pipe = DevicePipeline(
        prepare,
        dispatch=lambda payload: payload,
        wait=lambda handle: None,
        name="replica-test",
        replicas=2,
    )
    try:
        for i in range(3):
            pipe.submit(i)
        pipe.drain()
        tokens = pipe.replica_tokens()
        assert tokens == [(90, 192), (30, 192)]
        stats = pipe.replica_stats()
        assert stats[0]["rows"] == 9 and stats[1]["rows"] == 3
        assert stats[0]["pad_waste_ratio"] == pytest.approx(1 - 90 / 192)
        text = render_registries([pipeline_metrics()])
        assert 'pathway_device_pad_waste_ratio{worker="0",replica="0"}' in text
        assert 'pathway_device_pad_waste_ratio{worker="0",replica="1"}' in text
        assert (
            'pathway_device_pipeline_occupancy{worker="0",replica="1"}' in text
        )
        assert (
            'pathway_device_pipeline_in_flight{worker="0",replica="0"}' in text
        )
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# mesh straggler detection (8 emulated devices, injected slow replica)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _mesh(spec: str):
    import jax

    from pathway_tpu.analysis.mesh import MeshSpec
    from pathway_tpu.internals import mesh_backend

    need = MeshSpec.parse(spec).devices()
    if len(jax.devices()) < need:
        pytest.skip(f"needs {need} devices (conftest emulates 8)")
    backend = mesh_backend.activate(MeshSpec.parse(spec))
    try:
        yield backend
    finally:
        mesh_backend.deactivate()


def test_straggler_detection_via_injected_slow_replica():
    from pathway_tpu.internals import mesh_backend

    with _mesh("dp=4,tp=2") as backend:
        assert backend is not None
        faults.install("slow_replica@replica=2,factor=8")
        try:
            for _ in range(mesh_backend.SKEW_PATIENCE + 2):
                backend.note_dispatch_device_time(
                    0.01, replica_rows=[4, 4, 4, 4]
                )
            ratio = backend._skew_ratio_or_none()
            assert ratio is not None
            assert ratio >= mesh_backend.SKEW_THRESHOLD
            straggler = backend.straggler()
            assert straggler is not None
            assert straggler["replica"] == 2
            assert straggler["skew_ratio"] == pytest.approx(ratio, rel=0.01)
            kinds = [e["kind"] for e in backend.recorder.tail()]
            assert "replica_straggler" in kinds
            # exactly one flight event per episode, not one per dispatch
            assert kinds.count("replica_straggler") == 1
            assert any(k == "slow_replica" for k, _, _ in faults.events)
            status = backend.status()
            assert status["straggler"]["replica"] == 2
            assert status["skew_ratio"] >= mesh_backend.SKEW_THRESHOLD
        finally:
            faults.clear()


def test_balanced_replicas_do_not_trip_straggler():
    from pathway_tpu.internals import mesh_backend

    with _mesh("dp=4,tp=2") as backend:
        assert backend is not None
        for _ in range(mesh_backend.SKEW_PATIENCE + 2):
            backend.note_dispatch_device_time(0.01, replica_rows=[4, 4, 4, 4])
        ratio = backend._skew_ratio_or_none()
        assert ratio == pytest.approx(1.0)
        assert backend.straggler() is None
        kinds = [e["kind"] for e in backend.recorder.tail()]
        assert "replica_straggler" not in kinds


def test_skew_charges_work_share_not_wall_time():
    """One SPMD dispatch shares wall time; replicas are charged by row
    share, so a persistent row imbalance alone reads as skew."""
    from pathway_tpu.internals import mesh_backend

    with _mesh("dp=4,tp=2") as backend:
        assert backend is not None
        for _ in range(mesh_backend.SKEW_PATIENCE + 2):
            backend.note_dispatch_device_time(
                0.01, replica_rows=[13, 1, 1, 1]
            )
        # replica 0 holds 13/16 of the rows -> charged 13/16*4 = 3.25x
        assert backend._skew_ratio_or_none() == pytest.approx(3.25)
        straggler = backend.straggler()
        assert straggler is not None and straggler["replica"] == 0


# ---------------------------------------------------------------------------
# on-demand profiler capture (/profile route + busy guard)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def profile_server(monkeypatch):
    from pathway_tpu.internals.monitoring import PrometheusServer

    # /profile never touches the engine; keep the fixture light and keep
    # the periodic device-probe subprocess out of the test
    monkeypatch.setenv("PATHWAY_DEVICE_PROBE", "0")
    server = PrometheusServer(object(), port=_free_port())
    server.start()
    try:
        yield f"http://127.0.0.1:{server.port}"
    finally:
        server.stop()


def _get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def test_profile_endpoint_returns_readable_trace_dir(
    profile_server, tmp_path
):
    out = tmp_path / "trace"
    code, result = _get_json(
        f"{profile_server}/profile?seconds=0.2&dir={out}"
    )
    assert code == 200, result
    assert "error" not in result, result
    assert result["trace_dir"] == str(out)
    assert out.is_dir()
    assert result["files"] >= 1  # jax wrote an XPlane/TensorBoard layout
    assert result["seconds"] == pytest.approx(0.2)
    # capture state is visible afterwards through the status surface
    last = profiler.last_capture()
    assert last is not None and last["trace_dir"] == str(out)
    assert profiler.capture_active() is False


def test_profile_endpoint_rejects_concurrent_capture(
    profile_server, tmp_path
):
    errors: list = []

    def long_capture():
        try:
            _get_json(
                f"{profile_server}/profile?seconds=1.5"
                f"&dir={tmp_path / 'first'}"
            )
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    t = threading.Thread(target=long_capture)
    t.start()
    try:
        deadline = time.monotonic() + 5
        while not profiler.capture_active():
            assert time.monotonic() < deadline, "first capture never started"
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"{profile_server}/profile?seconds=0.1", timeout=10
            )
        assert exc_info.value.code == 409
        body = json.loads(exc_info.value.read().decode())
        assert "error" in body
    finally:
        t.join(timeout=30)
    assert not errors, errors
    assert not t.is_alive()


def test_profile_endpoint_validates_seconds(profile_server):
    for bad in ("abc", "-1", "0"):
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"{profile_server}/profile?seconds={bad}", timeout=10
            )
        assert exc_info.value.code == 400


def test_capture_seconds_clamped_to_bounds(monkeypatch, tmp_path):
    recorded = {}

    class _FakeProfiler:
        @staticmethod
        def start_trace(d):
            recorded["dir"] = d

        @staticmethod
        def stop_trace():
            pass

    import jax

    monkeypatch.setattr(jax, "profiler", _FakeProfiler)
    # lower clamp is observable cheaply (the upper one would sleep 120s)
    result = profiler.capture(0.001, str(tmp_path / "t"))
    assert result["seconds"] == pytest.approx(0.05)
    assert recorded["dir"] == str(tmp_path / "t")
    # upper bound: pin the constant the route advertises as its cap
    assert profiler.MAX_SECONDS == 120.0
    assert max(0.05, min(10_000.0, profiler.MAX_SECONDS)) == 120.0


def test_capture_reports_error_without_crashing(monkeypatch, tmp_path):
    class _Boom:
        @staticmethod
        def start_trace(d):
            raise RuntimeError("no backend")

    import jax

    monkeypatch.setattr(jax, "profiler", _Boom)
    result = profiler.capture(0.05, str(tmp_path / "t"))
    assert "error" in result and "no backend" in result["error"]
    assert profiler.capture_active() is False
