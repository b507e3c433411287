"""Accelerator health probe, a standing part of monitoring.

A local chip belongs to ONE process, so the probe runs inside the process
that owns it: a trivial jit dispatch per local device on a watchdog
thread, waited on with a deadline.  The dispatch queues behind whatever
the chip is executing, so a busy chip answers late, not never — only a
dispatch that outlives the deadline reads as a dead device, and a probe
still outstanding is waited on again rather than piled on.  A process
that never imported jax has no device to lose and reports healthy without
importing it.  The ``DeviceMonitor`` repeats the probe on a period,
exporting

  pathway_device_rtt_ms   gauge — host round trip of one tiny dispatch
  pathway_device_healthy  gauge — 1 healthy / 0 down

plus a ``"device"`` key in the /status JSON.

Config: ``PATHWAY_DEVICE_PROBE=0`` disables the monitor entirely;
``PATHWAY_DEVICE_PROBE_INTERVAL_S`` sets the period (default 300 s).
"""

from __future__ import annotations

import atexit
import sys
import threading
import time as time_mod
from typing import Any, Dict, Optional, Tuple

from pathway_tpu.internals import config as _config

_ProbeResult = Tuple[Optional[float], Optional[str]]


class _InProcessProbe:
    """One probe round on its own daemon thread: a tiny compiled matmul
    dispatched to every local device, the slowest round trip timed."""

    _fn = None  # jitted once per process; the first round pays the compile

    def __init__(self) -> None:
        self.done = threading.Event()
        self.result: _ProbeResult = (None, "device probe did not run")
        threading.Thread(
            target=self._run, daemon=True, name="pw-device-probe-dispatch"
        ).start()

    def _run(self) -> None:
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            cls = type(self)
            if cls._fn is None:
                cls._fn = jax.jit(lambda a: (a @ a).sum())
            x = jnp.ones((64, 64))
            rtt_ms = 0.0
            for device in jax.local_devices():
                xd = jax.device_put(x, device)
                np.asarray(cls._fn(xd))  # compile + warm for this device
                t0 = time_mod.perf_counter()
                np.asarray(cls._fn(xd))
                rtt_ms = max(rtt_ms, (time_mod.perf_counter() - t0) * 1000.0)
            self.result = (rtt_ms, None)
        except Exception as exc:  # noqa: BLE001 — the verdict IS the report
            self.result = (
                None, f"device probe failed: {type(exc).__name__}: {exc}"
            )
        finally:
            self.done.set()


_probe_lock = threading.Lock()
_outstanding: Optional[_InProcessProbe] = None


def device_probe(timeout_s: float = 120.0) -> _ProbeResult:
    """One in-process probe.  Returns ``(rtt_ms, None)`` when healthy,
    ``(None, error_string)`` when the device is unusable.  Starts no
    process: a child that initialised the TPU backend would fail (or come
    up on the CPU and report a CPU round trip as healthy) while this
    process holds the chip."""
    global _outstanding
    if "jax" not in sys.modules:
        return None, None
    with _probe_lock:
        probe = _outstanding
        if probe is None or probe.done.is_set():
            probe = _outstanding = _InProcessProbe()
    if not probe.done.wait(timeout_s):
        return None, f"device probe dispatch outstanding after {timeout_s}s"
    return probe.result


class DeviceMonitor:
    """Periodic device-health prober with its own metrics registry.

    The registry uses pull-time callback gauges over ``self.last``, so a
    scrape never triggers a probe — the daemon thread owns the cadence.
    ``probe`` is injectable for tests (the default dispatches in-process)."""

    def __init__(
        self,
        *,
        interval_s: float | None = None,
        timeout_s: float = 120.0,
        probe=device_probe,
    ):
        from pathway_tpu.internals.metrics import MetricsRegistry

        if interval_s is None:
            interval_s = _config.env("PATHWAY_DEVICE_PROBE_INTERVAL_S")
        self.interval_s = max(1.0, interval_s)
        self.timeout_s = timeout_s
        self.probe = probe
        self.last: Dict[str, Any] = {"status": "not_started"}
        # degradation state machine: HEALTHY <-> DEGRADED.  A failed (or
        # fault-injected) probe flips to DEGRADED — device-phase work
        # routes to the host path (see stdlib/indexing) — and the monitor
        # re-probes on a capped exponential backoff instead of the slow
        # steady-state period, so re-promotion is prompt after a blip but
        # a hard outage isn't probed every second.
        from pathway_tpu.internals.backoff import Backoff

        self.state = "healthy"  # optimistic until a probe says otherwise
        self.probes = 0  # completed probe rounds
        self.flaps = 0  # healthy->degraded transitions
        self.promotions = 0  # degraded->healthy transitions
        self.degraded_since: Optional[float] = None
        self._reprobe = Backoff(
            base=1.0, cap=self.interval_s, jitter=0.25, seed=0
        )
        reg = self.metrics = MetricsRegistry()
        reg.gauge(
            "pathway_device_degraded",
            help="1 while device-phase work is routed to the host path "
            "(probe failed or fault-injected flap), 0 when healthy",
            callback=lambda: 1 if self.state == "degraded" else 0,
        )
        reg.gauge(
            "pathway_device_rtt_ms",
            help="host round trip of one tiny jit dispatch on the "
            "accelerator (absent until the first probe completes)",
            callback=lambda: self.last.get("rtt_ms"),
        )
        reg.gauge(
            "pathway_device_healthy",
            help="1 when the last device probe succeeded, 0 when it "
            "failed or hung",
            callback=lambda: (
                None
                if "healthy" not in self.last
                else (1 if self.last["healthy"] else 0)
            ),
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def probe_once(self) -> Dict[str, Any]:
        from pathway_tpu.internals import faults

        if faults.ACTIVE and faults.probe_flap():
            rtt, err = None, "injected device flap (PATHWAY_FAULTS)"
        else:
            rtt, err = self.probe(self.timeout_s)
        self._transition(err is None)
        self.probes += 1
        self.last = {
            "status": "healthy" if err is None else "down",
            "healthy": err is None,
            "state": self.state,
            "rtt_ms": round(rtt, 3) if rtt is not None else None,
            "error": err,
            "checked_at": time_mod.time(),
            "probes": self.probes,
            "flaps": self.flaps,
            "promotions": self.promotions,
            "degraded_since": self.degraded_since,
        }
        return self.last

    def _transition(self, healthy: bool) -> None:
        if healthy:
            if self.state == "degraded":
                self.promotions += 1
            self.state = "healthy"
            self.degraded_since = None
            self._reprobe.reset()
        else:
            if self.state != "degraded":
                self.flaps += 1
                self.degraded_since = time_mod.time()
            self.state = "degraded"

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="pw-device-probe"
        )
        self._thread.start()

    def _run(self) -> None:
        while True:
            try:
                self.probe_once()
            except Exception as exc:  # noqa: BLE001 — monitor must survive
                self._transition(False)
                self.last = {"status": "down", "healthy": False,
                             "state": self.state,
                             "error": f"{type(exc).__name__}: {exc}"}
            # degraded: re-probe on capped exponential backoff so
            # re-promotion doesn't wait out the steady-state period
            if self.state == "degraded":
                delay = min(self._reprobe.next_delay(), self.interval_s)
            else:
                delay = self.interval_s
            if self._stop.wait(delay):
                return

    def stop(self, join_timeout_s: float = 0.0) -> None:
        self._stop.set()
        if join_timeout_s and self._thread is not None:
            self._thread.join(join_timeout_s)


# one monitor per process, however many PrometheusServers start
_monitor: Optional[DeviceMonitor] = None
_monitor_lock = threading.Lock()


def ensure_monitor() -> Optional[DeviceMonitor]:
    """Start (once) and return the process-wide device monitor; None when
    PATHWAY_DEVICE_PROBE=0."""
    global _monitor
    if not _config.env("PATHWAY_DEVICE_PROBE"):
        return None
    with _monitor_lock:
        if _monitor is None:
            _monitor = DeviceMonitor()
            _monitor.start()
        return _monitor


def _quiesce_at_exit() -> None:
    """Stop probing and let an outstanding dispatch return before the
    interpreter finalizes: a daemon thread that comes back from the
    runtime into a finalizing interpreter aborts the process."""
    if _monitor is not None:
        _monitor.stop(join_timeout_s=10.0)
    probe = _outstanding  # read after the monitor can start no new one
    if probe is not None:
        probe.done.wait(10.0)


atexit.register(_quiesce_at_exit)


def device_status() -> Dict[str, Any]:
    """The ``"device"`` key for /status."""
    if not _config.env("PATHWAY_DEVICE_PROBE"):
        return {"status": "disabled"}
    if _monitor is None:
        return {"status": "not_started"}
    out = dict(_monitor.last)
    # roofline context for the utilization gauges — only when jax is
    # already imported in this process (/status must not drag a backend
    # into a process that runs without one)
    if "jax" in sys.modules:
        from pathway_tpu.internals import costmodel, memtrack

        peak = costmodel.device_peak_flops()
        if peak:
            out["peak_tflops_bf16"] = round(peak / 1e12, 1)
        # device memory: the backend's own numbers when it reports them
        # (CPU devices report no memory stats -> None, the contract every
        # consumer expects — never a guess)
        stats = memtrack.jax_memory_stats()
        out["memory_total_bytes"] = (
            stats.get("bytes_limit") if stats else None
        )
        out["memory_available_bytes"] = (
            stats["bytes_limit"] - stats["bytes_in_use"]
            if stats and "bytes_limit" in stats and "bytes_in_use" in stats
            else None
        )
    return out


def device_degraded() -> bool:
    """Hot-path gate for host-path fallback: True while the monitor holds
    the device DEGRADED.  One global read + one attribute read when no
    monitor is running, so device-phase consumers can consult it per
    dispatch batch."""
    m = _monitor
    return m is not None and m.state == "degraded"
