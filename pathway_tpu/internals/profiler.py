"""On-demand `jax.profiler` capture — answer "why is MFU low" live.

One capture at a time, process-wide: `jax.profiler.start_trace` is a
global (a second start while one runs raises deep inside XLA), so the
guard lives here and both triggers share it:

  * the monitoring server's ``/profile?seconds=N`` route
    (internals/monitoring.py) — profile a RUNNING job without
    restarting it;
  * ``pathway-tpu profile`` (cli.py) — hit that route on a running
    job, or with ``--device`` capture locally while driving a small
    calibration matmul so the trace shows the chip's roofline shape.

Captures are bounded (MAX_SECONDS) and written under a fresh directory
(``PATHWAY_PROFILE_DIR`` or a tempdir) in the TensorBoard/XPlane layout
`jax.profiler` emits — open with `tensorboard --logdir` or xprof.
Failure to capture (no jax, unsupported backend) reports an error dict;
it never takes the serving job down.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Dict, Optional

from pathway_tpu.internals import config as _config

MAX_SECONDS = 120.0

_lock = threading.Lock()  # held for the WHOLE capture: the busy guard
_active: Optional[Dict[str, Any]] = None
_last: Optional[Dict[str, Any]] = None


class CaptureBusy(RuntimeError):
    """A capture is already in progress (one at a time, process-wide)."""


def _trace_dir(out_dir: Optional[str]) -> str:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        return out_dir
    base = _config.env("PATHWAY_PROFILE_DIR")
    if base:
        os.makedirs(base, exist_ok=True)
        return tempfile.mkdtemp(prefix="capture-", dir=base)
    return tempfile.mkdtemp(prefix="pathway-profile-")


def capture_active() -> bool:
    return _active is not None


def last_capture() -> Optional[Dict[str, Any]]:
    return _last


def profiler_status() -> Dict[str, Any]:
    """Capture state for /status["utilization"]["profiler"]."""
    return {"active": _active, "last": _last}


def capture(
    seconds: float,
    out_dir: Optional[str] = None,
    *,
    workload: Optional[Any] = None,
) -> Dict[str, Any]:
    """Run one jax.profiler trace for `seconds`, blocking the caller.

    Raises CaptureBusy when another capture is in flight.  `workload`
    (optional zero-arg callable) is invoked repeatedly during the
    window — used by the CLI's local mode; a server-side capture leaves
    it None and records whatever the job is doing.  Returns a dict with
    the trace dir (and file count) on success, or an "error" key when
    the profiler is unavailable — the monitoring route must keep
    serving either way."""
    global _active, _last
    seconds = max(0.05, min(float(seconds), MAX_SECONDS))
    if not _lock.acquire(blocking=False):
        raise CaptureBusy("a profiler capture is already in progress")
    try:
        trace_dir = _trace_dir(out_dir)
        _active = {
            "trace_dir": trace_dir,
            "seconds": seconds,
            "started_at": time.time(),
        }
        result = dict(_active)
        try:
            import jax

            jax.profiler.start_trace(trace_dir)
            try:
                deadline = time.monotonic() + seconds
                while time.monotonic() < deadline:
                    if workload is not None:
                        workload()
                    else:
                        time.sleep(min(0.05, seconds))
            finally:
                jax.profiler.stop_trace()
            result["files"] = sum(
                len(files) for _, _, files in os.walk(trace_dir)
            )
        except Exception as exc:  # noqa: BLE001 — report, never crash the job
            result["error"] = f"{type(exc).__name__}: {exc}"
        result["finished_at"] = time.time()
        _last = result
        return result
    finally:
        _active = None
        _lock.release()


def capture_local(seconds: float, out_dir: Optional[str] = None) -> Dict[str, Any]:
    """CLI `--device` mode: capture while driving a small calibration
    matmul chain, so the trace contains device activity even without a
    running job attached."""
    state: Dict[str, Any] = {}

    def workload() -> None:
        try:
            import jax
            import jax.numpy as jnp

            if "fn" not in state:
                k = jax.random.PRNGKey(0)
                state["x"] = jax.random.normal(
                    k, (1024, 1024), dtype=jnp.bfloat16
                )
                state["fn"] = jax.jit(lambda x: jnp.sum((x @ x) @ x))
            jax.block_until_ready(state["fn"](state["x"]))
        except Exception:  # noqa: BLE001 — trace whatever we can
            time.sleep(0.05)

    return capture(seconds, out_dir, workload=workload)
