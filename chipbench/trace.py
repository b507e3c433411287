"""Reduction of a profiler trace to device busy time, per-program time,
per-op time and idle gaps attributed to what the host was doing.

Two steps, so that the second can be checked on a small recorded trace
(chipbench/tests/data/trace_small.json):

  load_xplane(path)   .xplane.pb -> {"devices": {plane: {"ops": [...],
                      "programs": [...]}}}, each event [name, start_ns,
                      duration_ns], read with jax.profiler.ProfileData
  reduce(events, ...) the numbers

The measured window is cut on the device's own clock by two marker
programs the harness runs at window open and close (`MARKER`): the window
is from the end of the first marker event to the end of the last.  The
same two events give the offset between the device clock and the host's
perf_counter, which places the host sampler's stack samples in the gaps.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import threading
import time

MARKER = "chipbench_marker"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
MIN_GAP_NS = 50_000  # shorter gaps are launch latency, not host stalls


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {"ops": [], "programs": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", PROGRAMS_LINE: "programs"}.get(line.name)
            if key is None:
                continue
            lines[key] = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
        devices[plane.name] = lines
    return {"devices": devices}


_SHAPE = re.compile(r"\b(?:pred|[a-z]+\d+)\[[\d,]*\]")


def short_op_name(event_name: str) -> str:
    """An XLA op event is named by its whole HLO line.  Keep the op's name
    without its running number and the shapes it produces, so that the same
    fusion in every layer adds up: '%fusion.452 = (f32[312,12,256]{..},
    f32[312,12,256,256]{..}) fusion(...)' -> 'fusion f32[312,12,256]
    f32[312,12,256,256]'."""
    head, _, rest = event_name.partition(" = ")
    name = re.sub(r"[.\d]+$", "", head.strip().lstrip("%"))
    if not rest:
        return name[:96]
    produced = rest.split(") ", 1)[0] if rest.startswith("(") else rest.split(" ", 1)[0]
    return (name + " " + " ".join(_SHAPE.findall(produced)))[:96].strip()


def _program_name(event_name: str) -> str:
    """'jit__fwd_packed(123456)' -> 'jit__fwd_packed'."""
    return event_name.split("(", 1)[0]


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _window(device: dict) -> tuple:
    ends = [
        start + dur
        for name, start, dur in device["programs"]
        if MARKER in name
    ]
    if len(ends) < 2:
        raise ValueError(
            f"{len(ends)} marker program(s) in the trace; the window needs "
            "one at its open and one at its close"
        )
    ends.sort()
    return ends[-2], ends[-1]  # the last two: a cell may mark its lead-in too


def reduce(events: dict, samples: list | None = None, host_open_s: float | None = None) -> dict:
    """events: what load_xplane gives.  samples: the host sampler's
    [(perf_counter_s, label), ...]; host_open_s: perf_counter when the
    opening marker was seen complete on the host.  Returns busy_s and
    window_s (averaged over devices), idle share per device, seconds per
    program and per op (summed over devices, inside the window), and the
    idle gaps by host label."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace has no device plane")
    per_device = {}
    sample_times = [t for t, _ in samples] if samples else []
    programs: dict = {}
    program_runs: dict = {}
    ops: dict = {}
    gaps_by_label: dict = {}
    for plane, device in sorted(devices.items()):
        lo, hi = _window(device)
        clipped = []
        for name, start, dur in device["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                clipped.append((a, b))
                key = short_op_name(name)
                ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        for name, start, dur in device["programs"]:
            if MARKER in name or start < lo or start + dur > hi:
                continue  # only programs that ran wholly inside the window
            key = _program_name(name)
            programs[key] = programs.get(key, 0.0) + dur / 1e9
            program_runs[key] = program_runs.get(key, 0) + 1
        busy = _union(clipped)
        busy_ns = sum(b - a for a, b in busy)
        per_device[plane] = {
            "busy_s": busy_ns / 1e9,
            "window_s": (hi - lo) / 1e9,
            "idle_share": 1.0 - busy_ns / (hi - lo),
        }
        if samples and host_open_s is not None:
            edges = [lo] + [x for ab in busy for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b - a < MIN_GAP_NS:
                    continue
                label = _label_for(
                    samples, sample_times,
                    host_open_s + (a - lo) / 1e9,
                    host_open_s + (b - lo) / 1e9,
                )
                gaps_by_label[label] = gaps_by_label.get(label, 0.0) + (b - a) / 1e9
    n = len(per_device)
    return {
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "window_s": sum(d["window_s"] for d in per_device.values()) / n,
        "per_device": per_device,
        "programs": programs,
        "program_runs": program_runs,
        "ops": ops,
        "idle_gaps": gaps_by_label,
    }


def _label_for(samples: list, times: list, lo_s: float, hi_s: float) -> str:
    """The commonest host label among the samples inside [lo, hi]; for a
    gap shorter than the sampling period, the sample nearest to it."""
    a, b = bisect.bisect_left(times, lo_s), bisect.bisect_right(times, hi_s)
    inside = [label for _, label in samples[a:b]]
    if not inside:
        near = min(max(a, 1), len(samples) - 1)
        mid = (lo_s + hi_s) / 2
        pick = near if abs(times[near] - mid) < abs(times[near - 1] - mid) else near - 1
        return samples[pick][1]
    return max(set(inside), key=inside.count)


def top(table: dict, n: int = 10) -> list:
    return [
        [name, seconds]
        for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:n]
    ]


# -- host sampler --------------------------------------------------------------

_WAITING = {
    "wait", "select", "sleep", "get", "acquire", "poll", "accept", "recv",
    "recv_into", "readinto", "_recv", "join", "_wait_for_tstate_lock",
    "result", "as_completed", "read", "readline", "run_forever",
    "_run_once", "_worker", "serve_forever",
}


class HostSampler:
    """Every `period_s`, what each Python thread of the process is doing:
    the innermost frame that is not a wait, as 'thread:file:line.func'.
    A label joins the (at most two) threads that are doing something;
    'all_waiting' when none is.  On only in a traced run."""

    def __init__(self, period_s: float = 0.01):
        self.period_s = period_s
        self.samples: list = []
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="chipbench-sampler", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> list:
        self._stop.set()
        self._thread.join(timeout=5.0)
        return self.samples

    def _run(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(self.period_s):
            now = time.perf_counter()
            names = {t.ident: t.name for t in threading.enumerate()}
            working = []
            for ident, frame in sys._current_frames().items():
                if ident == me or names.get(ident) == "MainThread":
                    continue
                code = frame.f_code
                if code.co_name in _WAITING:
                    continue
                base = os.path.splitext(os.path.basename(code.co_filename))[0]
                working.append(
                    f"{names.get(ident, ident)}:{base}:{frame.f_lineno}.{code.co_name}"
                )
            working.sort()
            self.samples.append(
                (now, "|".join(working[:2])[:64] if working else "all_waiting")
            )
