"""End-to-end epoch tracing: span store, Chrome trace export, critical
path, and the slow-tick stack sampler.

Epoch-scoped spans in the style of Dapper-ish distributed tracing laid
over the engine's totally-ordered logical times (the progress-tracking
view of Naiad): every sampled epoch records one span per node that did
work, one span for watermark advancement, and one edge per cross-worker
exchange stamp (origin worker, send wall-time, receive wall-time).
Because all workers step epochs in SPMD lockstep, sampling by
``time % sample_every == 0`` is deterministic across the whole mesh —
whenever one worker records an epoch, every worker records it, which is
what makes symmetric stamp send/receive safe with zero coordination.

Sampling config (read once per engine):
  PATHWAY_TRACE=0          tracing fully off
  PATHWAY_TRACE=1          trace every epoch
  PATHWAY_TRACE_SAMPLE=N   trace epochs where time % N == 0 (default 16)
The ring keeps the last ``TRACE_EPOCHS`` sampled epochs.

Overhead budget: unsampled ticks pay one attribute load + one modulo;
sampled ticks add one tuple append per active node.  The perf-smoke
guard (tests/test_perf_smoke.py) holds the default-sampling cost of the
whole observability layer under 5% of the bare loop.

The span record (second half of this module) is the always-on,
process-wide companion: ``span(name, seq=, epoch=, rows=)`` at batch,
tick, file and collection granularity — never per row — feeds per-name
totals (``/status`` "spans"), one bounded ring (``dump_trace``), and a
``jax.profiler.TraceAnnotation`` so that under a profiler capture the
span lies on the capture's clock beside the device's ops.  Epoch
sampling above governs only the per-node spans inside a tick.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time as time_mod
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional

from pathway_tpu.internals import config as _config

TRACE_EPOCHS = 128  # sampled epochs a TraceStore's ring keeps


class _EpochRecord:
    """All spans/edges captured for one sampled epoch on one worker."""

    __slots__ = ("epoch", "t0", "t1", "spans", "edges", "wm")

    def __init__(self, epoch: int, t0: float):
        self.epoch = epoch
        self.t0 = t0
        self.t1 = t0
        # (node_idx, name, start_perf, duration_s, rows)
        self.spans: List[tuple] = []
        # (channel, origin_worker, send_wall, recv_wall)
        self.edges: List[tuple] = []
        self.wm: Optional[tuple] = None  # (start_perf, duration_s)


class TraceStore:
    """Per-engine bounded store of sampled epoch traces.

    The engine loop drives it: ``should_sample(time)`` gates the traced
    loop variant, ``begin_epoch``/``end_epoch`` bracket one tick, and
    the exchange node reports cross-worker edges via ``note_edge``.
    Spans carry perf_counter times (cheap, monotonic) converted to wall
    clock at export with the same offset trick the flight recorder uses;
    edges carry wall clock directly because they cross processes."""

    def __init__(
        self,
        worker_id: int = 0,
        *,
        sample_every: int | None = None,
        capacity: int | None = None,
    ):
        mode = _config.env("PATHWAY_TRACE")
        self.enabled = mode != "0"
        if sample_every is None:
            sample_every = (
                1 if mode == "1" else _config.env("PATHWAY_TRACE_SAMPLE")
            )
        self.sample_every = max(1, sample_every)
        self.worker_id = worker_id
        if capacity is None:
            capacity = TRACE_EPOCHS
        self.epochs: deque = deque(maxlen=max(1, capacity))
        self.current: Optional[_EpochRecord] = None
        # perf_counter -> wall-clock offset, sampled once (flight-recorder
        # convention): spans stamp the cheap clock, export converts
        self._epoch_off = time_mod.time() - time_mod.perf_counter()

    # -- engine-loop hooks -------------------------------------------------
    def should_sample(self, time: int) -> bool:
        return self.enabled and time % self.sample_every == 0

    def in_epoch(self, time: int) -> bool:
        cur = self.current
        return cur is not None and cur.epoch == time

    def begin_epoch(self, time: int, t0: float) -> _EpochRecord:
        rec = _EpochRecord(time, t0)
        self.current = rec
        return rec

    def end_epoch(self, wm_start: float, wm_end: float) -> None:
        """Close the current epoch after watermark advancement (the
        ``on_time_end`` sweep) ran between ``wm_start`` and ``wm_end``."""
        cur = self.current
        if cur is None:
            return
        cur.wm = (wm_start, wm_end - wm_start)
        self.epochs.append(cur)
        self.current = None

    def note_edge(
        self,
        time: int,
        channel: int,
        origin: int,
        send_wall: float,
        recv_wall: float,
    ) -> None:
        cur = self.current
        if cur is not None and cur.epoch == time:
            cur.edges.append((channel, origin, send_wall, recv_wall))

    # -- export ------------------------------------------------------------
    def export_events(self) -> List[tuple]:
        """Flatten the ring into compact self-describing tuples that
        survive the wire codec (dump_trace gathers them across processes
        via Coordinator.agree):
          ("tick", worker, epoch, start_wall, duration_s)
          ("span", worker, epoch, node_idx, name, start_wall, dur, rows)
          ("wm",   worker, epoch, start_wall, duration_s)
          ("edge", dst_worker, origin_worker, epoch, channel,
                   send_wall, recv_wall)"""
        off = self._epoch_off
        w = self.worker_id
        out: List[tuple] = []
        for ep in list(self.epochs):
            out.append(
                ("tick", w, ep.epoch, ep.t0 + off, max(0.0, ep.t1 - ep.t0))
            )
            for idx, name, ts, dur, rows in ep.spans:
                out.append(
                    ("span", w, ep.epoch, idx, name, ts + off, dur, rows)
                )
            if ep.wm is not None:
                out.append(("wm", w, ep.epoch, ep.wm[0] + off, ep.wm[1]))
            for channel, origin, sw, rw in ep.edges:
                out.append(("edge", w, origin, ep.epoch, channel, sw, rw))
        return out

    def critical_path(self, epoch: int | None = None) -> Optional[dict]:
        return critical_path_from_events(self.export_events(), epoch)


# ---------------------------------------------------------------------------
# Critical-path attribution
# ---------------------------------------------------------------------------


def critical_path_from_events(
    events: Iterable[tuple], epoch: int | None = None
) -> Optional[dict]:
    """Top-5 latency attribution for one completed epoch (default: the
    latest sampled one).  The engine is single-threaded per worker, so a
    worker's contribution to an epoch's wall time is literally the sum of
    its node spans + watermark sweep; cross-worker exchange transit shows
    up as explicit edge entries.  ``share_pct`` is relative to the
    longest per-worker tick (workers overlap in wall time)."""
    events = list(events)
    ticks = [e for e in events if e[0] == "tick"]
    if not ticks:
        return None
    if epoch is None:
        epoch = max(e[2] for e in ticks)
    per_worker_total: Dict[int, float] = {}
    for _, w, ep, _ts, dur in ticks:
        if ep == epoch:
            per_worker_total[w] = per_worker_total.get(w, 0.0) + dur
    entries: List[dict] = []
    for ev in events:
        kind = ev[0]
        if kind == "span" and ev[2] == epoch:
            _, w, _ep, idx, name, _ts, dur, rows = ev
            entries.append(
                {
                    "kind": "node",
                    "worker": w,
                    "node": idx,
                    "name": name,
                    "duration_ms": round(dur * 1000, 4),
                    "rows": rows,
                }
            )
        elif kind == "pspan" and ev[7] == epoch and ev[2] != "engine.tick":
            # program spans of the batches this epoch submitted: they run
            # on pipeline threads CONCURRENT with the tick (and outlive
            # it), so each is attributed to its layer — the prefix of its
            # name — never as serial engine-loop time
            _, w, name, _thread, _ts, dur, _seq, _ep, _parent, rows = ev
            entries.append(
                {
                    "kind": name.split(".", 1)[0],
                    "worker": w,
                    "node": -1,
                    "name": name,
                    "duration_ms": round(dur * 1000, 4),
                    "rows": rows,
                }
            )
        elif kind == "wm" and ev[2] == epoch:
            _, w, _ep, _ts, dur = ev
            per_worker_total[w] = per_worker_total.get(w, 0.0) + dur
            entries.append(
                {
                    "kind": "watermark",
                    "worker": w,
                    "node": -1,
                    "name": "watermark",
                    "duration_ms": round(dur * 1000, 4),
                    "rows": 0,
                }
            )
        elif kind == "edge" and ev[3] == epoch:
            _, dst, origin, _ep, channel, sw, rw = ev
            entries.append(
                {
                    "kind": "exchange",
                    "worker": dst,
                    "node": -1,
                    "name": f"ch{channel} w{origin}->w{dst}",
                    "duration_ms": round(max(0.0, rw - sw) * 1000, 4),
                    "rows": 0,
                }
            )
    if not entries and not per_worker_total:
        return None
    total_s = max(per_worker_total.values(), default=0.0)
    entries.sort(key=lambda e: e["duration_ms"], reverse=True)
    total_ms = total_s * 1000
    for e in entries:
        e["share_pct"] = (
            round(min(100.0, 100.0 * e["duration_ms"] / total_ms), 1)
            if total_ms > 0
            else None
        )
    return {
        "epoch": epoch,
        "total_ms": round(total_ms, 4),
        "entries": entries[:5],
    }


def merged_critical_path(engines: Iterable[Any]) -> Optional[dict]:
    """Critical path over the latest sampled epoch across a group of
    in-process engines (thread workers share memory, so no coordination
    is needed — the /status endpoint calls this on every request)."""
    events: List[tuple] = []
    for eng in engines:
        m = getattr(eng, "metrics", None)
        tr = getattr(m, "trace", None) if m is not None else None
        if tr is not None:
            events.extend(tr.export_events())
    events.extend(export_span_events())
    return critical_path_from_events(events)


# ---------------------------------------------------------------------------
# Cross-worker gather + Chrome trace_event export
# ---------------------------------------------------------------------------


def gather_trace_events(engine) -> List[tuple]:
    """All trace events visible from this engine: its own, its in-process
    sibling thread workers' (shared memory), and — across processes —
    every peer's, gathered with ONE ``agree`` round on the TCP mesh.

    The TCP gather is an SPMD collective: in multiprocess runs every
    process must call ``dump_trace`` (or this function) at the same point
    of its script, exactly once, or the agreement rounds desynchronize —
    the same contract every other coordinator call already has."""
    engines = [engine]
    coord = getattr(engine, "coord", None)
    group = getattr(coord, "group", None)
    if group is not None:
        for e in getattr(group, "engines", ()):
            if e not in engines:
                engines.append(e)
    events: List[tuple] = []
    for e in engines:
        m = getattr(e, "metrics", None)
        tr = getattr(m, "trace", None) if m is not None else None
        if tr is not None:
            events.extend(tr.export_events())
    # the process's span record, once, under this engine's worker id
    events.extend(export_span_events(getattr(engine, "worker_id", 0)))
    tcp = group.tcp if group is not None else None
    if tcp is None and coord is not None and hasattr(coord, "_recv_loop"):
        tcp = coord  # plain TcpCoordinator (threads == 1)
    if tcp is not None:
        gathered = tcp.agree(events)
        events = [
            tuple(ev) for per_process in gathered for ev in per_process
        ]
    return events


def build_chrome_trace(events: Iterable[tuple]) -> dict:
    """Render exported events as Chrome/Perfetto ``trace_event`` JSON:
    one pid per worker, complete ("X") spans for ticks/nodes/watermarks,
    flow ("s"/"f") arrows for cross-worker exchange edges."""
    events = list(events)
    workers = set()
    for ev in events:
        if ev[0] == "edge":
            workers.add(ev[1])
            workers.add(ev[2])
        else:
            workers.add(ev[1])
    te: List[dict] = []
    for w in sorted(workers):
        te.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": w,
                "tid": 0,
                "args": {"name": f"worker {w}"},
            }
        )
    flow_id = 0
    span_tids: Dict[tuple, int] = {}  # (worker, thread name) -> tid
    for ev in events:
        kind = ev[0]
        if kind == "pspan":
            _, w, name, thread, ts, dur, seq, epoch, parent, rows = ev
            tid = span_tids.get((w, thread))
            if tid is None:
                # tids 0 and 1 are the worker's tick and node rows
                tid = span_tids[(w, thread)] = 2 + len(span_tids)
                te.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": w,
                        "tid": tid,
                        "args": {"name": thread},
                    }
                )
            te.append(
                {
                    "ph": "X",
                    "cat": name.split(".", 1)[0],
                    "name": name,
                    "pid": w,
                    "tid": tid,
                    "ts": round(ts * 1e6, 1),
                    "dur": round(dur * 1e6, 1),
                    "args": {
                        "seq": seq,
                        "epoch": epoch,
                        "parent": parent,
                        "rows": rows,
                    },
                }
            )
        elif kind == "tick":
            _, w, epoch, ts, dur = ev
            te.append(
                {
                    "ph": "X",
                    "cat": "tick",
                    "name": f"epoch {epoch}",
                    "pid": w,
                    "tid": 0,
                    "ts": round(ts * 1e6, 1),
                    "dur": round(dur * 1e6, 1),
                    "args": {"epoch": epoch},
                }
            )
        elif kind == "span":
            _, w, epoch, idx, name, ts, dur, rows = ev
            te.append(
                {
                    "ph": "X",
                    "cat": "node",
                    "name": name,
                    "pid": w,
                    "tid": 1,
                    "ts": round(ts * 1e6, 1),
                    "dur": round(dur * 1e6, 1),
                    "args": {"epoch": epoch, "node": idx, "rows": rows},
                }
            )
        elif kind == "wm":
            _, w, epoch, ts, dur = ev
            te.append(
                {
                    "ph": "X",
                    "cat": "watermark",
                    "name": "watermark",
                    "pid": w,
                    "tid": 1,
                    "ts": round(ts * 1e6, 1),
                    "dur": round(dur * 1e6, 1),
                    "args": {"epoch": epoch},
                }
            )
        elif kind == "edge":
            _, dst, origin, epoch, channel, sw, rw = ev
            flow_id += 1
            common = {
                "cat": "exchange",
                "name": f"ch{channel}",
                "id": flow_id,
                "tid": 0,
            }
            te.append(
                {
                    "ph": "s",
                    "pid": origin,
                    "ts": round(sw * 1e6, 1),
                    "args": {"epoch": epoch},
                    **common,
                }
            )
            te.append(
                {
                    "ph": "f",
                    "bp": "e",
                    "pid": dst,
                    "ts": round(rw * 1e6, 1),
                    "args": {"epoch": epoch},
                    **common,
                }
            )
    return {"traceEvents": te, "displayTimeUnit": "ms"}


_ALLOWED_PH = frozenset("BEXiICsfTtbneMPNODSvVp")


def validate_chrome_trace(trace: Any) -> None:
    """Schema-check a Chrome ``trace_event`` object (raises ValueError):
    the structural rules Perfetto's importer actually enforces — phase
    codes, numeric timestamps, flow-event ids, JSON-serializability."""
    if not isinstance(trace, dict):
        raise ValueError("trace must be a JSON object")
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        raise ValueError("traceEvents must be a list")
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            raise ValueError(f"traceEvents[{i}] is not an object")
        ph = ev.get("ph")
        if not isinstance(ph, str) or ph not in _ALLOWED_PH:
            raise ValueError(f"traceEvents[{i}]: bad phase {ph!r}")
        if not isinstance(ev.get("pid"), int):
            raise ValueError(f"traceEvents[{i}]: pid must be an int")
        if ph != "M":
            if not isinstance(ev.get("ts"), (int, float)):
                raise ValueError(f"traceEvents[{i}]: ts must be numeric")
            if not isinstance(ev.get("name"), str):
                raise ValueError(f"traceEvents[{i}]: missing name")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(
                    f"traceEvents[{i}]: X event needs dur >= 0"
                )
        if ph in "sft" and "id" not in ev:
            raise ValueError(f"traceEvents[{i}]: flow event needs an id")
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as exc:
        raise ValueError(f"trace is not JSON-serializable: {exc}") from None


# ---------------------------------------------------------------------------
# Slow-tick sampler
# ---------------------------------------------------------------------------


class SlowTickWatchdog:
    """Capture all-thread Python stacks into the flight recorder when a
    tick exceeds PATHWAY_SLOW_TICK_MS.

    A daemon thread polls the in-flight tick marker at half the threshold
    period; the engine loop pays only two attribute stores per tick (and
    zero when the watchdog is disabled — the loop None-checks it).  One
    capture per offending tick: the point is "what was the engine doing
    while it was stuck", not a profiler."""

    def __init__(self, engine, recorder, threshold_ms: float):
        import weakref

        self.threshold_s = max(0.001, float(threshold_ms) / 1000.0)
        self.recorder = recorder
        self._engine_ref = weakref.ref(engine)
        self._current: Optional[tuple] = None  # (perf_start, engine_time)
        self._captured_for: Optional[tuple] = None
        self._stop = threading.Event()
        self._poll = min(0.25, max(0.001, self.threshold_s / 2.0))
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="pw-slow-tick"
        )
        self._thread.start()

    def begin(self, time: int) -> None:
        self._current = (time_mod.perf_counter(), time)

    def end(self) -> None:
        self._current = None

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            cur = self._current
            if cur is None or cur == self._captured_for:
                continue
            t0, etime = cur
            elapsed = time_mod.perf_counter() - t0
            if elapsed < self.threshold_s:
                continue
            self._captured_for = cur
            try:
                self._capture(etime, elapsed)
            except Exception:  # noqa: BLE001 — diagnostics must not kill runs
                pass

    def _capture(self, etime: int, elapsed: float) -> None:
        import sys
        import traceback

        me = threading.get_ident()
        parts = []
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = traceback.extract_stack(frame)[-8:]
            top = " < ".join(
                f"{f.name}@{os.path.basename(f.filename)}:{f.lineno}"
                for f in reversed(stack)
            )
            parts.append(f"[tid {tid}] {top}")
        eng = self._engine_ref()
        node = getattr(eng, "current_node", None) if eng is not None else None
        self.recorder.record(
            "slow_tick",
            time=etime,
            node=getattr(node, "_idx", -1),
            name=" | ".join(parts)[:4000],
            duration_s=elapsed,
        )

    def stop(self) -> None:
        self._stop.set()


# ---------------------------------------------------------------------------
# Flight-recorder causal merge
# ---------------------------------------------------------------------------


def merge_flight_tails(
    tails: Iterable[List[Dict[str, Any]]]
) -> List[Dict[str, Any]]:
    """Merge per-worker flight-recorder tails in causal order.

    Wall clocks skew across processes; (epoch, seq, worker) does not:
    epochs advance in lockstep, and within one epoch every worker appends
    events in the same node order (SPMD), so per-worker sequence numbers
    align causally."""
    merged = [e for tail in tails for e in tail]
    merged.sort(
        key=lambda e: (
            e.get("time", 0),
            e.get("seq", 0),
            e.get("worker", 0),
        )
    )
    return merged


# ---------------------------------------------------------------------------
# The span record: process-wide, always on
# ---------------------------------------------------------------------------
#
# One record per process, because the threads it covers (connector,
# engine, pipeline prep and dispatch) belong to the process and not to an
# engine.  Span sites are batch / tick / file / collection granularity
# (under ~200 spans a second on the busiest ingest), so there is no
# switch: a span costs two perf_counter reads, two thread_time reads
# (sampled where the name's spans are short), an inactive
# TraceAnnotation's flag test and no lock.

SPAN_RING = 8192  # closed spans kept for dump_trace
GC_RECENT_S = 64  # seconds of per-second longest collections kept
SHORT_SPAN_S = 1e-3  # mean duration under which CPU time is sampled
_perf = time_mod.perf_counter
_thread_time = time_mod.thread_time


def _process_start_monotonic() -> float:
    """time.monotonic() at which this process was created (Linux), so
    that a mark counts the interpreter's own start and the imports; where
    /proc is absent, now: the time of `import pathway_tpu`."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time_mod.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time_mod.monotonic()


T_PROCESS = _process_start_monotonic()
_FIELDS = ("count", "total_s", "cpu_s", "self_s", "rows", "max_s", "open_s")


class _ThreadSpans:
    """One thread's side of the record: its stack of open spans and its
    own totals, so that closing a span takes no lock (the totals of all
    threads are summed when they are read)."""

    __slots__ = ("name", "thread", "stack", "totals", "ring", "gc_span", "gc_t0")

    def __init__(self, ring: deque):
        self.thread = threading.current_thread()
        self.name = self.thread.name
        self.stack: list = []
        # name -> [count, total_s, cpu_s, self_s, rows, max_s]
        self.totals: Dict[str, list] = {}
        self.ring = ring
        self.gc_span = None
        self.gc_t0 = None

    def close(
        self, name, t0, t1, cpu_s, self_s, seq, epoch, parent, rows
    ) -> None:
        dur = t1 - t0
        tot = self.totals.get(name)
        if tot is None:
            tot = self.totals[name] = [0, 0.0, 0.0, 0.0, 0, 0.0]
        tot[0] += 1
        tot[1] += dur
        tot[2] += cpu_s
        tot[3] += self_s
        tot[4] += rows
        if dur > tot[5]:
            tot[5] = dur
        self.ring.append((name, self.name, t0, t1, seq, epoch, parent, rows))
        sink = _SUBSCRIBERS.get(name)
        if sink is not None:
            sink(dur)


def _fold(into: Dict[str, list], totals: Dict[str, list]) -> None:
    for name, tot in list(totals.items()):
        acc = into.get(name)
        if acc is None:
            into[name] = list(tot)
        else:
            for i in range(5):
                acc[i] += tot[i]
            if tot[5] > acc[5]:
                acc[5] = tot[5]


class SpanRecord:
    """Every thread's totals, the ring of closed spans (name, thread, t0,
    t1, seq, epoch, parent, rows; perf_counter times), and the longest
    recent garbage collections."""

    def __init__(self, capacity: int = SPAN_RING):
        # the list of threads.  Re-entrant: a collection can start while
        # this thread holds it, and the hook below may take it (`here`)
        self.lock = threading.RLock()
        self.threads: List[_ThreadSpans] = []
        self.retired: Dict[str, list] = {}  # totals of threads that ended
        self.ring: deque = deque(maxlen=capacity)
        # slot `second % GC_RECENT_S` -> (t_end_monotonic, duration_s,
        # generation): the longest collection that ended in that second (a
        # maximum between two /status readings cannot be differenced from
        # cumulative totals).  Collections do not overlap, so no lock
        self.gc_recent: list = [None] * GC_RECENT_S
        self.marks: set = set()  # the `setup.at.*` marks written so far
        self.local = threading.local()
        self.wall_off = time_mod.time() - _perf()

    def here(self) -> _ThreadSpans:
        try:
            return self.local.spans
        except AttributeError:
            mine = self.local.spans = _ThreadSpans(self.ring)
            with self.lock:
                if len(self.threads) >= 64:
                    # threads come and go (a server's request threads):
                    # keep what the ended ones measured, not the threads
                    for ended in [t for t in self.threads if not t.thread.is_alive()]:
                        _fold(self.retired, ended.totals)
                        self.threads.remove(ended)
                self.threads.append(mine)
            return mine

    def totals(self) -> Dict[str, list]:
        """name -> the six totals of its closed spans, and seventh the
        seconds so far of its spans that are open right now.  A reader
        that differences `total_s + open_s` between two readings gets the
        time inside the interval exactly, however long a span is (the
        dispatch thread of a chip-bound ingest waits two seconds at a
        time)."""
        with self.lock:
            out = {name: list(tot) for name, tot in self.retired.items()}
            threads = list(self.threads)
        for t in threads:
            _fold(out, t.totals)
        for tot in out.values():
            tot.append(0.0)
        now = _perf()
        for t in threads:
            for sp in list(t.stack):
                tot = out.get(sp.name)
                if tot is None:
                    tot = out[sp.name] = [0, 0.0, 0.0, 0.0, 0, 0.0, 0.0]
                tot[6] += max(0.0, now - sp.t0)
        return out


_RECORD = SpanRecord()
# name -> callable(duration_s), run where a span of that name closes:
# wiring, not state, so reset_spans() keeps it
_SUBSCRIBERS: Dict[str, Callable[[float], None]] = {}
# run before the totals are read, so that a counter of elapsed time
# (health.pressure) is up to date at every reading
_REFRESHERS: List[Callable[[], None]] = []


def subscribe(name: str, sink: Callable[[float], None]) -> None:
    """Hand every closed span of `name` to `sink(duration_s)` — how
    internals/utilization.py receives the pipeline's durations."""
    _SUBSCRIBERS[name] = sink


def on_read(refresh: Callable[[], None]) -> None:
    """Run `refresh()` before every reading of the totals."""
    if refresh not in _REFRESHERS:
        _REFRESHERS.append(refresh)


def reset_spans(capacity: int = SPAN_RING) -> SpanRecord:
    """Fresh totals and ring (tests scope a record to one scenario).  A
    span open on another thread closes into the record it was opened in."""
    global _RECORD
    _RECORD = SpanRecord(capacity)
    return _RECORD


_ANNOTATION = None  # jax's TraceAnnotation class, once jax is loaded


def _find_annotation():
    """Never imports jax (the connector and the engine stay jax-free):
    the class is taken from the module once something else loaded it.
    With jax loaded and no capture running a span pays one flag test."""
    global _ANNOTATION
    _ANNOTATION = getattr(
        sys.modules.get("jax.profiler"), "TraceAnnotation", None
    )
    return _ANNOTATION


class span:
    """``with span("pipeline.launch", seq=7, epoch=12, rows=512): ...``

    `seq` and `epoch` default to the enclosing span's on this thread, so
    children need not be told.  Inside the body `rows` may still be set;
    `cancel()` keeps the span out of the record (a drain that had nothing
    to wait for).  After the body `t0`, `t1` and `dur` hold the timing,
    for a caller that feeds it elsewhere too.  A span that has exited may
    be entered again (the engine keeps one object for its ticks and sets
    `epoch` and `rows` anew each time)."""

    __slots__ = (
        "name", "seq", "epoch", "rows", "t0", "t1", "dur", "child_s",
        "_cancelled", "_stats", "_ann", "_mine", "_cpu_weight", "_cpu0",
    )

    def __init__(self, name: str, *, seq=None, epoch=None, rows: int = 0):
        self.name = name
        self.seq = seq
        self.epoch = epoch
        self.rows = rows
        self._cancelled = False
        self._stats = None  # further stats for the annotation (host.gc's generation)
        self._ann = None

    def cancel(self) -> None:
        self._cancelled = True

    def __enter__(self) -> "span":
        mine = self._mine = _RECORD.here()
        self.child_s = 0.0
        stack = mine.stack
        if stack:
            parent = stack[-1]
            if self.seq is None:
                self.seq = parent.seq
            if self.epoch is None:
                self.epoch = parent.epoch
        annotation = _ANNOTATION or _find_annotation()
        if annotation is not None and annotation.is_enabled():
            # a capture is running: the span goes onto its clock
            stats = self._stats or {}
            if self.seq is not None:
                stats["seq"] = self.seq
            if self.epoch is not None:
                stats["epoch"] = self.epoch
            self._ann = annotation(self.name, **stats)
            self._ann.__enter__()
        # thread_time is a system call (0.5 us here, three times that on
        # a loaded machine) where perf_counter is not.  A name whose spans
        # average under a millisecond — the engine's tick on a fast graph
        # — has its CPU time read on one span in 16 and counted 16 times
        tot = mine.totals.get(self.name)
        if tot is None or tot[1] >= tot[0] * SHORT_SPAN_S:
            self._cpu_weight = 1
        elif tot[0] & 15:
            self._cpu_weight = 0
        else:
            self._cpu_weight = 16
        self._cpu0 = _thread_time() if self._cpu_weight else 0.0
        self.t0 = _perf()
        # pushed last: a reading of the open spans finds this one only
        # with the t0 of this entry (a span object may be entered again)
        stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self.t1 = _perf()
        weight = self._cpu_weight
        cpu_s = weight * (_thread_time() - self._cpu0) if weight else 0.0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        mine = self._mine
        stack = mine.stack
        stack.pop()
        dur = self.dur = t1 - self.t0
        parent = None
        if stack:
            parent = stack[-1]
            parent.child_s += dur
            parent = parent.name
        if not self._cancelled:
            mine.close(
                self.name, self.t0, t1, cpu_s, dur - self.child_s,
                self.seq, self.epoch, parent, self.rows,
            )
        return False


def current_span() -> Optional[span]:
    """The innermost open span on this thread, or None."""
    stack = _RECORD.here().stack
    return stack[-1] if stack else None


def current_epoch():
    """The epoch of the innermost open span on this thread (the engine
    tick that is running), or None."""
    sp = current_span()
    return sp.epoch if sp is not None else None


def record(name: str, t0: float, t1: float, *, seq=None, epoch=None,
           rows: int = 0) -> None:
    """A span timed by the caller (perf_counter), into totals and ring
    only: no annotation and no nesting.  For an interval that is an
    estimate, like the pipeline's completion-to-completion device time —
    the profiler's device plane has the truth."""
    _RECORD.here().close(name, t0, t1, 0.0, t1 - t0, seq, epoch, None, rows)


def add(name: str, seconds: float = 0.0, n: int = 1) -> None:
    """A counter in the same table: `n` more occurrences and `seconds`
    more of `total_s`.  For what has no span — time spent in a state
    that may still hold when the table is read."""
    totals = _RECORD.here().totals
    tot = totals.get(name)
    if tot is None:
        tot = totals[name] = [0, 0.0, 0.0, 0.0, 0, 0.0]
    tot[0] += n
    tot[1] += seconds


def mark(name: str) -> None:
    """`setup.at.<name>`: the process's age, in seconds since its
    creation, the first time this is reached; later calls write nothing.
    Marks cut a start's wall clock where spans give a site's own time
    (`imported`, `run`, `first_launch`, `first_completion`,
    `first_search`).  After the first time a site on a hot path pays this
    call and one look into a set, no lock."""
    rec = _RECORD
    if name in rec.marks:
        return
    with rec.lock:
        if name in rec.marks:
            return
        rec.marks.add(name)
    add("setup.at." + name, seconds=time_mod.monotonic() - T_PROCESS)


def spans_status() -> Dict[str, Any]:
    """The `"spans"` key of /status: cumulative totals of the closed
    spans per name plus `open_s`, the seconds so far of those open now;
    the program's clock at this reading; and the longest collection of
    each recent second as (t_end_monotonic, duration_s, generation)."""
    for refresh in list(_REFRESHERS):
        refresh()
    rec = _RECORD
    totals = rec.totals()
    now = time_mod.monotonic()
    gc_recent = sorted(
        list(held) for held in list(rec.gc_recent)
        if held is not None and held[0] > now - GC_RECENT_S
    )
    return {
        "monotonic_s": now,
        "totals": {
            name: dict(zip(_FIELDS, tot)) for name, tot in sorted(totals.items())
        },
        "gc_recent": gc_recent,
    }


def export_span_events(worker: int = 0) -> List[tuple]:
    """The ring as wire-safe tuples for the Chrome writer:
    ("pspan", worker, name, thread, start_wall, dur, seq, epoch, parent,
    rows)."""
    rec = _RECORD
    off = rec.wall_off
    return [
        ("pspan", worker, name, thread, t0 + off, t1 - t0, seq, epoch,
         parent, rows)
        for name, thread, t0, t1, seq, epoch, parent, rows in list(rec.ring)
    ]


# -- host.gc --------------------------------------------------------------


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook: every collection into the `host.gc` totals and
    the per-second maxima; one of generation 1 or 2 also as a span (ring,
    annotation, the enclosing span's self time).  Generation 0 runs every
    few hundred allocations — row granularity — so it gets no span."""
    rec = _RECORD
    mine = rec.here()
    generation = info["generation"]
    if phase == "start":
        if generation == 0:
            mine.gc_t0 = _perf()
        else:
            sp = span("host.gc")
            sp._stats = {"generation": generation}
            mine.gc_span = sp.__enter__()
        return
    if generation == 0:
        if mine.gc_t0 is None:
            return  # the hook came in between this collection's two calls
        dur = _perf() - mine.gc_t0
        mine.gc_t0 = None
        add("host.gc", dur)
    else:
        sp = mine.gc_span
        if sp is None:
            return
        mine.gc_span = None
        sp.rows = info.get("collected", 0)
        sp.__exit__(None, None, None)
        dur = sp.dur
    t_end = time_mod.monotonic()
    slot = int(t_end) % GC_RECENT_S
    held = rec.gc_recent[slot]
    if held is None or int(held[0]) != int(t_end) or dur > held[1]:
        rec.gc_recent[slot] = (t_end, dur, generation)


def install_gc_hook() -> None:
    """Installed once a process, by the first engine."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
