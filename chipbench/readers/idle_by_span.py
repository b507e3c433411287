"""Whose time the chip's idle seconds are: each idle gap of the traced
window goes to the program span (pathway_tpu/internals/tracing.py) that
was open on the dispatch thread in the gap's middle.

The program's spans are `jax.profiler.TraceAnnotation`s, so under the
harness's capture they lie in the `.xplane.pb` host plane on the
capture's own clock, beside the device's "XLA Ops".  This reader loads
the capture itself (the readers run before the run directory goes),
takes the device's busy intervals inside the marker window exactly as
chipbench/trace.py does, and finds the dispatch thread's line as the one
that holds the `pipeline.launch` events (the host plane's lines carry OS
thread names, not Python's).

The metric is the share of idle seconds that fall under the spans named
in `spans` (a span's children included).  The reader is its own gate and
says why on stderr when it returns None:

  * no capture (`--dry`), or no program spans in it (the parent of the PR
    that brought them);
  * the clock check fails.  Every run of the encoder program on the device
    must start at or after the start of the `launch.encode` span that
    enqueued it (the i-th run and the i-th span, in order).  On the v5e
    the device plane's stamps come out up to a millisecond early (PERF.md,
    PR 27), so the device plane is shifted later by the least amount that
    puts every run at or after its launch, and the check fails where that
    shift is over MAX_SKEW_NS (the planes share no clock) or where the
    share read with the shift and without it differ by more than
    MAX_SHARE_MOVE points (the attribution hangs on the correction);
  * under 0.1 s of idle gaps in the window (a chip-bound cell);
  * under 90% of the idle seconds fall under any span of that line.

It also prints the idle seconds by innermost span, for PERF.md."""

from __future__ import annotations

import bisect
import glob
import os
import statistics
import sys

from chipbench import trace as tr

DISPATCH_MARK = "pipeline.launch"
ENCODE_SPAN = "launch.encode"
ENCODER_PROGRAM = "_fwd_packed"
# the span record's layers; other events of the line are jax's own
SPAN_PREFIXES = ("pipeline.", "launch.", "prep.", "engine.", "connector.", "host.gc")
MIN_IDLE_S = 0.1
MIN_ATTRIBUTED = 0.9
MAX_SKEW_NS = 5_000_000  # ten times the launch latency: beyond it, no shared clock
MAX_SHARE_MOVE = 1.0  # points a share may move between the raw and the shifted clock

_TABLES: dict = {}  # capture path -> what attribute() made of it


def _say(msg: str) -> None:
    print(f"[idle_by_span] {msg}", file=sys.stderr, flush=True)


def capture_path(trace_dir: str):
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return paths[-1] if paths else None


def dispatch_line(path: str) -> list:
    """The program's spans on the dispatch thread's line, as
    [name, start_ns, duration_ns]; [] where no line holds a launch."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if ev.name.startswith(SPAN_PREFIXES)
            ]
            if any(name == DISPATCH_MARK for name, _, _ in spans):
                return spans
    return []


def open_spans(spans: list) -> tuple:
    """(starts, chains): from starts[i] to starts[i+1] the spans open on
    the thread are chains[i], outermost first (() where none is).  The
    spans of one thread nest."""
    starts: list = []
    chains: list = []
    stack: list = []  # (end, name)

    def mark(at: int) -> None:
        chain = tuple(name for _, name in stack)
        if starts and starts[-1] == at:
            chains[-1] = chain
        else:
            starts.append(at)
            chains.append(chain)

    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            mark(stack.pop()[0])
        stack.append((start + dur, name))
        mark(start)
    while stack:
        mark(stack.pop()[0])
    return starts, chains


def idle_gaps(device: dict, shift_ns: int = 0) -> list:
    """[(start_ns, end_ns)] of the gaps of at least MIN_GAP_NS between the
    device's ops inside the marker window, `shift_ns` later."""
    lo, hi = tr._window(device)
    clipped = [
        (max(start, lo), min(start + dur, hi))
        for _, start, dur in device["ops"]
        if min(start + dur, hi) > max(start, lo)
    ]
    edges = [lo] + [x for ab in tr._union(clipped) for x in ab] + [hi]
    return [
        (a + shift_ns, b + shift_ns)
        for a, b in zip(edges[0::2], edges[1::2])
        if b - a >= tr.MIN_GAP_NS
    ]


def clock_differences(devices: dict, spans: list):
    """Device start of each encoder run minus host start of the
    `launch.encode` span that enqueued it, in ns; None where the two
    cannot be paired."""
    encodes = sorted(start for name, start, _ in spans if name == ENCODE_SPAN)
    diffs = []
    for device in devices.values():
        runs = sorted(
            start for name, start, _ in device["programs"] if ENCODER_PROGRAM in name
        )
        if len(runs) > len(encodes):
            return None
        diffs += [run - host for run, host in zip(runs, encodes)]
    return diffs or None


def _gaps_by_chain(devices: dict, starts: list, chains: list, shift_ns: int) -> list:
    gaps = []
    for device in devices.values():
        for a, b in idle_gaps(device, shift_ns):
            at = bisect.bisect_right(starts, (a + b) // 2) - 1
            gaps.append(((b - a) / 1e9, chains[at] if at >= 0 else ()))
    return gaps


def attribute(devices: dict, spans: list) -> dict:
    """{"idle_s", "gaps": [(seconds, chain)] on the shifted clock,
    "raw_gaps": the same on the capture's own, "clock_ns": diffs or None,
    "shift_ns"}"""
    starts, chains = open_spans(spans)
    diffs = clock_differences(devices, spans)
    shift = max(0, -min(diffs)) if diffs else 0
    raw = _gaps_by_chain(devices, starts, chains, 0)
    gaps = _gaps_by_chain(devices, starts, chains, shift) if shift else raw
    return {
        "idle_s": sum(s for s, _ in gaps),
        "gaps": gaps,
        "raw_gaps": raw,
        "clock_ns": diffs,
        "shift_ns": shift,
    }


def by_innermost(gaps: list) -> dict:
    table: dict = {}
    for seconds, chain in gaps:
        if not chain:
            label = "unattributed"
        elif chain[-1] == DISPATCH_MARK:
            label = DISPATCH_MARK + " self"
        else:
            label = chain[-1]
        table[label] = table.get(label, 0.0) + seconds
    return table


def _table_for(ctx: dict):
    from chipbench import harness

    trace_dir = os.path.join(harness.WORK, "run", ctx["cell"].name, "trace")
    path = capture_path(trace_dir)
    if path is None:
        _say(f"no capture under {trace_dir}")
        return None
    if path not in _TABLES:
        spans = dispatch_line(path)
        if not spans:
            _say(f"no host line holds {DISPATCH_MARK!r}: the program records no spans")
            _TABLES[path] = None
        else:
            table = attribute(tr.load_xplane(trace_dir)["devices"], spans)
            _TABLES[path] = table
            report(table)
    return _TABLES[path]


def report(table: dict) -> None:
    diffs = table["clock_ns"]
    if diffs:
        _say(
            f"clock check: {len(diffs)} encoder runs, device start minus "
            f"{ENCODE_SPAN} start: median {statistics.median(diffs) / 1e3:.1f} us, "
            f"min {min(diffs) / 1e3:.1f} us; device plane read "
            f"{table['shift_ns'] / 1e3:.1f} us later"
        )
    _say(f"idle seconds by span (of {table['idle_s']:.3f} s):")
    for label, seconds in sorted(by_innermost(table["gaps"]).items(), key=lambda kv: -kv[1]):
        _say(f"  {label:28s} {seconds:9.4f} s")


def _under(gaps: list, spans) -> float:
    return sum(s for s, chain in gaps if any(n in spans for n in chain))


def share(table: dict, spans) -> float | None:
    """The gate, then the share of idle seconds under `spans`."""
    diffs = table["clock_ns"]
    if not diffs:
        _say("clock check: no encoder run could be paired with its launch span")
        return None
    if table["shift_ns"] > MAX_SKEW_NS:
        _say(
            f"clock check failed: a run starts {table['shift_ns']} ns before its "
            f"launch, over {MAX_SKEW_NS}: the planes share no clock"
        )
        return None
    if table["idle_s"] < MIN_IDLE_S:
        _say(f"{table['idle_s']:.4f} s of idle gaps, under {MIN_IDLE_S} s: nothing to attribute")
        return None
    attributed = sum(s for s, chain in table["gaps"] if chain)
    if attributed < MIN_ATTRIBUTED * table["idle_s"]:
        _say(
            f"only {attributed:.3f} of {table['idle_s']:.3f} idle seconds fall "
            "under a span of the dispatch line"
        )
        return None
    value = 100.0 * _under(table["gaps"], spans) / table["idle_s"]
    raw = 100.0 * _under(table["raw_gaps"], spans) / table["idle_s"]
    if abs(value - raw) > MAX_SHARE_MOVE:
        _say(
            f"clock check failed: {sorted(spans)} reads {raw:.2f}% on the capture's "
            f"clock and {value:.2f}% on the shifted one"
        )
        return None
    return value


def read(ctx: dict, spans):
    if ctx["trace"] is None:
        return None
    table = _table_for(ctx)
    return None if table is None else share(table, set(spans))
