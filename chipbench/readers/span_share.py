"""Shares of the program's own span record (`/status` "spans",
pathway_tpu/internals/tracing.py): the difference of the cumulative totals
between the two snapshots the harness takes around the window, over the
time between them (times `threads`) or over another span's total.

A name `node:<Type>` stands for the engine's per-node latency total of the
nodes of that type, so that a node's time can be split into what a span
inside it covers and the rest.  `field` is one of the totals' own
(`total_s`, `cpu_s`, `self_s`, `count`, `rows`: closed spans only, as a
node's latency total is of closed ticks only), `elapsed_s` (`total_s` plus
`open_s`, the seconds so far of the spans open at the reading: its
difference is the time inside the interval exactly, which matters where
one span lasts seconds), `wait_s` (wall minus CPU time of the span's
thread: waiting for the interpreter lock, where the span's body computes
in Python), or `recent_max_ms`: the longest single
`host.gc` collection that ended between the snapshots, which cumulative
totals cannot give and the program's list of recent collections can.

Silent (None) where the program has no span record (the parent of the PR
that brought it), and in the CPU rehearsal, whose timings are the CPU
backend's and not the deployment's."""


from chipbench.readers.node_busy_share import _total as _node_total


def _value(status: dict, name: str, field: str) -> float:
    if name.startswith("node:"):
        return _node_total(status, name[len("node:"):])
    entry = status["spans"]["totals"].get(name)
    if entry is None:
        return 0.0  # a span that has not occurred yet
    if field == "wait_s":
        return float(entry["total_s"]) - float(entry["cpu_s"])
    if field == "elapsed_s":
        return float(entry["total_s"]) + float(entry.get("open_s", 0.0))
    return float(entry[field])


def _delta(ctx: dict, names, field: str) -> float:
    return sum(
        _value(ctx["status_close"], n, field) - _value(ctx["status_open"], n, field)
        for n in names
    )


def _threads(ctx: dict, threads) -> float:
    if isinstance(threads, str):  # a key of the pipeline's /status entry
        return float(ctx["status_close"]["device_pipeline"][threads])
    return float(threads)


def read(ctx: dict, spans, field: str = "total_s", minus=(), over: str = "interval",
         threads=1):
    opened, closed = ctx["status_open"], ctx["status_close"]
    if opened is None or closed is None or ctx["trace"] is None:
        return None
    if "spans" not in opened or "spans" not in closed:
        return None
    if field == "recent_max_ms":
        since = float(opened["spans"]["monotonic_s"])
        pauses = [d for t_end, d, _gen in closed["spans"]["gc_recent"] if t_end > since]
        return 1000.0 * max(pauses) if pauses else None
    spent = _delta(ctx, spans, field) - _delta(ctx, minus, field)
    if over == "interval":
        base = float(ctx["status_interval_s"]) * _threads(ctx, threads)
    else:
        base = _delta(ctx, [over], "total_s")
    return 100.0 * spent / base if base > 0 else None
