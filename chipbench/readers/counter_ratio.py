"""One of the program's counters over another: counts of its span record
(`/status` "spans", pathway_tpu/internals/tracing.py `add`), differenced
between the two snapshots the harness takes around the window.  In
percent unless `percent` is false.

Silent (None) where the program has no span record or no such counter
(the parent of the PR that brought it), or counted nothing in the window.
Unlike a span's seconds a count is the same on any backend, so the CPU
rehearsal reads it too."""


def counted(ctx: dict, name: str):
    """How often the program counted `name` between the snapshots, or None."""
    opened, closed = ctx["status_open"], ctx["status_close"]
    if opened is None or closed is None:
        return None
    if "spans" not in opened or "spans" not in closed:
        return None
    after = closed["spans"]["totals"].get(name)
    if after is None:
        return None
    before = opened["spans"]["totals"].get(name, {"count": 0})
    return int(after["count"]) - int(before["count"])


def read(ctx: dict, counter: str, over: str, percent: bool = True):
    n, base = counted(ctx, counter), counted(ctx, over)
    if n is None or not base:
        return None
    return (100.0 if percent else 1.0) * n / base
