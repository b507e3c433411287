"""A value of the program's own span record (`/status` "spans",
pathway_tpu/internals/tracing.py): the sum of one field of the named
totals, less that of the names in `minus`.

The totals are cumulative since the process began, so the snapshot the
harness takes at window open (`at="open"`) holds set-up's: seconds under a
span of set-up's work, counts of the compile cache, and the marks
`setup.at.<mark>`, whose `total_s` is the process's age when the mark was
first reached.  `at="window"` is the difference between the two snapshots.

A name that has not occurred reads 0.0 beside one that has.  Silent (None)
where `span_share` is silent: where the program has no span record, or
none of `spans` in it (the parent of the PR that brought them), and in the
CPU rehearsal, whose set-up is the CPU backend's and not the deployment's
(the rehearsal's accepted test holds its line to the metrics it had)."""


def _sum(status: dict, names, field: str) -> float:
    totals = status["spans"]["totals"]
    return sum(float(totals[n][field]) for n in names if n in totals)


def read(ctx: dict, spans, field: str = "total_s", at: str = "open", minus=()):
    if at not in ("open", "window"):
        raise ValueError(f"at={at!r}: 'open' or 'window'")
    opened, closed = ctx["status_open"], ctx["status_close"]
    last = opened if at == "open" else closed
    if opened is None or last is None or ctx["trace"] is None:
        return None
    if "spans" not in opened or "spans" not in last:
        return None
    if not any(n in last["spans"]["totals"] for n in spans):
        return None
    value = _sum(last, spans, field) - _sum(last, minus, field)
    if at == "window":
        value -= _sum(opened, spans, field) - _sum(opened, minus, field)
    return value
