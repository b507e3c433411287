"""The serving tier's own 99th percentile of the time a query waited in
the micro-batcher ("serving": batch_wait_p99_ms), as /status gives it at
window close: the digest runs from start-up, warm-up bursts included."""


def read(ctx: dict):
    serving = (ctx["status_close"] or {}).get("serving") or {}
    wait = serving.get("batch_wait_p99_ms")
    return None if wait is None else float(wait)
