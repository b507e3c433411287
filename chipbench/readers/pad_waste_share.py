"""The device pipeline's own pad-waste counter: slab tokens that were
padding, as a share of all slab tokens dispatched since start-up (the
warm-up files have the window's lengths, so the share is the window's)."""


def read(ctx: dict):
    status = ctx["status_close"]
    ratio = (status or {}).get("device_pipeline", {}).get("pad_waste_ratio")
    return None if ratio is None else 100.0 * float(ratio)
