"""1 - union of device-op intervals over the traced window, on the chip
that idled most."""


def read(ctx: dict):
    reduced = ctx["trace"]
    if reduced is None:
        return None
    return 100.0 * max(d["idle_share"] for d in reduced["per_device"].values())
