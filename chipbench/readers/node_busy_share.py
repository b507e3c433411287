"""Share of the time between the two /status snapshots that the engine
spent inside nodes of one type (their latency histograms' `total_s`)."""


def _total(status: dict, node_type: str) -> float:
    return sum(
        float(node["total_s"])
        for worker in status["workers"]
        for node in worker["nodes"]
        if node["type"] == node_type
    )


def read(ctx: dict, node_type: str):
    if ctx["status_open"] is None or ctx["status_close"] is None:
        return None
    spent = _total(ctx["status_close"], node_type) - _total(ctx["status_open"], node_type)
    return 100.0 * spent / ctx["status_interval_s"]
