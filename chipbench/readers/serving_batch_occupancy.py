"""Queries per micro-batch flush of the serving tier between the two
/status snapshots ("serving": batched_queries over batches)."""


def read(ctx: dict):
    a, b = ctx["status_open"], ctx["status_close"]
    if a is None or b is None or not b.get("serving", {}).get("active"):
        return None
    batches = b["serving"]["batches"] - a["serving"].get("batches", 0)
    queries = b["serving"]["batched_queries"] - a["serving"].get("batched_queries", 0)
    return None if batches <= 0 else queries / batches
