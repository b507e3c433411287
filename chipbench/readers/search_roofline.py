"""Share of its roofline that the fused embed-and-search programs
reached.  The least a batch can take: one read of the provisioned buffer
(every row is scored, filled or not) and of the model's weights against
2*Q*N*D scoring FLOPs plus the queries' model FLOPs, whichever binds."""

from chipbench import costs
from chipbench.readers import program_time, serve_flops


def read(ctx: dict, programs: list):
    reduced = ctx["trace"]
    if reduced is None:
        return None
    seconds, runs = program_time(reduced, programs)
    if seconds <= 0 or runs <= 0:
        return None
    config, work = ctx["cell"].config, ctx["arch"].costs
    store, model = config["store"], config["model"]
    buffer_bytes = (
        float(costs.dtype_bytes(store["index_dtype"]))
        * store["reserved_space"] * work.embed_dim(model)
    )
    nbytes = runs * (buffer_bytes + work.weight_bytes(model))
    least = costs.roofline_seconds(serve_flops(ctx), nbytes, ctx["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
