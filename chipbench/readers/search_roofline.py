"""Share of its roofline that the fused embed-and-search programs
reached.  The least a batch can take: one read of the provisioned buffer
(every row is scored, filled or not) against 2*Q*N*D scoring FLOPs plus
the queries' encoder FLOPs, whichever binds (chipbench/costs.py)."""

from chipbench import costs
from chipbench.readers import program_time, serve_flops


def read(ctx: dict, programs: list):
    reduced = ctx["trace"]
    if reduced is None:
        return None
    seconds, runs = program_time(reduced, programs)
    if seconds <= 0 or runs <= 0:
        return None
    config = ctx["cell"].config
    n, d = config["store"]["reserved_space"], config["model"]["hidden"]
    nbytes = runs * (4.0 * n * d + costs.encoder_weight_bytes(config["model"]))
    least = costs.roofline_seconds(serve_flops(ctx), nbytes, ctx["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
