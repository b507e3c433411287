"""Share of its roofline that the encoder programs reached: the least
time the chips could take for the window's documents (the architecture's
costs, from token counts) over the device time of the named programs in
the trace, summed over chips."""

from chipbench import costs
from chipbench.readers import program_time, window_tokens


def read(ctx: dict, programs: list):
    reduced = ctx["trace"]
    if reduced is None:
        return None
    seconds, runs = program_time(reduced, programs)
    if seconds <= 0 or runs <= 0:
        return None
    model, work = ctx["cell"].config["model"], ctx["arch"].costs
    tokens = window_tokens(ctx)
    flops = sum(work.flops(model, t) for t in tokens)
    # every run of the program reads the layer weights once; under dp each
    # chip runs it, and `runs` counts every chip's
    nbytes = runs * work.weight_bytes(model) + sum(
        work.activation_bytes(model, t) for t in tokens
    )
    least = costs.roofline_seconds(flops, nbytes, ctx["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
