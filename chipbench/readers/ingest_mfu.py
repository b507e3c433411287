"""The whole step's share of the chips' peak: useful model FLOPs (the
architecture's count) of every document the window ingested over
window x chips x peak FLOP/s."""

from chipbench.readers import window_tokens


def read(ctx: dict):
    if ctx["peaks"] is None:
        return None
    model, work = ctx["cell"].config["model"], ctx["arch"].costs
    flops = sum(work.flops(model, t) for t in window_tokens(ctx))
    if flops <= 0:
        return None
    peak = ctx["peaks"]["flops"] * ctx["cell"].chips
    return 100.0 * flops / (ctx["window_s"] * peak)
