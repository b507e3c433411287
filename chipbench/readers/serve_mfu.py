"""The whole step's share of the chip's peak: model plus scoring FLOPs
(2*N*D a query over the provisioned buffer) of every query answered in the
window over window x chips x peak FLOP/s."""

from chipbench.readers import serve_flops


def read(ctx: dict):
    if ctx["peaks"] is None or not ctx.get("queries_answered"):
        return None
    return 100.0 * serve_flops(ctx) / (ctx["window_s"] * ctx["peaks"]["flops"] * ctx["cell"].chips)
