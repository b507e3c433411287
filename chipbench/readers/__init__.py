"""Per-layer metric readers.  Each module has `read(ctx, **args)` and gives
a number, or None where it finds nothing to read (never 0 for a share of a
roofline or of a peak).  `ctx` is what chipbench/harness.py gathered in the
traced run; chipbench/README.md lists its keys.  What a model costs comes
from the cell's architecture, `ctx["arch"].costs`."""


def program_time(reduced: dict, programs: list) -> tuple:
    """(device seconds, runs) of the programs whose name holds one of
    `programs`, inside the traced window, summed over chips."""
    named = [n for n in reduced["programs"] if any(p in n for p in programs)]
    return (
        sum(reduced["programs"][n] for n in named),
        sum(reduced["program_runs"][n] for n in named),
    )


def serve_flops(ctx: dict) -> float:
    """What the window's answered queries need: 2*N*D scoring FLOPs a
    query over the provisioned buffer plus the queries' model FLOPs."""
    config, costs = ctx["cell"].config, ctx["arch"].costs
    n, d = config["store"]["reserved_space"], costs.embed_dim(config["model"])
    return 2.0 * n * d * ctx["queries_answered"] + sum(
        costs.flops(config["model"], t) for t in ctx["query_tokens"]
    )


def window_tokens(ctx: dict) -> list:
    """Real tokens of each document of the window.  Every file of a run
    has the same lengths, so the window's documents are whole files plus a
    prefix of one."""
    tokens = ctx["tokens_per_file"]
    files, rest = divmod(int(ctx["docs_in_window"]), int(ctx["docs_per_file"]))
    return list(tokens) * files + list(tokens[:rest])
