"""Share of its roofline that a kernel reached: the least time the chip
could take for the kernel's own work in the window over the device time of
its ops in the trace (every op whose name holds one of `ops`; a Pallas
kernel is named by its `name`, a grouped matmul of `jax.lax.ragged_dot` is
the op `ragged-dot`).

The work is the architecture's: `costs.<cost>_flops` and `<cost>_bytes`.
Without `counter` it is a sum over the window's documents,
`f(model, tokens)`.  With `counter` it is `f(model, n)` for the `n` the
program counted between the two `/status` snapshots (a count in its span
record, such as the (token, held expert) pairs actually routed here:
what was held, not what a buffer's empty rows execute), and the bytes are
`b(model, n, runs)` with the runs of `programs` in the window.

Silent (None) where the trace has no such op, the architecture no such
cost, or the program no such counter."""

from chipbench import costs
from chipbench.readers import program_time, window_tokens
from chipbench.readers.counter_ratio import counted


def read(ctx: dict, ops: list, cost: str, counter=None, programs=()):
    reduced = ctx["trace"]
    if reduced is None:
        return None
    seconds = sum(
        s for name, s in reduced["ops"].items() if any(o in name for o in ops)
    )
    work, model = ctx["arch"].costs, ctx["cell"].config["model"]
    flops_of = getattr(work, cost + "_flops", None)
    bytes_of = getattr(work, cost + "_bytes", None)
    if seconds <= 0 or flops_of is None or bytes_of is None:
        return None
    if counter is None:
        tokens = window_tokens(ctx)
        flops = sum(flops_of(model, t) for t in tokens)
        nbytes = sum(bytes_of(model, t) for t in tokens)
    else:
        n = counted(ctx, counter)
        if not n:
            return None
        _, runs = program_time(reduced, list(programs))
        flops, nbytes = flops_of(model, n), bytes_of(model, n, runs)
    least = costs.roofline_seconds(flops, nbytes, ctx["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
