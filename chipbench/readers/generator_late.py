"""How late the load generator ran: a percentile of (send - due) over
the window's requests, from the generator's own clock readings."""

import numpy as np


def read(ctx: dict, percentile: float):
    late = ctx.get("generator_late_ms")
    if not late:
        return None
    return float(np.percentile(np.array(late), percentile))
