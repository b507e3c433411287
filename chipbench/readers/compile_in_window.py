"""Backend compilations (cache look-ups included) that jax reported
between window open and close.  0 is the sound reading, and it is reported
as 0: this is a count, not a share."""


def read(ctx: dict):
    return float(ctx["compiles_in_window"])
