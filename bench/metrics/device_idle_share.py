"""One minus the union of the device's op intervals over the traced window,
in percent, averaged over the cell's chips (device trace)."""


def read(ctx):
    r = ctx.reduced
    if r is None or r["busy_ns"] is None:
        return None
    return 100.0 * (1.0 - r["busy_ns"] / r["window_ns"])
