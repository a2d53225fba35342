"""Device self time per step of the ops under ``edgc.entropy``, the GDS
entropy reading, amortised over every step of the window (device trace,
``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "entropy", ctx.steps)
