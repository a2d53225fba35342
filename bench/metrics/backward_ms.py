"""Device self time per step of the backward pass: ops under
``transpose(...edgc.forward...)`` (remat's recompute included) or under
``edgc.backward`` (the pipelined step's backward ticks) (device trace,
``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "backward", ctx.steps)
