"""Device self time per step of the ops under ``edgc.optimizer``, the Adam
update (device trace, ``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "optimizer", ctx.steps)
