"""Device self time per step of the ops under ``edgc.forward`` that are not
in the gradient: the model's forward pass, the flash forward kernel
included (device trace, ``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "forward", ctx.steps)
