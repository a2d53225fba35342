"""Device self time per step of the ops under no ``edgc.*`` scope: copies,
waits and whatever the compiler hoisted out of the scopes (device trace,
``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "unscoped", ctx.steps)
