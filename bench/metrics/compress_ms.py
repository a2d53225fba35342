"""Device self time per step of the ops under ``edgc.compress``: the DP
gradient sync, PowerSGD's factor products, QR, error feedback and
decompression (device trace, ``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.scope_ms(ctx.reduced, "compress", ctx.steps)
