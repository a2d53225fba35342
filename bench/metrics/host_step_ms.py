"""The host loop's own time per step: the mean ``edgc.step`` host span less
the ``edgc.*`` spans nested in it (flush, window end, checkpoint) (the
program's host spans in the trace, ``xplane.reduce_scopes``)."""
import xplane


def read(ctx):
    return xplane.host_step_ms(ctx.reduced)
