"""XLA backend compiles (``jax.monitoring`` events) inside the timed window;
reads 0 when set-up warmed every step variant the window uses."""


def read(ctx):
    return ctx.window_compiles
