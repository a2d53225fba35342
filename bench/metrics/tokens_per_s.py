"""All tokens of all steps in the window over the window's wall time, from
the first dispatch to the state's ``block_until_ready`` (host clock)."""


def read(ctx):
    return ctx.tokens / ctx.window_s
