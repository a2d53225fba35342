"""Process start to the first timed step: imports, trainer build, compile or
cache load of the window's step variants, the first steps (host clock)."""


def read(ctx):
    return ctx.setup_s
