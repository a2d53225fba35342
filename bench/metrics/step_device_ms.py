"""Mean device duration of the train step's executions in the window: the
XLA module that took the most device time, its entropy-on and entropy-off
variants together (device trace)."""


def read(ctx):
    r = ctx.reduced
    if r is None or not r["modules"]:
        return None
    runs = max(r["modules"].values(), key=sum)
    return sum(runs) / len(runs) / 1e6
