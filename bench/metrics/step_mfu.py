"""The step's share of the chips' bf16 peak on the device: model FLOPs per
step (``flops.py``: 6 N + 12 L s d, recomputation not counted) over the
mean device time of the step module's executions (as ``step_device_ms``)
times the chips' peak (``peaks.json``), in percent (device trace)."""
import flops


def read(ctx):
    r = ctx.reduced
    if r is None or not r["modules"]:
        return None
    runs = max(r["modules"].values(), key=sum)
    step_s = sum(runs) / len(runs) / 1e9
    peak = flops.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * ctx.flops_per_token * ctx.tokens / ctx.steps / (
        step_s * ctx.chips * peak)
