"""Model FLOPs utilization: ``tokens_per_s`` times model FLOPs per token
(``flops.py``: 6 N + 12 L s d, recomputation not counted) over the chips'
bf16 peak (``peaks.json``, by ``device_kind``), in percent."""
import flops


def read(ctx):
    peak = flops.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * ctx.tokens / ctx.window_s * ctx.flops_per_token / (
        ctx.chips * peak)
