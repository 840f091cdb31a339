"""Model FLOPs utilization of the whole round, in percent: training FLOPs
per token (bench/flops.py) times tokens per second per chip, over the
chip's bf16 peak (bench/peaks.json)."""


def read(ctx):
    if not ctx.window_s:
        return None
    rate = ctx.window_tokens / ctx.window_s / ctx.chips
    return 100.0 * ctx.flops_per_token * rate / ctx.peak["bf16_flops_per_s"]
