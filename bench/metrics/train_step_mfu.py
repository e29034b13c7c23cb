"""Model FLOP utilization of the traced window, in %: model FLOPs per
token (``counters.flops_per_token``: each event's gradient counted once)
times the window's tokens per second, over chips x peak bf16 FLOP/s."""


def read(ctx):
    if ctx.trace is None or not ctx.peaks or not ctx.events:
        return None
    rate = ctx.counts["flops_per_token"] * ctx.tokens / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peaks["peak_flops_bf16"])
