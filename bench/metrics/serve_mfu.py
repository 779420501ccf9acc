"""Share of the bf16 peak spent on frames served: FLOPs of the frames
retired in the window (padding rows excluded) over the window's seconds
times the peak."""


def read(ctx):
    f = ctx.counters.get("window_served_flops")
    if not f:
        return None
    return 100.0 * f / (ctx.seconds * ctx.peaks["bf16_flops"])
