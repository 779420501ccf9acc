"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.lib.busy_s(ctx.trace) / ctx.trace.window_s)
