"""Share of the bf16 peak the whole round reaches: the FLOPs of the rounds
completed in the window (lookups, absorption, merge; from shapes) over the
window's seconds times the peak."""


def read(ctx):
    c = ctx.counters
    if not c.get("rounds"):
        return None
    f = c["rounds"] * ctx.counts.round_flops(c["K"], c["F"], c["L"], c["I"],
                                             c["d"])
    return 100.0 * f / (ctx.seconds * ctx.peaks["bf16_flops"])
