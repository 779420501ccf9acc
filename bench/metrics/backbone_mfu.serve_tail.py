"""Share of the bf16 peak one backbone call reaches: its FLOPs over all
rows (padding included, counted from the configuration's shapes) over its
device time in the trace."""


def read(ctx):
    name = ctx.counters.get("backbone_module")
    if not name:
        return None
    s, n = ctx.lib.module_seconds(ctx.trace, lambda m: name in m)
    if not n or s <= 0:
        return None
    per_call = s / n
    return 100.0 * ctx.counters["call_flops"] / per_call / ctx.peaks[
        "bf16_flops"]
