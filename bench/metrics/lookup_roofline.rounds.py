"""Lookup kernel time against its roofline: the least time of the round's
lookups (the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth; the tables read once, the taps once) over the kernel's device
time in the trace.  The kernel is the
``custom-call`` named after its Pallas function,
``cache_lookup_all_layers`` (the single-pass kernel)."""

KERNEL = "%cache_lookup_all_layers"


def is_lookup(op: str) -> bool:
    return op.startswith(KERNEL)


def read(ctx):
    c = ctx.counters
    s, n = ctx.lib.op_seconds(ctx.trace, is_lookup)
    if not n or s <= 0:
        return None
    least = ctx.counts.roofline_s(
        *ctx.counts.lookup_work(c["K"], c["F"], c["L"], c["I"], c["d"]),
        ctx.peaks)
    return 100.0 * n * least / s
