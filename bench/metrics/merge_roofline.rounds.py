"""Merge kernel time against its roofline: the least time of the round's
Eq.-4/5 merge (the table read and written once, each upload read once)
over the kernel's device time in the trace.  The kernel is the
``custom-call`` named after its Pallas function, ``cache_merge_round``."""

KERNEL = "%cache_merge_round"


def is_merge(op: str) -> bool:
    return op.startswith(KERNEL)


def read(ctx):
    c = ctx.counters
    s, n = ctx.lib.op_seconds(ctx.trace, is_merge)
    if not n or s <= 0:
        return None
    least = ctx.counts.roofline_s(
        *ctx.counts.merge_work(c["K"], c["L"], c["I"], c["d"]), ctx.peaks)
    return 100.0 * n * least / s
