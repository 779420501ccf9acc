"""Share of the traced window in which no op ran on the chip while the
host was inside the program's ``coca.round`` span, or in an allocation
made outside one (``coca.round.aca``, ``coca.round.cut``): device idle
time the round caused (ACA, table cuts, stacking, dispatch, and the
transfers back in its one sync)."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.host_idle_share(
        ctx.trace, program_trace.read(ctx.trace),
        ("coca.round", *program_trace.ALLOC), ctx.lib.union)
