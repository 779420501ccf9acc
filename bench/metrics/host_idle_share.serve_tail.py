"""Share of the traced window in which no op ran on the chip while the
host was inside the program's ``coca.tick`` span: device idle time the
tick caused (admission, the dispatches, the transfers back in its one
sync, the retire bookkeeping), as against waiting for arrivals."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.host_idle_share(
        ctx.trace, program_trace.read(ctx.trace), ("coca.tick",),
        ctx.lib.union)
