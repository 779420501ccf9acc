"""Median host milliseconds of a serving tick that admitted work, not
counting its wait for the device: over the program's ``coca.tick`` spans
that hold a ``coca.tick.classify``, the span's duration less its
``coca.tick.sync`` child (EDF admission, the backbone's and the lookup's
dispatch, the retire bookkeeping)."""

from bench.lib import program_trace


def read(ctx):
    sp = program_trace.read(ctx.trace)
    return program_trace.median_ms([
        sp.self_ns(t, "coca.tick.sync") for t in sp.named("coca.tick")
        if sp.within(t, "coca.tick.classify")])
