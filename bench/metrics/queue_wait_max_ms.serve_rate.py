"""Longest host milliseconds any request admitted in the window waited from
``submit`` to its admission by EDF: the largest ``wait_us_max`` over the
program's ``coca.tick.classify`` spans (a host stall shows here)."""

from bench.lib import program_trace


def read(ctx):
    spans = program_trace.read(ctx.trace).named("coca.tick.classify")
    if not spans:
        return None
    return max(s.counters["wait_us_max"] for s in spans) / 1e3
