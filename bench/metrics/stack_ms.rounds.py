"""Median milliseconds of a round's stacking (the clients' tables, taps and
logits into the fused round's arguments): over the program's vectorised
``coca.round`` spans, the ``coca.round.stack`` child."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.median_ms([
        p["stack"] for p in program_trace.round_parts(
            program_trace.read(ctx.trace))])
