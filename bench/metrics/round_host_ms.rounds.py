"""Median host milliseconds of a collaborative round, not counting its wait
for the device: over the program's vectorised ``coca.round`` spans (those
holding a ``coca.round.sync``), the duration less that sync, plus any
allocation made for the round before it (``program_trace.round_parts``)."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.median_ms([
        p["host"] for p in program_trace.round_parts(
            program_trace.read(ctx.trace))])
