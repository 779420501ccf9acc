"""Median milliseconds of a round's table cuts (``allocate_subtable`` of
every client's allocation): over the program's vectorised ``coca.round``
spans, the summed ``coca.round.cut`` spans made for the round
(``program_trace.round_parts``)."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.median_ms([
        p["cut"] for p in program_trace.round_parts(
            program_trace.read(ctx.trace))])
