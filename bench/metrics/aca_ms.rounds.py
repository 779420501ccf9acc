"""Median milliseconds of a round's ACA allocation (numpy, every client):
over the program's vectorised ``coca.round`` spans, the summed
``coca.round.aca`` spans made for the round (``program_trace.round_parts``)."""

from bench.lib import program_trace


def read(ctx):
    return program_trace.median_ms([
        p["aca"] for p in program_trace.round_parts(
            program_trace.read(ctx.trace))])
