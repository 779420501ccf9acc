"""Mean real requests per backbone call: ``rows`` over the program's
``coca.tick.classify`` spans (one per admitting tick, of ``max_slots``
rows per call)."""

from bench.lib import program_trace


def read(ctx):
    spans = program_trace.read(ctx.trace).named("coca.tick.classify")
    if not spans:
        return None
    return sum(s.counters["rows"] for s in spans) / len(spans)
