"""Mean host milliseconds from a request's ``submit`` to its admission by
EDF: the sum of ``wait_us_sum`` over the sum of ``rows``, over the
program's ``coca.tick.classify`` spans."""

from bench.lib import program_trace


def read(ctx):
    spans = program_trace.read(ctx.trace).named("coca.tick.classify")
    rows = sum(s.counters["rows"] for s in spans)
    if not rows:
        return None
    return sum(s.counters["wait_us_sum"] for s in spans) / rows / 1e3
