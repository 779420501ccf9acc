"""Device milliseconds per backbone call of the granite cell: busy time
inside the adapter's program (``jit_bench_backbone``) over the number of
such programs run."""


def read(ctx):
    s, n = ctx.lib.module_seconds(ctx.trace, lambda m: "bench_backbone" in m)
    return 1e3 * s / n if n and s > 0 else None
