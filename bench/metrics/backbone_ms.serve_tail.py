"""Device milliseconds per backbone call: busy time inside the adapter's
program (``jit_bench_backbone``) over the number of such programs run."""


def read(ctx):
    name = ctx.counters.get("backbone_module")
    if not name:
        return None
    s, n = ctx.lib.module_seconds(ctx.trace, lambda m: name in m)
    return 1e3 * s / n if n and s > 0 else None
