"""Share of the bf16 peak one granite backbone call reaches: its FLOPs
over all rows (padding included; Mamba-2 mixers, attention, router, the
held experts' routed assignments and the shared expert, counted from the
configuration's shapes by ``bench/configs/granite_h.py``) over its device
time in the trace."""

from bench.configs import granite_h


def read(ctx):
    s, n = ctx.lib.module_seconds(ctx.trace, lambda m: "bench_backbone" in m)
    if not n or s <= 0:
        return None
    flops = granite_h.cell_work()["call_flops"]
    return 100.0 * flops / (s / n) / ctx.peaks["bf16_flops"]
