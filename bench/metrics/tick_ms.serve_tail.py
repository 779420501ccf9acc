"""Median host milliseconds of a serving tick that admitted work: EDF
admission, the backbone adapter, the tick's lookup and its one bundled
device_get, and the slot bookkeeping (the benchmark's span around
``ServingSession.tick``)."""

import numpy as np


def read(ctx):
    ms = ctx.counters.get("tick_ms_admit")
    return float(np.median(ms)) if ms else None
