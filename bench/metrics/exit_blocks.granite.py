"""Mean blocks a request of the granite cell resolves to in the window
(the session's ``exit_blocks``: the exit tap + 1 on a cache hit, every
block on a miss).  In a closed loop the rate follows it."""

import numpy as np


def read(ctx):
    b = ctx.counters.get("exit_blocks")
    return float(np.mean(b)) if b is not None and len(b) else None
