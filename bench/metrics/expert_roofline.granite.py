"""The held experts' matmuls against their roofline: the least time of
the window's held-expert work (one per layer per backbone call: the held
weights once, the rows routed to them in and out once; FLOPs for the
assignments that land on held experts, from
``bench/configs/granite_h.py``) over the device time of the grouped
matmuls, the ``ragged-dot`` custom calls, which only the held-expert
layer uses."""

from bench.configs import granite_h

OP = "%ragged-dot"


def read(ctx):
    return granite_h.roofline_share(ctx, "experts",
                                    lambda op: op.startswith(OP))
