"""The Mamba-2 SSD scan against its roofline: the least time of the
window's scans (one per Mamba layer per backbone call; the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, from
``bench/configs/granite_h.py``) over the device time of the Pallas
kernel, the ``custom-call`` named ``ssd_scan``."""

from bench.configs import granite_h

KERNEL = "%ssd_scan"


def read(ctx):
    return granite_h.roofline_share(ctx, "ssd",
                                    lambda op: op.startswith(KERNEL))
