"""Cells cut to a size the CPU runs in seconds, for the harness's tests.

Only the shapes shrink; the drivers, the reference and the comparison are
the ones the chip runs."""

from __future__ import annotations

import copy

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "kv_heads": 4,
              "d_ff": 128, "vocab_size": 512, "frontend_len": 8,
              "sem_dim": 32, "num_classes": 10, "tap_every": 1}


def tiny(loaded: dict, **workload) -> dict:
    """A copy of ``run.load_cell``'s result at test size."""
    ld = copy.deepcopy(loaded)
    ld["config"]["model"].update(TINY_MODEL)
    tr = ld["traffic"]
    if tr["kind"] == "rounds":
        tr.update(clients=3, frames=40, label_rounds=64)
        ld["workload"].update(accuracy_rounds=5)
    else:
        tr.update(shared_per_class=4, max_per_stream=256)
        ld["workload"].update(sample=10_000, ref_block=8)   # every one
        if "rate" in ld["workload"]:
            ld["workload"]["rate"] = 40.0
    ld["workload"].update(workload)
    return ld
