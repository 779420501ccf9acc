"""The ``granite-h-serve-fixedmix`` cell at test size on the CPU: a sound
run is correct, the fp8 control stands well above the program, and an
altered answer fails.  Only the widths and depth shrink (one period of 10
layers stays); the driver, the reference (``bench/configs/granite_h.py``)
and the comparison are the ones the chip runs.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import copy
import importlib

import pytest

from bench import faults, run
from bench.lib import counts, env

NAME = "granite-h-serve-fixedmix"
SEED = 2**31 + 16
TINY = {"d_model": 64, "num_heads": 4, "kv_heads": 2, "head_dim": 16,
        "vocab_size": 512, "frontend_len": 8, "sem_dim": 32,
        "num_classes": 10, "attn_scale": 0.0625,
        "moe": {"num_experts": 8, "top_k": 3, "d_expert": 32,
                "d_shared": 48, "held": [0, 2]},
        "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
                "chunk": 8, "conv_bias": True}}


@pytest.fixture(autouse=True)
def program_on_path():
    env.prepare()


def tiny() -> dict:
    ld = copy.deepcopy(run.load_cell(NAME))
    ld["config"]["model"].update(TINY)
    ld["traffic"].update(shared_per_class=4)
    ld["workload"].update(sample=10_000, ref_block=8)     # every one
    # At this size a frame has 16 positions to pool over, not 512, and the
    # bfloat16 program reads tap_err 0.010-0.013 and cls_err 0.009-0.011
    # (the cell's own size: 0.0067 and 0.015-0.019); the control reads
    # 0.083 and 0.083-0.090.  Judge at limits between those readings.
    ld["workload"]["limits"].update(tap_err=0.025, cls_err=0.025)
    return ld


def test_sound_run_is_correct():
    out = run.run_cell(NAME, SEED, 1.5, False, device_check=False,
                       loaded=tiny())
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [3, SEED])
def test_control_separates(seed):
    """The control (float8 weights and activations) stands at least 3x
    above the program on the taps and on the class logits (6-8x at this
    size; the closed loop's batches, and so the sampled rows, follow the
    host's timing)."""
    ld = tiny()
    ctx = run.Context(name=NAME, seed=seed, seconds=1.0, spans=env.Spans(),
                      config=ld["config"], traffic=ld["traffic"],
                      workload=ld["workload"], counts=counts)
    cell = importlib.import_module("bench.drivers.serve").Cell(ctx)
    cell.setup()
    cell.window(1.0)
    cell.metrics()
    cell.release()
    prog, ctl = cell.check(), cell.control()
    assert ctl["tap_err"] >= 3 * prog["tap_err"], (prog, ctl)
    assert ctl["cls_err"] >= 3 * prog["cls_err"], (prog, ctl)


def test_altered_answer_fails(monkeypatch):
    faults.serve_altered_answer(monkeypatch.setattr)
    out = run.run_cell(NAME, SEED, 1.5, False, device_check=False,
                       loaded=tiny())
    assert not out["correct"]
    c = out["checks"]["lookup_gap"]
    assert c["value"] > c["limit"], out["checks"]
