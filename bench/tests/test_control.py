"""The control comes out further from the reference than the program.

The control is the plain reference put in the program's place one
precision step below the configuration's: float8 e4m3 weights for the
bfloat16 backbone of a serving cell, three bfloat16 passes for the float32
cache of the rounds cell.  At test size on the CPU its numbers must stand
at least three times above the program's on the number that separates
them; ``bench/control.py`` reads both on the chip at the cells' own sizes,
and the limits in ``bench/workloads/`` lie between those readings.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import importlib

import pytest

from bench import run
from bench.lib import counts, env
from bench.tests.tiny import tiny


def program_and_control(name: str, seed: int):
    env.prepare()
    ld = tiny(run.load_cell(name))
    ctx = run.Context(name=name, seed=seed, seconds=1.0, spans=env.Spans(),
                      config=ld["config"], traffic=ld["traffic"],
                      workload=ld["workload"], counts=counts)
    cell = importlib.import_module(
        f"bench.drivers.{ld['traffic']['kind']}").Cell(ctx)
    cell.setup()
    cell.window(1.0)
    cell.metrics()
    cell.release()
    return cell.check(), cell.control()


@pytest.mark.parametrize("name,number", [
    ("ast-esc50-serve", "tap_err"),
    ("ast-esc50-rounds", "server_gap"),
])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_separates(name, number, seed):
    prog, ctl = program_and_control(name, seed)
    assert ctl[number] >= 3 * prog[number], (prog, ctl)
