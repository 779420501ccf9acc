"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole run of a cell at test size on the CPU (the chip
check skipped), with the timed path broken underneath by one of
``bench/faults.py``'s faults, and sees ``correct`` come out false; the first
test sees a sound run come out true.  The faults are those a cell can have:
an answer altered where it is produced, half of the batch left out, a step
that returns its state unchanged.  (No cell spans chips, so none can lose an
exchange between them.)

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import pytest

from bench import faults, run
from bench.lib import env
from bench.tests.tiny import tiny

SEED = 2**31 + 12345


@pytest.fixture(autouse=True)
def program_on_path():
    env.prepare()


def run_tiny(name: str) -> dict:
    return run.run_cell(name, SEED, 1.5, False, device_check=False,
                        loaded=tiny(run.load_cell(name)))


@pytest.mark.parametrize("name", ["ast-esc50-serve", "ast-esc50-rounds",
                                  "phi3v-serve-closed"])
def test_sound_run_is_correct(name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]


def _fails(monkeypatch, fault: str, cell: str, number: str | None) -> None:
    faults.FAULTS[fault][1](monkeypatch.setattr)
    out = run_tiny(cell)
    assert not out["correct"]
    if number is not None:
        c = out["checks"][number]
        assert c["value"] > c["limit"], out["checks"]


def test_serve_altered_answer(monkeypatch):
    _fails(monkeypatch, "serve_altered_answer", "ast-esc50-serve",
           "lookup_gap")


def test_serve_half_batch_left_out(monkeypatch):
    _fails(monkeypatch, "serve_half_batch", "phi3v-serve-closed", "tap_err")


def test_serve_profile_shifted(monkeypatch):
    _fails(monkeypatch, "serve_profile_shifted", "ast-esc50-serve", "r_gap")


def test_rounds_altered_answer(monkeypatch):
    _fails(monkeypatch, "rounds_altered_answer", "ast-esc50-rounds", None)


def test_rounds_state_unchanged(monkeypatch):
    _fails(monkeypatch, "rounds_state_unchanged", "ast-esc50-rounds",
           "server_gap")


def test_rounds_half_batch_left_out(monkeypatch):
    _fails(monkeypatch, "rounds_half_batch", "ast-esc50-rounds", None)
