"""The trace reduction against a small trace recorded on a v5e.

``data/probe.xplane.pb`` was written by ``record_probe.py``: inside one
``bench.window`` span, five calls of a jitted ``bench_probe`` (a 2048^2
bfloat16 matmul), each in a ``bench.step`` span and followed by a 20 ms
``bench.sleep`` span.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_trace.py
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import trace

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    if not PROBE.exists():
        pytest.skip("no recorded probe trace")
    return trace.read(str(PROBE))


def test_window_and_devices(tr):
    assert len(tr.devices) == 1
    assert 0.1 < tr.window_s < 1.0           # 5 x (20 ms sleep + a call)


def test_probe_programs(tr):
    """Five calls ran; in this recording the device's timeline leads the
    host spans by about 1.1 ms, so the first call (0.1 ms, launched as the
    window opened) falls before the window's start and is left out."""
    s, n = trace.module_seconds(tr, lambda m: "bench_probe" in m)
    assert n == 4
    assert 0 < s <= trace.busy_s(tr)


def test_busy_is_the_union_of_ops(tr):
    busy = trace.busy_s(tr)
    ops = sorted((a, b) for d in tr.devices for _, a, b in d.ops)
    covered = 0
    end = tr.t0
    for a, b in ops:                        # a plain sweep, independently
        a = max(a, end)
        if b > a:
            covered += b - a
            end = b
    assert busy == pytest.approx(covered / 1e9)
    assert 0 < busy < tr.window_s


def test_idle_gaps_add_up_and_fall_in_sleep(tr):
    gaps = dict(trace.idle_gaps(tr, k=100))
    idle = sum(gaps.values())
    assert idle == pytest.approx(tr.window_s - trace.busy_s(tr), rel=1e-9)
    assert max(gaps, key=gaps.get) == "bench.sleep"
    assert gaps["bench.sleep"] > 0.09        # five 20 ms sleeps


def test_top_ops_sum_to_busy_or_more(tr):
    top = trace.top_ops(tr, k=1000)
    assert sum(v for _, v in top) >= trace.busy_s(tr) * (1 - 1e-9)
