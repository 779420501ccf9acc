"""The readers of the program's own spans (``bench/lib/program_trace.py``).

A traced run of each cell at test size on the CPU gives a finite value for
every metric read from the program's spans and counters; the device-based
``host_idle_share.*`` reads nothing there, since a CPU trace has no device
plane, and is checked on a synthetic trace with known intervals.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_program_trace.py
"""

from __future__ import annotations

import math

import pytest

from bench import run
from bench.lib import env, program_trace, trace
from bench.tests.tiny import tiny

SEED = 2**31 + 777

SPAN_METRICS = {
    "ast-esc50-serve": ["tick_host_ms.serve_tail", "queue_wait_ms.serve_tail",
                        "queue_wait_max_ms.serve_tail",
                        "batch_rows.serve_tail"],
    "phi3v-serve-closed": ["tick_host_ms.serve_rate",
                           "queue_wait_ms.serve_rate",
                           "queue_wait_max_ms.serve_rate",
                           "batch_rows.serve_rate"],
    "ast-esc50-rounds": ["round_host_ms.rounds", "aca_ms.rounds",
                         "cut_ms.rounds", "stack_ms.rounds"],
}
DEVICE_METRICS = {"ast-esc50-serve": "host_idle_share.serve_tail",
                  "ast-esc50-rounds": "host_idle_share.rounds"}


@pytest.fixture(scope="module", params=sorted(SPAN_METRICS))
def traced(request):
    env.prepare()
    name = request.param
    return name, run.run_cell(name, SEED, 1.5, True, device_check=False,
                              loaded=tiny(run.load_cell(name)))


def test_span_metrics_are_finite(traced):
    name, out = traced
    assert out["correct"], out["checks"]
    for m in SPAN_METRICS[name]:
        v = out["metrics"][m]["value"]
        assert math.isfinite(v) and v >= 0, (m, v)
    assert DEVICE_METRICS.get(name) not in out["metrics"]


def test_counters_are_in_range(traced):
    name, out = traced
    m = out["metrics"]
    if name == "ast-esc50-rounds":
        assert (m["aca_ms.rounds"]["value"] + m["cut_ms.rounds"]["value"]
                + m["stack_ms.rounds"]["value"]
                <= m["round_host_ms.rounds"]["value"])
    else:
        tail = "serve_tail" if name == "ast-esc50-serve" else "serve_rate"
        slots = run.load_cell(name)["traffic"]["max_slots"]
        assert 1 <= m[f"batch_rows.{tail}"]["value"] <= slots
        assert m[f"tick_host_ms.{tail}"]["value"] > 0
        assert (m[f"queue_wait_ms.{tail}"]["value"]
                <= m[f"queue_wait_max_ms.{tail}"]["value"])


def test_no_program_spans_reads_nothing(tmp_path):
    """A trace without ``coca.*`` spans (a program that has none) gives
    every reader nothing to read."""
    tr = trace.Trace([], [], 0, 10)
    sp = program_trace.read(tr, tmp_path)
    assert sp.all == []
    assert program_trace.median_ms([]) is None
    assert program_trace.round_parts(sp) == []
    assert program_trace.host_idle_share(tr, sp, ("coca.tick",),
                                         trace.union) is None


def S(name, a, b, **c):
    return program_trace.Span(name, a, b, c)


def test_host_idle_share_on_known_intervals():
    """Window 0-1000 ns.  Two ticks, 100-300 and 500-900 (sync children
    250-300 and 800-880, which count like the rest of the tick).  Device
    busy 0-120, 200-260, 600-700, 850-1000.  Idle inside the ticks:
    120-200 (80) + 260-300 (40) + 500-600 (100) + 700-850 (150) = 370 of
    1000."""
    dev = trace.Device("/device:TPU:0", [("op", 0, 120), ("op", 200, 260),
                                         ("op", 600, 700),
                                         ("op", 850, 1000)], [])
    tr = trace.Trace([dev], [], 0, 1000)
    sp = program_trace.SpanIndex([
        S("coca.tick", 100, 300, tick=0), S("coca.tick.sync", 250, 300),
        S("coca.tick", 500, 900, tick=1), S("coca.tick.sync", 800, 880),
        S("coca.round", 300, 500, round=0)])
    share = program_trace.host_idle_share(tr, sp, ("coca.tick",),
                                          trace.union)
    assert share == pytest.approx(37.0)
    # a second device, busy the whole window, halves the average
    tr2 = trace.Trace([dev, trace.Device("/device:TPU:1",
                                         [("op", 0, 1000)], [])], [], 0, 1000)
    assert program_trace.host_idle_share(
        tr2, sp, ("coca.tick",), trace.union) == pytest.approx(18.5)
    # spans of several names count once where they overlap
    assert program_trace.host_idle_share(
        tr, sp, ("coca.tick", "coca.tick.sync"), trace.union) \
        == pytest.approx(37.0)
    t0, t1 = sp.named("coca.tick")
    assert sp.self_ns(t0, "coca.tick.sync") == 150
    assert sp.self_ns(t1, "coca.tick.sync") == 320
    assert sp.within(t0, "coca.round") == []


@pytest.mark.parametrize("shift", [-300, -100, 0, 100, 300])
def test_host_idle_share_holds_across_clock_offsets(shift):
    """The device's ops moved against the host spans by less than the idle
    at a tick's edges (here 400 ns before, 500 after) leave the share as
    it was: the sync's edges, where device work ends, are not counted."""
    ops = [(1000, 3000), (3100, 3500), (6000, 8500)]
    ticks = [(600, 4000), (5500, 9000)]
    syncs = [(3200, 4000), (8000, 9000)]
    dev = trace.Device("/device:TPU:0", [("op", a + shift, b + shift)
                                         for a, b in ops], [])
    tr = trace.Trace([dev], [], 0, 10_000)
    sp = program_trace.SpanIndex(
        [S("coca.tick", a, b) for a, b in ticks]
        + [S("coca.tick.sync", a, b) for a, b in syncs])
    idle = (4000 - 600) + (9000 - 5500) - 2400 - 2500
    assert program_trace.host_idle_share(tr, sp, ("coca.tick",),
                                         trace.union) \
        == pytest.approx(100 * idle / 10_000)


def test_round_parts_attribute_outside_allocation():
    """Round 0 allocates inside itself; round 1's tables were cut before
    it (aca 1100-1150, cut 1150-1300), which counts toward round 1 in
    ``aca``, ``cut`` and ``host``; round 2 has no sync (the per-client
    path) and is left out."""
    sp = program_trace.SpanIndex([
        S("coca.round", 0, 1000, round=0),
        S("coca.round.aca", 10, 60, client=0),
        S("coca.round.cut", 60, 200, client=0),
        S("coca.round.stack", 200, 300),
        S("coca.round.sync", 600, 1000),
        S("coca.round.aca", 1100, 1150, client=0),
        S("coca.round.cut", 1150, 1300, client=0),
        S("coca.round", 1400, 2000, round=1),
        S("coca.round.stack", 1400, 1500),
        S("coca.round.sync", 1700, 2000),
        S("coca.round", 2100, 2500, round=2)])
    assert program_trace.round_parts(sp) == [
        {"aca": 50, "cut": 140, "stack": 100, "host": 600},
        {"aca": 50, "cut": 150, "stack": 100, "host": 500}]
    for p in program_trace.round_parts(sp):
        assert p["aca"] + p["cut"] + p["stack"] <= p["host"]
