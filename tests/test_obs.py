"""Host spans of the serving tick and the collaborative round.

A tiny :class:`ServingSession` and a tiny :class:`CocaCluster` run under
``jax.profiler.trace``; the trace is read back with ``ProfileData`` and
held to :mod:`repro.obs`'s list of spans: every span appears, children sit
inside their parents, the counters count what the program did, and the
results are bit-identical with the profiler on and off.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import (AcaPolicy, CacheConfig, CocaCluster, FrameBatch,
                        SimulationConfig, calibrate)
from repro.data import (StreamConfig, make_tap_model, perturb_tap_model,
                        synthesize_taps)
from repro.serving.batching import BatchingConfig
from repro.serving.loop import ServeLoopConfig, ServingSession

I, L, D = 12, 4, 16
NB = L + 1
K, F, ROUNDS = 3, 40, 3
DOCUMENTED = set(re.findall(r"``(coca\.[a-z.]*[a-z])``", obs.__doc__))
SERVE_SPANS = {s for s in DOCUMENTED if s.startswith("coca.tick")}
ROUND_SPANS = {s for s in DOCUMENTED if s.startswith("coca.round")}


@pytest.fixture(scope="module")
def world():
    scfg = StreamConfig(num_classes=I, num_layers=L, sem_dim=D)
    tm = make_tap_model(jax.random.PRNGKey(0), scfg)
    tm_cal = perturb_tap_model(jax.random.PRNGKey(42), tm, 0.3)
    cm = calibrate(np.full(NB, 5.0), np.full(L, D), head_cost=1.0)
    shared = np.tile(np.arange(I), 10)

    def make_cluster(num_clients):
        cache = CacheConfig(num_classes=I, num_layers=L, sem_dim=D,
                            theta=0.08)
        sim = SimulationConfig(cache=cache, round_frames=F,
                               mem_budget=float(8 * I * D))
        cluster = CocaCluster(sim, cm, policy=AcaPolicy(),
                              num_clients=num_clients)
        cluster.bootstrap(
            jax.random.PRNGKey(0),
            lambda lab: synthesize_taps(jax.random.PRNGKey(1), tm_cal,
                                        jnp.asarray(lab), scfg),
            shared)
        return cluster

    def taps(labels, seed):
        return synthesize_taps(jax.random.PRNGKey(seed), tm,
                               jnp.asarray(labels), scfg)

    return make_cluster, taps


def read_spans(path) -> list[tuple[str, float, float, dict]]:
    """The ``coca.*`` host events of the one trace under ``path``:
    ``(name, start_ns, end_ns, counters)`` in start order."""
    (f,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(f).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("coca."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def children(parent, spans, name):
    _, a, b, _ = parent
    return [s for s in spans if s[0] == name and a <= s[1] and s[2] <= b]


def named(spans, name):
    return [s for s in spans if s[0] == name]


# ---------------------------------------------------------------- serving
def serve(world, use_cache: bool):
    """A session fed 20 requests in two bursts, ticked until empty; returns
    the session and what it served."""
    make_cluster, taps = world

    def tap_fn(_w, labels):
        return taps(labels, seed=int(labels.sum()))

    cfg = ServeLoopConfig(
        batching=BatchingConfig(num_blocks=NB, max_slots=4), windows=1,
        window_ticks=64, slo_ticks=1e9, adapt_theta=False, reallocate=False)
    s = ServingSession(make_cluster(1), cfg, None, tap_fn,
                       use_cache=use_cache).start()
    s.begin_window(0)
    labels = np.arange(20) % I
    retired = []
    for lab in labels[:12]:
        s.submit(int(lab))
    for t in range(40):
        if t == 3:
            for lab in labels[12:]:
                s.submit(int(lab))
        retired += [(r.rid, lat, miss) for r, lat, miss in s.tick(0)]
        if not s.backlog() and t > 3:
            break
    return s, {"retired": retired, "pred": dict(s._pred_by_rid),
               "exit_blocks": s.report().exit_blocks}


@pytest.mark.parametrize("use_cache", [True, False])
def test_tick_spans_nest_and_count(world, tmp_path, use_cache):
    with jax.profiler.trace(str(tmp_path)):
        session, served = serve(world, use_cache)
    spans = read_spans(str(tmp_path))
    names = {s[0] for s in spans}
    want = SERVE_SPANS - ({"coca.tick.lookup"} if not use_cache else set())
    assert names == want

    ticks = named(spans, "coca.tick")
    assert [s[3]["tick"] for s in ticks] == list(range(len(ticks)))
    admitting = [t for t in ticks if children(t, spans, "coca.tick.classify")]
    assert admitting and len(admitting) < len(ticks)
    for t in ticks:
        for child in ("coca.tick.admit", "coca.tick.retire"):
            assert len(children(t, spans, child)) == 1, (t, child)
    for t in admitting:
        (cls,) = children(t, spans, "coca.tick.classify")
        assert len(children(t, spans, "coca.tick.sync")) == 1
        assert len(children(cls, spans, "coca.tick.sync")) == 1
        assert len(children(cls, spans, "coca.tick.backbone")) == 1
        assert (len(children(cls, spans, "coca.tick.lookup"))
                == int(use_cache))
    classify = named(spans, "coca.tick.classify")
    assert len(classify) == len(admitting)
    assert sum(s[3]["rows"] for s in classify) == session.admitted == 20
    for s in classify:
        c = s[3]
        assert 0 <= c["wait_us_max"] <= c["wait_us_sum"]
        assert c["wait_us_sum"] <= c["rows"] * c["wait_us_max"]
    # the second burst queued behind the first: it waited for slots
    assert max(s[3]["wait_us_max"] for s in classify) > 0
    assert not session._submit_ns          # every stamp popped at admission


@pytest.mark.parametrize("use_cache", [True, False])
def test_session_bit_identical_with_profiler(world, tmp_path, use_cache):
    _, off = serve(world, use_cache)
    with jax.profiler.trace(str(tmp_path)):
        _, on = serve(world, use_cache)
    assert on["retired"] == off["retired"]
    assert on["pred"] == off["pred"]
    np.testing.assert_array_equal(on["exit_blocks"], off["exit_blocks"])


# ------------------------------------------------------------------ round
def rounds(world, cut_first: bool = False):
    """ROUNDS rounds of K clients; with ``cut_first`` the caller cuts the
    tables (``allocate_tables``) and hands them to ``step``."""
    make_cluster, taps = world
    cluster = make_cluster(K)
    rng = np.random.default_rng(np.random.SeedSequence((7,)))
    out = []
    for r in range(ROUNDS):
        frames = []
        for k in range(K):
            lab = rng.integers(0, I, F).astype(np.int32)
            sems, logits = taps(lab, seed=100 * r + k)
            frames.append(FrameBatch(sems, logits, lab))
        tables = cluster.allocate_tables() if cut_first else None
        m = cluster.step(frames, tables=tables)
        out.append((m.pred, m.hit, m.exit_layer))
    srv = cluster.server
    out.append(tuple(np.asarray(x) for x in jax.device_get(
        (srv.entries, srv.phi_global, srv.r_est))))
    return out


def test_round_spans_nest_and_count(world, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        rounds(world)
    spans = read_spans(str(tmp_path))
    # the bootstrap cuts no table: every span of the trace is a round's
    assert {s[0] for s in spans} == ROUND_SPANS
    rnds = named(spans, "coca.round")
    assert [s[3]["round"] for s in rnds] == list(range(ROUNDS))
    for r in rnds:
        got = children(r, spans, "coca.round.aca")
        assert [s[3]["client"] for s in got] == list(range(K))
        # one cut of all K tables per round
        assert [s[3]["clients"]
                for s in children(r, spans, "coca.round.cut")] == [K]
        for child in ("coca.round.stack", "coca.round.dispatch",
                      "coca.round.sync"):
            assert len(children(r, spans, child)) == 1, child
    for name in ROUND_SPANS - {"coca.round"}:
        assert len(named(spans, name)) == ROUNDS * (
            K if name == "coca.round.aca" else 1)


def test_round_tables_cut_by_the_caller(world, tmp_path):
    """Tables the caller cuts before ``step(tables=...)`` give their ACA
    spans (one per client) and their one cut span just before the round,
    outside it; the round itself holds none, and its results match the
    round that cuts its own tables."""
    with jax.profiler.trace(str(tmp_path)):
        outside = rounds(world, cut_first=True)
    spans = read_spans(str(tmp_path))
    assert {s[0] for s in spans} == ROUND_SPANS
    rnds = named(spans, "coca.round")
    assert len(rnds) == ROUNDS
    prev_end = -np.inf
    for r in rnds:
        for child, counter, want in (("coca.round.aca", "client",
                                      list(range(K))),
                                     ("coca.round.cut", "clients", [K])):
            assert children(r, spans, child) == []
            got = [s for s in named(spans, child)
                   if prev_end <= s[1] and s[2] <= r[1]]
            assert [s[3][counter] for s in got] == want, child
        prev_end = r[2]
    for a, b in zip(outside, rounds(world)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_round_bit_identical_with_profiler(world, tmp_path):
    off = rounds(world)
    with jax.profiler.trace(str(tmp_path)):
        on = rounds(world)
    for a, b in zip(on, off):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_documented_spans_cover_both_layers():
    assert len(SERVE_SPANS) == 7 and len(ROUND_SPANS) == 6
    assert SERVE_SPANS | ROUND_SPANS == DOCUMENTED
