"""cocalint's runtime sanitizer harness against the real engine/serving
paths: transfer-guard scopes prove the jitted round and the serving tick
perform no *implicit* host<->device transfers (the bundled explicit
``device_get`` stays legal), the recompilation sentinel pins "exactly one
compile per distinct shape" across rounds and serving windows, and the
checkify debug mode sees NaNs through the fused Pallas lookup.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AcaPolicy, CacheConfig, CocaCluster, FrameBatch,
                        SimulationConfig, calibrate)
from repro.core import engine as engine_mod
from repro.data import (PoissonArrivals, RequestStream, StreamConfig,
                        make_tap_model, perturb_tap_model, synthesize_taps)
from repro.serving import loop as loop_mod
from repro.serving.batching import BatchingConfig
from repro.serving.loop import ServeLoopConfig, ServingSession
from tools.cocalint.sanitize import (checked_lookup, no_implicit_transfers,
                                     sentinel_batched_lookup,
                                     sentinel_round_stack,
                                     sentinel_round_step, sentinel_table_cut)

I, L, D, F = 12, 4, 16, 40
NB = L + 1


@pytest.fixture(scope="module")
def world():
    scfg = StreamConfig(num_classes=I, num_layers=L, sem_dim=D)
    tm = make_tap_model(jax.random.PRNGKey(0), scfg)
    tm_cal = perturb_tap_model(jax.random.PRNGKey(42), tm, 0.3)
    cm = calibrate(np.full(NB, 5.0), np.full(L, D), head_cost=1.0)
    shared = np.tile(np.arange(I), 10)

    def make_cluster(theta=0.08, **kw):
        cache = CacheConfig(num_classes=I, num_layers=L, sem_dim=D,
                            theta=theta)
        sim = SimulationConfig(cache=cache, round_frames=F,
                               mem_budget=float(8 * I * D))
        kw.setdefault("policy", AcaPolicy())
        cluster = CocaCluster(sim, cm, **kw)
        cluster.bootstrap(
            jax.random.PRNGKey(0),
            lambda lab: synthesize_taps(jax.random.PRNGKey(1), tm_cal,
                                        jnp.asarray(lab), scfg),
            shared)
        return cluster

    def taps_for(labels, seed=5):
        return synthesize_taps(jax.random.PRNGKey(seed), tm,
                               jnp.asarray(labels), scfg)

    return make_cluster, taps_for


def _round_batches(taps_for, num_clients, round_index):
    rng = np.random.default_rng(
        np.random.SeedSequence((99, round_index)))
    out = []
    for k in range(num_clients):
        labels = rng.integers(0, I, F).astype(np.int64)
        sems, logits = taps_for(labels, seed=10 + round_index * 7 + k)
        out.append(FrameBatch(sems, logits, labels))
    return out


def _serving_cfg(**kw):
    kw.setdefault("batching", BatchingConfig(num_blocks=NB, max_slots=8,
                                             lookup_tick_fraction=0.02))
    kw.setdefault("windows", 3)
    kw.setdefault("window_ticks", 16)
    kw.setdefault("slo_ticks", 24.0)
    return ServeLoopConfig(**kw)


def _session(cluster, taps_for, tap_fn=None, **kw):
    stream = RequestStream(num_classes=I, arrivals=PoissonArrivals(rate=2.0),
                           seed=3)
    if tap_fn is None:
        def tap_fn(window, labels):
            return taps_for(labels, seed=1000 + window)

    return ServingSession(cluster, _serving_cfg(**kw.pop("cfg_kw", {})),
                          stream, tap_fn, **kw)


def _admitted(res):
    return sum(w.admitted for w in res.windows)


# ---------------------------------------------------------------------------
# Transfer guard: no implicit transfers in the hot paths
# ---------------------------------------------------------------------------


def test_engine_rounds_run_under_transfer_guard(world):
    """Steady-state rounds (vmapped client round -> Eq.-4/5 merges -> ONE
    bundled explicit device_get) perform no implicit transfer.  Round 0
    runs unguarded: the cluster's lazy client-state init and the jit
    compile legitimately materialise host constants once."""
    make_cluster, taps_for = world
    cluster = make_cluster(num_clients=2)
    rounds = [_round_batches(taps_for, 2, r) for r in range(3)]
    cluster.step(rounds[0])             # warm-up: one-time init + compile
    with no_implicit_transfers():
        for batches in rounds[1:]:
            m = cluster.step(batches)
    assert len(m.pred) == 2 * F


def test_caller_cut_rounds_run_under_transfer_guard(world):
    """Rounds handed the caller's tables (the fault and topology layers'
    ``step(tables=allocate_tables())``) cut and stack them with no implicit
    transfer either."""
    make_cluster, taps_for = world
    cluster = make_cluster(num_clients=2)
    rounds = [_round_batches(taps_for, 2, r) for r in range(3)]
    cluster.step(rounds[0], tables=cluster.allocate_tables())
    with no_implicit_transfers():
        for batches in rounds[1:]:
            m = cluster.step(batches, tables=cluster.allocate_tables())
    assert len(m.pred) == 2 * F


def test_serving_session_runs_under_transfer_guard(world):
    """A full multi-window online session — admission, the jitted tick
    lookup, Θ control, between-window re-allocation — with implicit
    transfers disallowed.  The tap_fn hands back *host* arrays (an edge
    client's tensors), so every h2d/d2h in the tick must be the session's
    own explicit asarray/bundled device_get."""
    make_cluster, taps_for = world
    # Per-class prototype taps, materialised on host OUTSIDE the guard —
    # inside it, only the session moves data.
    sems_all, logits_all = taps_for(np.arange(I))
    sems_all, logits_all = np.asarray(sems_all), np.asarray(logits_all)

    def host_tap_fn(_w, lab):
        idx = np.asarray(lab, dtype=np.int64)
        return sems_all[idx], logits_all[idx]

    session = _session(make_cluster(num_clients=1), taps_for,
                       tap_fn=host_tap_fn)
    with no_implicit_transfers():
        res = session.run()
    assert res.arrivals > 0 and res.served > 0


@pytest.mark.no_implicit_transfers
def test_marker_applies_guard_for_the_whole_test():
    """The plugin's autouse fixture wraps marked tests in the guard: an
    implicit transfer (eager basic indexing materialises host index
    scalars) raises without any explicit context manager here."""
    with pytest.raises(Exception, match="[Dd]isallow"):
        jnp.zeros(3)[:2]


def test_guard_still_catches_a_smuggled_numpy_operand(world):
    """Sanity: the guard has teeth — an np array leaking into a jitted
    call inside the scope raises."""
    f = jax.jit(lambda x: x + 1)
    f(jnp.zeros(4))                    # compile outside the guard
    with pytest.raises(Exception, match="[Dd]isallow"):
        with no_implicit_transfers():
            f(np.zeros(4))


# ---------------------------------------------------------------------------
# Recompilation sentinel: one compile per distinct shape
# ---------------------------------------------------------------------------


def test_round_step_compiles_once_across_rounds(world, monkeypatch):
    make_cluster, taps_for = world
    counted, counter = sentinel_round_step()
    monkeypatch.setattr(engine_mod, "round_step", counted)
    cluster = make_cluster(num_clients=2)
    for r in range(3):
        cluster.step(_round_batches(taps_for, 2, r))
    assert counter.traces == 1          # 3 identical-shape rounds, 1 compile
    counter.assert_one_compile_per_shape()


def test_round_step_retraces_only_on_new_active_count(world, monkeypatch):
    make_cluster, taps_for = world
    counted, counter = sentinel_round_step()
    monkeypatch.setattr(engine_mod, "round_step", counted)
    cluster = make_cluster(num_clients=2)
    cluster.step(_round_batches(taps_for, 2, 0))
    cluster.add_client()                # K: 2 -> 3, a genuinely new shape
    cluster.step(_round_batches(taps_for, 3, 1))
    cluster.step(_round_batches(taps_for, 3, 2))
    assert counter.traces == 2
    counter.assert_one_compile_per_shape()


@pytest.mark.parametrize("name, sentinel", [
    ("allocate_subtables", sentinel_table_cut),
    ("_stack", sentinel_round_stack)])
def test_round_preparation_compiles_once_per_active_count(world, monkeypatch,
                                                          name, sentinel):
    """The round's batched cut and its stack of taps and logits trace once
    across same-shape rounds, and once more when ``add_client`` changes K."""
    make_cluster, taps_for = world
    counted, counter = sentinel()
    monkeypatch.setattr(engine_mod, name, counted)
    cluster = make_cluster(num_clients=2)
    for r in range(3):
        cluster.step(_round_batches(taps_for, 2, r))
    assert counter.traces == 1
    cluster.add_client()
    for r in range(3, 5):
        cluster.step(_round_batches(taps_for, 3, r))
    assert counter.traces == 2
    counter.assert_one_compile_per_shape()


def test_serving_lookup_compiles_once_with_frozen_theta(world, monkeypatch):
    """Fixed max_slots padding + frozen Θ: the whole multi-window session
    (re-allocating its table every window) re-hits one compiled trace."""
    make_cluster, taps_for = world
    counted, counter = sentinel_batched_lookup()
    monkeypatch.setattr(loop_mod, "_batched_lookup", counted)
    session = _session(make_cluster(num_clients=1), taps_for,
                       cfg_kw=dict(adapt_theta=False))
    res = session.run()
    assert _admitted(res) > 0
    assert counter.traces == 1
    counter.assert_one_compile_per_shape()


def test_serving_lookup_compiles_once_per_quantised_theta(world, monkeypatch):
    """With Θ adaptation on, every compile is explained by a distinct
    (shape, quantised Θ) signature — adaptation must not retrace-storm."""
    make_cluster, taps_for = world
    counted, counter = sentinel_batched_lookup()
    monkeypatch.setattr(loop_mod, "_batched_lookup", counted)
    session = _session(make_cluster(num_clients=1), taps_for,
                       cfg_kw=dict(windows=4, target=0.5))
    res = session.run()
    assert _admitted(res) > 0
    counter.assert_one_compile_per_shape()
    assert counter.traces <= len(set(res.theta_trace)) + 1  # + drain Θ


# ---------------------------------------------------------------------------
# Checkify debug mode: NaN/OOB checks through the fused Pallas lookup
# ---------------------------------------------------------------------------


def _serving_table_and_taps(world):
    make_cluster, taps_for = world
    cluster = make_cluster(num_clients=1)
    table = cluster.serving_table()
    labels = np.arange(8) % I
    sems, _ = taps_for(labels)
    return cluster, table, jnp.asarray(sems)


def test_checked_lookup_clean_table_passes(world):
    cluster, table, sems = _serving_table_and_taps(world)
    out = checked_lookup(table, sems, cluster.sim.cache, impl="fused")
    ref = loop_mod.lookup_all_layers(table, sems, cluster.sim.cache,
                                     impl="fused")
    np.testing.assert_array_equal(np.asarray(out.hit), np.asarray(ref.hit))
    np.testing.assert_array_equal(np.asarray(out.exit_layer),
                                  np.asarray(ref.exit_layer))


def test_checked_lookup_catches_nan_poisoned_table(world):
    cluster, table, sems = _serving_table_and_taps(world)
    poisoned = table._replace(
        entries=table.entries.at[0, 0, 0].set(jnp.nan))
    with pytest.raises(Exception, match="nan"):
        checked_lookup(poisoned, sems, cluster.sim.cache, impl="fused")


def test_debug_mode_is_transparent_for_a_clean_session(world, monkeypatch):
    """--cocalint-debug reroutes the tick lookup through checkify; on a
    clean world the session's outcome is bit-identical."""
    make_cluster, taps_for = world
    base = _session(make_cluster(num_clients=1), taps_for).run()

    def checked(table, sems, cfg):
        return checked_lookup(table, sems, cfg, impl="auto")

    monkeypatch.setattr(loop_mod, "_batched_lookup", checked)
    dbg = _session(make_cluster(num_clients=1), taps_for).run()
    np.testing.assert_array_equal(dbg.exit_blocks, base.exit_blocks)
    assert dbg.hit_ratio == base.hit_ratio
    assert dbg.theta_trace == base.theta_trace
