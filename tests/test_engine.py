"""The CocaCluster session API: parity against the legacy drivers, policy
swaps, variable-length streaming, per-round controllers, deprecation shims.

The headline guarantee: ``CocaCluster`` + :class:`AcaPolicy` reproduces
``run_simulation_reference`` round metrics **bit-for-bit** on the quick
world — per-frame latencies included (aggregation is order-pinned in the
canonical RoundMetrics record).
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import calibrate, run_simulation, run_simulation_reference
from repro.core.baselines import FoggyCache
from repro.core.semantic_cache import allocate_subtable

I, L, D, F, K, R = 10, 4, 16, 24, 3, 3


def _world(theta=0.05, **sim_kw):
    cache = api.CacheConfig(num_classes=I, num_layers=L, sem_dim=D,
                            theta=theta)
    sim = api.SimulationConfig(cache=cache, round_frames=F,
                               mem_budget=8_000.0, **sim_kw)
    cm = calibrate(np.linspace(2.0, 1.0, L + 1), np.full(L, D), head_cost=0.5)

    key = jax.random.PRNGKey(0)
    centroids = jax.random.normal(key, (L, I, D))

    def taps_for(labels, seed):
        k = jax.random.PRNGKey(seed)
        lab = jnp.asarray(labels)
        sems = centroids[:, lab, :].transpose(1, 0, 2) + \
            0.6 * jax.random.normal(k, (len(labels), L, D))
        logits = (jax.nn.one_hot(lab, I) * 4.0
                  + jax.random.normal(jax.random.fold_in(k, 1),
                                      (len(labels), I)))
        return sems, logits

    def tap_shared(labels):
        return taps_for(labels, 999)

    def tap_fn(r, k_, labels):
        return taps_for(labels, 7 + 13 * r + 131 * k_)

    rng = np.random.default_rng(3)
    labels = rng.integers(0, I, size=(R, K, F))
    shared = np.tile(np.arange(I), 8)
    return sim, cm, tap_shared, shared, tap_fn, labels


def _batches(tap_fn, labels, r):
    return [api.FrameBatch(*tap_fn(r, k, labels[r, k]), labels=labels[r, k])
            for k in range(labels.shape[1])]


def _drive(cluster, tap_fn, labels):
    for r in range(labels.shape[0]):
        cluster.step(_batches(tap_fn, labels, r))
    return cluster.result()


# ---------------------------------------------------------------------------
# bit-for-bit parity against the reference driver
# ---------------------------------------------------------------------------

def test_cluster_aca_matches_reference_bit_for_bit():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        ref = run_simulation_reference(sim, server, tap_fn, labels, cm, R, K)

    cluster = api.CocaCluster(sim, cm, policy=api.AcaPolicy(), server=server)
    res = _drive(cluster, tap_fn, labels)

    assert res.avg_latency == ref.avg_latency          # bitwise, not approx
    assert res.accuracy == ref.accuracy
    assert res.hit_ratio == ref.hit_ratio
    assert res.hit_accuracy == ref.hit_accuracy
    np.testing.assert_array_equal(res.per_round_latency,
                                  ref.per_round_latency)
    np.testing.assert_array_equal(res.per_round_accuracy,
                                  ref.per_round_accuracy)
    np.testing.assert_array_equal(res.exit_histogram, ref.exit_histogram)
    assert res.hit_ratio > 0                  # the case must exercise hits


def test_cluster_round_metrics_match_reference_mode_per_frame():
    """Vectorised and reference cluster modes agree per-frame, per-round."""
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    vec = api.CocaCluster(sim, cm, server=server)
    ref = api.CocaCluster(sim, cm, server=server, vectorized=False)
    for r in range(R):
        m1 = vec.step(_batches(tap_fn, labels, r))
        m2 = ref.step(_batches(tap_fn, labels, r))
        np.testing.assert_array_equal(m1.pred, m2.pred)
        np.testing.assert_array_equal(m1.hit, m2.hit)
        np.testing.assert_array_equal(m1.exit_layer, m2.exit_layer)
        np.testing.assert_array_equal(m1.latency, m2.latency)   # bitwise
        np.testing.assert_array_equal(m1.client, m2.client)


def test_run_simulation_wrapper_matches_cluster():
    from repro.core.simulation import _reset_deprecation_warnings
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    _reset_deprecation_warnings()            # the warning fires once/process
    with pytest.warns(DeprecationWarning):
        old = run_simulation(sim, server, tap_fn, labels, cm, R, K)
    res = _drive(api.CocaCluster(sim, cm, server=server), tap_fn, labels)
    assert old.avg_latency == res.avg_latency
    np.testing.assert_array_equal(old.exit_histogram, res.exit_histogram)


# ---------------------------------------------------------------------------
# baselines behind the same step() loop (policy swap only)
# ---------------------------------------------------------------------------

def test_foggycache_runs_through_cluster_step_policy_swap():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    cluster = api.CocaCluster(sim, cm, policy=api.FoggyCachePolicy())
    cluster.bootstrap(jax.random.PRNGKey(0), tap_shared, shared)
    res = _drive(cluster, tap_fn, labels)

    # the exact same engines driven directly must agree per frame
    engines = [FoggyCache(cfg=sim.cache, cm=cm, key_layer=L - 1, seed=k)
               for k in range(K)]
    lat = []
    preds = []
    for r in range(R):
        for k in range(K):
            sems, logits = tap_fn(r, k, labels[r, k])
            out = engines[k].round(np.asarray(sems), np.asarray(logits))
            lat.append(out.latency)
            preds.append(out.pred)
    direct = np.concatenate(lat)
    got = np.concatenate([m.latency for m in cluster.history])
    np.testing.assert_array_equal(got, direct)
    np.testing.assert_array_equal(
        np.concatenate([m.pred for m in cluster.history]),
        np.concatenate(preds))
    assert np.isfinite(res.avg_latency)
    assert res.server is not None          # bootstrap still attached a server


def test_engine_policy_metrics_carry_labels_and_clients():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    cluster = api.CocaCluster(sim, cm, policy=api.SMTMPolicy())
    cluster.bootstrap(jax.random.PRNGKey(0), tap_shared, shared)
    m = cluster.step(_batches(tap_fn, labels, 0))
    assert m.frames == K * F
    np.testing.assert_array_equal(m.labels, labels[0].reshape(-1))
    np.testing.assert_array_equal(m.client, np.repeat(np.arange(K), F))
    assert 0.0 <= m.accuracy <= 1.0
    assert m.exit_histogram().sum() == K * F


# ---------------------------------------------------------------------------
# variable-length / ragged streaming
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_variable_length_rounds_and_ragged_batches():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    vec = api.CocaCluster(sim, cm, server=server)
    ref = api.CocaCluster(sim, cm, server=server, vectorized=False)
    rng = np.random.default_rng(0)
    sizes = [(20, 20, 20), (12, 12, 12), (9, 17, 5)]   # last round: ragged
    for r, fs in enumerate(sizes):
        batches = []
        for k, f in enumerate(fs):
            lab = rng.integers(0, I, size=f)
            sems, logits = tap_fn(10 + r, k, lab)
            batches.append((sems, logits, lab))        # plain-triple input
        m1 = vec.step(batches)
        m2 = ref.step(batches)
        assert m1.frames == sum(fs)
        np.testing.assert_array_equal(m1.pred, m2.pred)
        np.testing.assert_array_equal(m1.latency, m2.latency)
    r1, r2 = vec.result(), ref.result()
    assert r1.avg_latency == r2.avg_latency
    np.testing.assert_array_equal(r1.exit_histogram, r2.exit_histogram)


def test_max_history_bounds_retention_without_changing_result():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    full = _drive(api.CocaCluster(sim, cm, server=server), tap_fn, labels)
    bounded_cluster = api.CocaCluster(sim, cm, server=server, max_history=1)
    bounded = _drive(bounded_cluster, tap_fn, labels)
    assert len(bounded_cluster.history) == 1     # only the last round kept
    assert bounded.avg_latency == full.avg_latency
    np.testing.assert_array_equal(bounded.per_round_latency,
                                  full.per_round_latency)
    np.testing.assert_array_equal(bounded.exit_histogram,
                                  full.exit_histogram)


# ---------------------------------------------------------------------------
# per-round controllers
# ---------------------------------------------------------------------------

def test_slo_theta_controller_lowers_theta_under_pressure():
    sim, cm, tap_shared, shared, tap_fn, labels = _world(theta=0.3)
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    # impossible per-frame SLO -> attainment 0 -> theta must come down
    cluster = api.CocaCluster(sim, cm, server=server,
                              theta_policy=api.SLOTheta(slo_latency=1e-9))
    _drive(cluster, tap_fn, labels)
    assert cluster.sim.cache.theta < 0.3

    # infinitely generous SLO -> theta drifts up (spend slack on accuracy)
    cluster2 = api.CocaCluster(sim, cm, server=server,
                               theta_policy=api.SLOTheta(slo_latency=1e9))
    _drive(cluster2, tap_fn, labels)
    assert cluster2.sim.cache.theta >= 0.3


def test_adaptive_absorption_recalibrates_thresholds():
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    cluster = api.CocaCluster(sim, cm,
                              absorption_policy=api.AdaptiveAbsorption())
    cluster.bootstrap(jax.random.PRNGKey(0), tap_shared, shared)
    before = cluster.sim.absorb
    res = _drive(cluster, tap_fn, labels)
    after = cluster.sim.absorb
    assert after != before                      # thresholds were re-derived
    assert after.beta == before.beta            # decay is not the target
    assert np.isfinite(res.avg_latency)
    assert res.accuracy > 0.5

# ---------------------------------------------------------------------------
# the round's own batched cut against the caller's per-client tables
# ---------------------------------------------------------------------------

def _with_entry_dtype(sim, dtype):
    return dataclasses.replace(
        sim, cache=dataclasses.replace(sim.cache, entry_dtype=dtype))


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_step_own_cut_matches_caller_cut_bit_for_bit(dtype):
    """Rounds that cut their own tables (one batched cut) serve and merge
    bit for bit as rounds handed ``allocate_tables()``'s list."""
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    sim = _with_entry_dtype(sim, dtype)
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    own = api.CocaCluster(sim, cm, server=server, num_clients=K)
    caller = api.CocaCluster(sim, cm, server=server, num_clients=K)
    for r in range(R):
        m1 = own.step(_batches(tap_fn, labels, r))
        m2 = caller.step(_batches(tap_fn, labels, r),
                         tables=caller.allocate_tables())
        for f in ("pred", "hit", "exit_layer", "latency"):
            np.testing.assert_array_equal(getattr(m1, f), getattr(m2, f))
    assert own.result().hit_ratio > 0
    _assert_trees_equal(own.server, caller.server)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_active_subset_cut_matches_separate_cuts(dtype):
    """After ``remove_client`` the batched cut covers only the active slots,
    each table bitwise the one ``allocate_subtable`` cuts from that
    client's own allocation."""
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    sim = _with_entry_dtype(sim, dtype)
    cluster = api.CocaCluster(sim, cm, policy=api.AcaPolicy(), num_clients=5)
    cluster.bootstrap(jax.random.PRNGKey(0), tap_shared, shared)
    cluster.step([api.FrameBatch(*tap_fn(0, k, labels[0, k % K]),
                                 labels=labels[0, k % K]) for k in range(5)])
    cluster.remove_client(1)
    cluster.remove_client(3)
    tables = cluster.allocate_tables()
    assert cluster.active_clients == [0, 2, 4] and len(tables) == 3
    entries = cluster.gathered_entries()
    for k, t in zip(cluster.active_clients, tables):
        x = api.AcaPolicy().allocate(cluster.allocation_context(k))
        alone = allocate_subtable(entries, jnp.asarray(x), entry_dtype=dtype)
        assert (t.entry_scale is None) == (dtype == "float32")
        _assert_trees_equal(alone, t)


# ---------------------------------------------------------------------------
# serving-path table unification
# ---------------------------------------------------------------------------

def test_allocate_serving_table_matches_cluster_allocation():
    from repro.serving.engine import allocate_serving_table
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    cluster = api.CocaCluster(sim, cm, num_clients=1)
    cluster.bootstrap(jax.random.PRNGKey(0), tap_shared, shared)
    t_cluster = cluster.allocate_tables()[0]
    t_serving = allocate_serving_table(
        cluster.server, api.AcaPolicy(), sim.cache, cm,
        mem_budget=sim.mem_budget, round_frames=sim.round_frames)
    np.testing.assert_array_equal(np.asarray(t_cluster.class_mask),
                                  np.asarray(t_serving.class_mask))
    np.testing.assert_array_equal(np.asarray(t_cluster.layer_mask),
                                  np.asarray(t_serving.layer_mask))
    np.testing.assert_array_equal(np.asarray(t_cluster.entries),
                                  np.asarray(t_serving.entries))


def test_simulate_metrics_consumes_round_records():
    from repro.serving.batching import BatchingConfig, simulate_metrics
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    cluster = api.CocaCluster(sim, cm, server=server)
    _drive(cluster, tap_fn, labels)
    stats = simulate_metrics(cluster.history,
                             BatchingConfig(num_blocks=L + 1, max_slots=4))
    assert stats.requests == R * K * F
    assert stats.throughput_gain > 1.0          # early exits must help
    # a single RoundMetrics record (not wrapped in a list) works too
    one = simulate_metrics(cluster.history[0],
                           BatchingConfig(num_blocks=L + 1, max_slots=4))
    assert one.requests == K * F


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_old_entry_points_warn_but_work():
    from repro.core.simulation import _reset_deprecation_warnings
    sim, cm, tap_shared, shared, tap_fn, labels = _world()
    server = api.bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared,
                                  shared, cm)
    _reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning):
        run_simulation(sim, server, tap_fn, labels, cm, 1, K)

    import repro.core.baselines as bl
    import repro.core.policies as pol
    import repro.core.simulation as sim_mod
    for mod, name in ((bl, "RoundResult"), (pol, "PolicyRoundResult"),
                      (sim_mod, "RoundMetrics")):
        with pytest.warns(DeprecationWarning):
            alias = getattr(mod, name)
        assert alias is api.RoundMetrics
