"""One CoCa engine: the policy-pluggable :class:`CocaCluster` session object.

The paper's system is a single loop — clients stream frames through cache
layers, the server periodically merges a 2-D global cache (Eq. 4/5) and
re-allocates per-client sub-tables (Alg. 1) — and this module is that loop's
one implementation.  Everything else (the ``run_simulation`` wrappers, the
figure scripts, the baseline studies, the serving path's table plumbing)
drives it through the same three calls:

    cluster = CocaCluster(sim, cost_model, policy=AcaPolicy())
    cluster.bootstrap(key, tap_shared, shared_labels)
    for round_frames in stream:                  # any F, even ragged per client
        metrics = cluster.step(round_frames)     # -> canonical RoundMetrics
    summary = cluster.result()                   # -> SimulationResult

Three pluggable axes:

* **Allocation policies** decide each client's cache table at round start:
  :class:`AcaPolicy` (Alg. 1), :class:`StaticPolicy` (budget-truncated fixed
  layers — the DCA-off ablation), :class:`FixedPolicy` (frozen explicit
  allocation).  The protocol is one method,
  ``allocate(ctx: AllocationContext) -> (L, I) bool``.
* **Client-engine policies** swap the whole client round for a baseline
  system (:class:`FoggyCachePolicy`, :class:`SMTMPolicy`,
  :class:`LearnedCachePolicy`, :class:`ReplacementPolicy` for LRU/FIFO/RAND)
  while the cluster keeps the loop, the data plumbing and the metrics — the
  paper's §VI comparisons as a policy swap.
* **Per-round controllers**: ``theta_policy`` adapts Θ between rounds from
  observed metrics (:class:`SLOTheta`, backed by the serving scheduler's
  ``ThetaController``); ``absorption_policy`` re-derives the Γ/Δ absorption
  thresholds from the shared validation set
  (:class:`AdaptiveAbsorption`, wiring :mod:`repro.core.adaptive_thresholds`).

The *online serving* loop (:mod:`repro.serving.loop`) drives the same
session through two window-boundary hooks instead of ``step()``:
``set_theta`` (the SLO controller's Θ verdict) and ``serving_table`` (ACA
re-allocation against the recency the request stream actually exhibited).

The round itself is decomposed into pure, jit-friendly pieces —
:func:`round_step` (vmapped client round → upload → ``lax.scan`` Eq.-4/5
merge, one device computation, one bundled ``device_get``) — plus a thin host
driver.  ``step()`` accepts variable-length frame batches: a new uniform F
just retraces, ragged per-client F falls back to the per-client reference
path (same round semantics, bit-identical metrics).  The ``mesh=`` class
sharding of the server cache (:mod:`repro.distributed.sharding`) threads
through unchanged: one all-gather per round at subtable allocation.

The cluster membership is **dynamic**: clients join (``add_client``), leave
(``remove_client`` — state retained), and rejoin with their stale status
vectors (``rejoin_client``).  Inactive slots are masked out of the round
entirely — the vectorized path gathers only active slots into the one fused
``round_step`` dispatch, so the server's Eq.-4/5 merge scan never sees an
inactive client's upload, and the active policy re-allocates for the new
membership at the next ``step()``.  Declarative dynamic worlds (concept
drift, bursts, churn schedules) live in :mod:`repro.data.scenarios`; client
*failures* route into this lifecycle via
:class:`repro.distributed.fault_tolerance.ClientChurn` — a dropped client is
churn, not a crash.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aca as aca_mod
from repro.core.adaptive_thresholds import ThresholdTarget, calibrate_absorption
from repro.core.client import (AbsorptionConfig, ClientState, init_client,
                               make_upload, reset_round, run_round)
from repro.core.cost_model import CostModel, frame_latency
from repro.core.metrics import FrameBatch, RoundMetrics
from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                       allocate_subtable, allocate_subtables,
                                       lookup_all_layers)
from repro.core.server import (ServerConfig, ServerState, global_update,
                               init_server, merge_round,
                               profile_initial_cache)

# --------------------------------------------------------------------------
# Configuration and result records (the session-level types)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SimulationConfig:
    cache: CacheConfig
    absorb: AbsorptionConfig = AbsorptionConfig()
    server: ServerConfig = ServerConfig()
    round_frames: int = 300                  # F (nominal cycle; Eq.-10 unit)
    mem_budget: float = 64_000.0             # Π (bytes) per client
    dynamic_allocation: bool = True          # DCA (Fig. 9 ablation)
    global_updates: bool = True              # GCU (Fig. 9 ablation)
    static_layers: tuple[int, ...] = ()      # used when DCA is off
    straggler_deadline: float | None = None  # seconds; None = no deadline


class SimulationResult(NamedTuple):
    avg_latency: float
    accuracy: float
    hit_ratio: float
    hit_accuracy: float
    per_round_latency: np.ndarray
    per_round_accuracy: np.ndarray
    exit_histogram: np.ndarray
    server: ServerState | None


# TapFn: (round_index, client_index, labels) -> (sems (F,L,d), logits (F,C))
TapFn = Callable[[int, int, np.ndarray], tuple[jax.Array, jax.Array]]


# --------------------------------------------------------------------------
# Allocation policies (table-cutting: ACA / static / fixed)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AllocationContext:
    """The server's round-start view for one client — Alg. 1's inputs."""

    round_index: int
    client_index: int
    phi_global: np.ndarray     # (I,) Φ — global class frequencies
    tau: np.ndarray            # (I,) τᵏ — this client's recency timestamps
    r_est: np.ndarray          # (L,) R — expected per-layer hit ratios
    upsilon: np.ndarray        # (L,) Υ — saved seconds on a hit at layer j
    entry_sizes: np.ndarray    # (L,) bytes per cache entry at layer j
    mem_budget: float          # Π — client cache-size threshold in bytes
    round_frames: int          # F — nominal update cycle (Eq. 10 recency unit)

    @property
    def num_layers(self) -> int:
        return len(self.r_est)

    @property
    def num_classes(self) -> int:
        return len(self.phi_global)


@runtime_checkable
class AllocationPolicy(Protocol):
    """Decides one client's cache allocation at a round boundary."""

    def allocate(self, ctx: AllocationContext) -> np.ndarray:
        """Return the (L, I) boolean allocation indicator Xᵏ."""
        ...


@dataclasses.dataclass(frozen=True)
class AcaPolicy:
    """Algorithm 1 — the paper's Adaptive Cache Allocation."""

    name = "aca"

    def allocate(self, ctx: AllocationContext) -> np.ndarray:
        return aca_mod.aca_allocate(aca_mod.AllocationRequest(
            phi_global=ctx.phi_global, tau=ctx.tau, r_est=ctx.r_est,
            upsilon=ctx.upsilon, entry_sizes=ctx.entry_sizes,
            mem_budget=ctx.mem_budget, round_frames=ctx.round_frames))


@dataclasses.dataclass(frozen=True)
class StaticPolicy:
    """DCA-off baseline (§VI.G): Eq.-10 hot-spot classes at a fixed layer
    set, truncated so the fixed layers fit the same byte budget Π."""

    layers: tuple[int, ...] = ()
    name = "static"

    def allocate(self, ctx: AllocationContext) -> np.ndarray:
        scores = aca_mod.class_scores(ctx.phi_global, ctx.tau,
                                      ctx.round_frames)
        hot = aca_mod.select_hotspot_classes(scores)
        sizes = ctx.entry_sizes
        per_class = float(sum(sizes[j] for j in self.layers)) or 1.0
        max_classes = max(int(ctx.mem_budget // per_class), 1)
        return aca_mod.fixed_allocate(hot[:max_classes], list(self.layers),
                                      ctx.num_layers, ctx.num_classes)


@dataclasses.dataclass(frozen=True)
class FixedPolicy:
    """Completely frozen allocation: explicit classes at explicit layers."""

    classes: tuple[int, ...]
    layers: tuple[int, ...]
    name = "fixed"

    def allocate(self, ctx: AllocationContext) -> np.ndarray:
        return aca_mod.fixed_allocate(np.asarray(self.classes, int),
                                      list(self.layers),
                                      ctx.num_layers, ctx.num_classes)


# --------------------------------------------------------------------------
# Client-engine policies (baseline systems behind the same loop)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClientEngineContext:
    """What the cluster hands a baseline adapter to build one client engine."""

    cache: CacheConfig
    cost_model: CostModel
    entries: np.ndarray | None       # (L, I, d) bootstrap centroids, if any
    round_frames: int
    shared: tuple | None             # (sems, logits, labels) calibration set
    client_index: int
    num_clients: int


class ClientEnginePolicy(Protocol):
    """Swaps the whole client round for a baseline system.

    ``make_engine`` builds one per-client engine at first ``step()``
    (lazily for slots added or wiped by churn afterwards); an optional
    ``reset(num_clients)`` hook is called once per fresh engine *set*,
    before any ``make_engine``, so policies can re-arm cluster-shared state.
    ``run_round`` drives an engine for one :class:`FrameBatch` and returns a
    single-client :class:`RoundMetrics` (the cluster stamps labels/client).
    Engine policies bypass the global-cache merge — their cross-client
    sharing (if any) lives inside the engines, as in the original systems.
    """

    def make_engine(self, ctx: ClientEngineContext): ...

    def run_round(self, engine, batch: FrameBatch) -> RoundMetrics: ...


def _require_entries(ctx: ClientEngineContext, who: str) -> np.ndarray:
    if ctx.entries is None:
        raise RuntimeError(
            f"{who} needs the bootstrapped global table: call "
            "cluster.bootstrap(...) (or attach_server) before step()")
    return ctx.entries


@dataclasses.dataclass
class FoggyCachePolicy:
    """FoggyCache (§VI.B) behind ``cluster.step()``: A-LSH + H-kNN reuse with
    a server-side store consulted on local misses."""

    key_layer: int | None = None     # default: the deepest tap
    k: int = 5
    homogeneity: float = 0.6
    local_capacity: int = 200
    server_capacity: int = 2000
    network_cost: float = 0.0
    seed: int = 0
    name = "foggy"

    def make_engine(self, ctx: ClientEngineContext):
        from repro.core.baselines import FoggyCache
        key_layer = (self.key_layer if self.key_layer is not None
                     else ctx.cache.num_layers - 1)
        return FoggyCache(cfg=ctx.cache, cm=ctx.cost_model,
                          key_layer=key_layer, k=self.k,
                          homogeneity=self.homogeneity,
                          local_capacity=self.local_capacity,
                          server_capacity=self.server_capacity,
                          network_cost=self.network_cost,
                          seed=self.seed + ctx.client_index)

    def run_round(self, engine, batch: FrameBatch) -> RoundMetrics:
        return engine.round(np.asarray(batch.sems), np.asarray(batch.logits))


@dataclasses.dataclass
class SMTMPolicy:
    """SMTM (§VI.B): single-client semantic cache, local hot-spot ranking,
    local EMA entry maintenance — no global merge, no layer selection."""

    ema: float = 0.9
    name = "smtm"

    def make_engine(self, ctx: ClientEngineContext):
        from repro.core.baselines import SMTM
        entries = _require_entries(ctx, "SMTMPolicy")
        return SMTM(cfg=ctx.cache, cm=ctx.cost_model, entries=entries.copy(),
                    ema=self.ema, round_frames=ctx.round_frames)

    def run_round(self, engine, batch: FrameBatch) -> RoundMetrics:
        return engine.round(np.asarray(batch.sems), np.asarray(batch.logits))


@dataclasses.dataclass
class LearnedCachePolicy:
    """LearnedCache (§VI.B): per-exit linear heads, periodically refit —
    the refit bill amortised into per-frame latency."""

    exit_layers: tuple[int, ...] | None = None   # default range(1, L, 3)
    margin: float = 0.4
    retrain_rounds: int = 3
    name = "learned"

    def make_engine(self, ctx: ClientEngineContext):
        from repro.core.baselines import LearnedCache
        if ctx.shared is None:
            raise RuntimeError(
                "LearnedCachePolicy needs the shared calibration set for the "
                "initial head fit: call cluster.bootstrap(...) first")
        exits = (self.exit_layers if self.exit_layers is not None
                 else tuple(range(1, ctx.cache.num_layers, 3)))
        m = LearnedCache(cfg=ctx.cache, cm=ctx.cost_model,
                         exit_layers=list(exits), margin=self.margin,
                         retrain_rounds=self.retrain_rounds)
        sems, _, labels = ctx.shared
        m.fit(np.asarray(sems), np.asarray(labels))
        return m

    def run_round(self, engine, batch: FrameBatch) -> RoundMetrics:
        return engine.round(np.asarray(batch.sems), np.asarray(batch.logits),
                            labels_for_refit=np.asarray(batch.labels))


class _ReplacementEngine:
    def __init__(self, caches, layers, table, cfg, cm, rng, insert_observed):
        self.caches, self.layers, self.table = caches, layers, table
        self.cfg, self.cm, self.rng = cfg, cm, rng
        self.insert_observed = insert_observed

    def round(self, sems: np.ndarray, logits: np.ndarray) -> RoundMetrics:
        from repro.core.policies import run_policy_round
        return run_policy_round(self.caches, self.layers, self.table,
                                sems, logits, self.cfg, self.cm, self.rng,
                                insert_observed=self.insert_observed)


@dataclasses.dataclass
class ReplacementPolicy:
    """Classical replacement (LRU / FIFO / RAND, §VI.G) at fixed layers,
    reading entries from the same bootstrapped global table as CoCa — the
    ACA-vs-replacement comparison of Fig. 8 as a policy swap."""

    policy: str = "lru"              # "lru" | "fifo" | "rand"
    capacity: int = 15               # max classes resident per layer
    layers: tuple[int, ...] | None = None
    insert_observed: bool = False
    seed: int = 7

    @property
    def name(self) -> str:
        return self.policy

    def reset(self, num_clients: int) -> None:
        # one shared stream across a cluster's clients (the Fig. 8 study),
        # restarted per engine *set* so each cluster replays the same seed;
        # lazily rebuilt engines (churn rejoins/joins) keep sharing it
        self._rng = np.random.default_rng(np.random.SeedSequence((self.seed,)))

    def make_engine(self, ctx: ClientEngineContext):
        from repro.core.policies import PolicyCache
        if not hasattr(self, "_rng"):        # engine built without reset()
            self._rng = np.random.default_rng(
                np.random.SeedSequence((self.seed,)))
        L = ctx.cache.num_layers
        layers = (list(self.layers) if self.layers is not None else
                  list(np.linspace(0, L - 1, max(L // 3, 2))
                       .round().astype(int)))
        entries = _require_entries(ctx, "ReplacementPolicy")
        caches = [PolicyCache(capacity=self.capacity, policy=self.policy)
                  for _ in layers]
        return _ReplacementEngine(caches, layers, entries.copy(), ctx.cache,
                                  ctx.cost_model, self._rng,
                                  self.insert_observed)

    def run_round(self, engine, batch: FrameBatch) -> RoundMetrics:
        return engine.round(np.asarray(batch.sems), np.asarray(batch.logits))


def resolve_policy(policy, sim: SimulationConfig):
    """Resolve ``policy=`` inputs: None (from the config's DCA flags), a
    registry name, or a policy object (returned unchanged)."""
    if policy is None:
        return (AcaPolicy() if sim.dynamic_allocation
                else StaticPolicy(tuple(sim.static_layers)))
    if isinstance(policy, str):
        name = policy.lower()
        if name == "aca":
            return AcaPolicy()
        if name == "static":
            return StaticPolicy(tuple(sim.static_layers))
        if name == "foggy":
            return FoggyCachePolicy()
        if name == "smtm":
            return SMTMPolicy()
        if name == "learned":
            return LearnedCachePolicy()
        if name in ("lru", "fifo", "rand"):
            return ReplacementPolicy(policy=name)
        raise KeyError(f"unknown policy name: {policy!r} (known: aca, "
                       "static, foggy, smtm, learned, lru, fifo, rand)")
    return policy


# --------------------------------------------------------------------------
# Per-round controllers (theta / absorption thresholds)
# --------------------------------------------------------------------------


class ThetaPolicy(Protocol):
    """Between-round Θ adaptation from observed round metrics."""

    def update(self, metrics: RoundMetrics, theta: float) -> float: ...


@dataclasses.dataclass
class SLOTheta:
    """Adapt Θ to a per-frame latency SLO via the serving scheduler's
    bang-bang :class:`~repro.serving.scheduler.ThetaController`: attainment
    below target lowers Θ (more early exits), slack raises it (accuracy)."""

    slo_latency: float               # per-frame latency budget (seconds)
    target: float = 0.95
    margin: float = 0.02
    step: float = 0.1
    lo: float = 0.01
    hi: float = 0.5
    _ctl: object = dataclasses.field(default=None, repr=False)

    def update(self, metrics: RoundMetrics, theta: float) -> float:
        from repro.serving.scheduler import ThetaController
        if self._ctl is None:
            self._ctl = ThetaController(theta=theta, target=self.target,
                                        margin=self.margin, step=self.step,
                                        lo=self.lo, hi=self.hi)
        attainment = float((metrics.latency <= self.slo_latency).mean())
        # quantised so repeated values re-hit the jit cache
        return round(self._ctl.update(attainment), 6)


class AbsorptionPolicy(Protocol):
    """Between-round Γ/Δ recalibration; returns a new AbsorptionConfig."""

    def update(self, cluster: "CocaCluster") -> AbsorptionConfig | None: ...


@dataclasses.dataclass
class AdaptiveAbsorption:
    """Re-derive the Γ/Δ absorption thresholds each round from the server's
    shared validation set replayed against the *current* global cache
    (:mod:`repro.core.adaptive_thresholds` — the §VI.D sweep, automated).

    ``+inf`` thresholds mean "absorb nothing" — the calibrator could not find
    a threshold meeting the accuracy bar; values are quantised so unchanged
    thresholds re-hit the jit cache.
    """

    target: ThresholdTarget = ThresholdTarget()
    every: int = 1                   # recalibrate every N rounds
    decimals: int = 3

    def update(self, cluster: "CocaCluster") -> AbsorptionConfig | None:
        if cluster.round_index % self.every:
            return None
        if cluster._shared is None or cluster.server is None:
            return None
        sems, logits, labels = cluster._shared
        cfg = cluster.sim.cache
        full = CacheTable(
            entries=cluster._gathered_entries(),
            class_mask=jnp.ones(cfg.num_classes, bool),
            layer_mask=jnp.ones(cfg.num_layers, bool))
        look = lookup_all_layers(full, jnp.asarray(sems), cfg)
        hit = np.asarray(look.hit)
        scores = np.asarray(look.scores)
        el = np.minimum(np.asarray(look.exit_layer), cfg.num_layers - 1)
        d_at_exit = scores[np.arange(len(el)), el]
        cache_pred = np.asarray(look.pred)

        logits_np = np.asarray(logits)
        z = logits_np - logits_np.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        top2 = -np.sort(-p, axis=1)[:, :2]
        margin = top2[:, 0] - top2[:, 1]
        model_pred = logits_np.argmax(axis=1)
        labels = np.asarray(labels)

        gamma, delta = calibrate_absorption(
            d_at_exit[hit], (cache_pred == labels)[hit],
            margin[~hit], (model_pred == labels)[~hit], self.target)
        q = (lambda v: float(v) if not np.isfinite(v)
             else round(float(v), self.decimals))
        cur = cluster.sim.absorb
        return AbsorptionConfig(gamma_hit=q(gamma), delta_miss=q(delta),
                                beta=cur.beta)


# --------------------------------------------------------------------------
# Pure round-step functions (the decomposed device computation)
# --------------------------------------------------------------------------


@jax.jit
def _stack(trees: list):
    """Stack a list of like pytrees leaf by leaf, in one program."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *trees)


def _stack_tables(tables: list[CacheTable]) -> CacheTable:
    scale = [t.entry_scale for t in tables]
    if any((s is None) != (scale[0] is None) for s in scale):
        raise ValueError("cannot stack mixed float32/int8 cache tables")
    return _stack(list(tables))


def _init_clients_batched(cfg: CacheConfig, num_clients: int) -> ClientState:
    one = init_client(cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (num_clients,) + x.shape), one)


@partial(jax.jit, static_argnames=("cfg", "absorb", "scfg", "cm",
                                   "global_updates", "deadline", "mesh"))
def round_step(states: ClientState, tables: CacheTable, sems: jax.Array,
               logits: jax.Array, server: ServerState,
               *, cfg: CacheConfig, absorb: AbsorptionConfig,
               scfg: ServerConfig, cm: CostModel, global_updates: bool,
               deadline: float | None, upload_mask: jax.Array | None = None,
               mesh=None):
    """One full round for all K clients as a single device computation:
    client round (vmapped) → uploads → Eq.-4/5 merges (``lax.scan``, client
    order preserved).

    ``states``/``tables``/``sems``/``logits`` carry a leading client axis K.
    ``upload_mask`` — optional (K,) bool: clients whose Eq.-4/5 upload merges
    this round (the fault-injection harness masks dropped / delayed /
    quarantined uploads; ``None`` = everyone, the default path).
    ``mesh`` — the mesh of a class-sharded ``server``: the merge runs per
    class shard (:func:`repro.core.server.merge_round`), the lookup whole on
    every device (:func:`repro.core.semantic_cache.lookup_all_layers`).
    Returns ``(new states, new server, per-frame metrics dict)`` — the
    metrics are (K, F) arrays (pred / hit / exit_layer / lat); nothing here
    forces a host sync.
    """
    states = reset_round(states)                     # elementwise, vmap-free

    # Every client's lookup in one launch over the stacked tables; the rest
    # of the client round is vmapped.
    look = lookup_all_layers(tables, sems, cfg, mesh=mesh)._replace(acc=None)
    out = jax.vmap(lambda s, t, se, lo, lk: run_round(s, t, se, lo, cfg,
                                                      absorb, look=lk))(
        states, tables, sems, logits, look)

    n_hot = tables.class_mask.sum(axis=1)                          # (K,)
    lat = jax.vmap(lambda e, lm, nh: frame_latency(cm, e, lm, nh))(
        out.exit_layer, tables.layer_mask, n_hot)                  # (K, F)

    metrics = {"pred": out.pred, "hit": out.hit,
               "exit_layer": out.exit_layer, "lat": lat}

    if global_updates:
        if deadline is None:
            include = jnp.ones((lat.shape[0],), bool)
        else:
            include = lat.sum(axis=1) <= deadline
        if upload_mask is not None:
            include = include & upload_mask
        uploads = make_upload(out.state)             # leading K axis on leaves
        server = merge_round(server, uploads, include, scfg, mesh)

    return out.state, server, metrics


# --------------------------------------------------------------------------
# Server bootstrap (§III.3, §V.A)
# --------------------------------------------------------------------------


def bootstrap_server_from_taps(sim: SimulationConfig, sems: jax.Array,
                               shared_labels: np.ndarray,
                               cost_model: CostModel,
                               r0: np.ndarray | None = None,
                               mesh=None) -> ServerState:
    """Server warm start from already-synthesised shared-set taps.

    Entries = per-class per-layer centroids of the shared set; R = profiled
    first-hit CDF measured by replaying the shared set against the freshly
    built full table ("empirical relation tested on a shared dataset").

    With ``mesh`` the profiled table is built class-sharded and the returned
    ServerState lives on the mesh; the R-profiling replay (a dense full-table
    lookup, same shape of work as subtable allocation) gathers first.
    """
    entries, counts = profile_initial_cache(sems, jnp.asarray(shared_labels),
                                            sim.cache.num_classes, mesh=mesh)
    if r0 is None:
        lookup_entries = entries
        if mesh is not None:
            from repro.distributed.sharding import gather_cache
            lookup_entries = gather_cache(entries, mesh)
        full = CacheTable(entries=lookup_entries,
                          class_mask=jnp.ones(sim.cache.num_classes, bool),
                          layer_mask=jnp.ones(sim.cache.num_layers, bool))
        look = lookup_all_layers(full, sems, sim.cache, mesh=mesh)
        first = np.bincount(np.asarray(look.exit_layer),
                            minlength=sim.cache.num_layers + 1)[:-1]
        r0 = np.cumsum(first) / max(len(shared_labels), 1)
    server = init_server(sim.cache, entries, counts, jnp.asarray(r0),
                         jnp.asarray(cost_model.saved_time()))
    if mesh is not None:
        from repro.distributed.sharding import shard_server_state
        server = shard_server_state(server, mesh)
    return server


def bootstrap_server(key: jax.Array, sim: SimulationConfig, tap_fn_shared,
                     shared_labels: np.ndarray, cost_model: CostModel,
                     r0: np.ndarray | None = None,
                     mesh=None) -> ServerState:
    """Classic entry point: synthesise the shared-set taps, then bootstrap."""
    sems, _ = tap_fn_shared(shared_labels)
    return bootstrap_server_from_taps(sim, sems, shared_labels, cost_model,
                                      r0=r0, mesh=mesh)


# --------------------------------------------------------------------------
# The session object
# --------------------------------------------------------------------------


class CocaCluster:
    """A CoCa deployment as a session: K clients + one server + a policy.

    Parameters
    ----------
    sim : SimulationConfig — cache / absorption / server / budget knobs.
        (The legacy ``dynamic_allocation`` / ``static_layers`` flags only
        matter when ``policy=None``; a policy object wins otherwise.)
    cost_model : CostModel — the analytic latency accounting.
    policy : None | str | AllocationPolicy | ClientEnginePolicy.
    num_clients : fixed here or inferred from the first ``step()``.
    mesh : optional ``jax.sharding.Mesh`` — the server cache lives
        class-sharded; one all-gather per round at subtable allocation.
    vectorized : run rounds as one device computation (vmap over clients +
        scanned merges).  ``False`` = per-client reference path — the parity
        oracle.  Ragged frame batches always take the reference path.

    Membership is dynamic: ``add_client()`` grows the cluster,
    ``remove_client(k)`` deactivates a slot (its client state is retained),
    ``rejoin_client(k)`` reactivates it with the stale state (``fresh=True``
    wipes it).  ``step()`` then takes one frame batch per *active* client,
    in ascending slot order (``cluster.active_clients``).  A change in the
    active count retraces the jitted round step once per new count.
    theta_policy / absorption_policy : optional per-round controllers.
    max_history : keep only the last N per-frame :class:`RoundMetrics`
        records in ``cluster.history`` (None = keep all).  ``result()``
        aggregates incrementally, so bounding the history does not change
        the summary — set this for long-running streaming sessions.
    """

    def __init__(self, sim: SimulationConfig, cost_model: CostModel, *,
                 policy=None, num_clients: int | None = None, mesh=None,
                 vectorized: bool = True, server: ServerState | None = None,
                 theta_policy: ThetaPolicy | None = None,
                 absorption_policy: AbsorptionPolicy | None = None,
                 max_history: int | None = None):
        self.sim = sim
        self._cm = cost_model
        self._mesh = mesh
        self._vectorized = vectorized
        self._policy = resolve_policy(policy, sim)
        self._is_engine_policy = hasattr(self._policy, "make_engine")
        self._theta_policy = theta_policy
        self._absorption_policy = absorption_policy

        self._K = num_clients
        self._active = (np.ones(num_clients, bool)
                        if num_clients is not None else None)
        self._states: ClientState | None = None
        self._engines: list | None = None
        self._server: ServerState | None = None
        self._shared: tuple | None = None     # (sems, logits, labels)
        self._alloc_entries = None            # gathered table (mesh path)
        self._round = 0
        self._max_history = max_history
        self._history: list[RoundMetrics] = []
        # incremental per-round aggregates — result() never needs the
        # (possibly trimmed) per-frame history
        self._agg_lat: list[float] = []
        self._agg_frames: list[int] = []
        self._agg_correct: list[int] = []
        self._agg_hits = 0
        self._agg_hit_cor = 0
        self._agg_exit = np.zeros(sim.cache.num_layers + 1, np.int64)

        self._host_phi = self._host_r = self._host_ups = None
        self._host_tau = None
        if server is not None:
            self.attach_server(server)

    # ----------------------------------------------------------- properties
    @property
    def policy(self):
        return self._policy

    @property
    def cost_model(self) -> CostModel:
        """The analytic latency model this session bills rounds with — the
        escalation layers (:mod:`repro.topology`) bill their hops and tier
        lookups against the same model."""
        return self._cm

    @property
    def server(self) -> ServerState | None:
        return self._server

    @property
    def round_index(self) -> int:
        return self._round

    @property
    def num_clients(self) -> int | None:
        return self._K

    @property
    def active_clients(self) -> list[int]:
        """Ascending slot indices of the currently active clients — the
        order ``step()`` expects its frame batches in."""
        if self._K is None:
            return []
        if self._active is None:
            return list(range(self._K))
        return [int(k) for k in np.flatnonzero(self._active)]

    @property
    def history(self) -> list[RoundMetrics]:
        return list(self._history)

    @property
    def r_est(self) -> np.ndarray:
        """(L,) host copy of the server's profiled first-hit CDF R — the
        third serving hook (with :meth:`set_theta` / :meth:`serving_table`):
        the online loop derives its admission-time cost estimate from it."""
        if self._host_r is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() first")
        return self._host_r

    # ------------------------------------------------------------ lifecycle
    def bootstrap(self, key: jax.Array, taps, shared_labels=None,
                  r0: np.ndarray | None = None,
                  server: ServerState | None = None) -> "CocaCluster":
        """Warm-start the server from the globally shared dataset.

        ``taps`` — either a callable ``labels -> (sems, logits)`` (the classic
        ``tap_fn_shared``) or a precomputed ``(sems, logits)`` pair.  The
        shared set is retained for baseline head fits
        (:class:`LearnedCachePolicy`) and for :class:`AdaptiveAbsorption`.
        ``server`` — reuse an already-profiled ServerState (same shared set)
        instead of re-running `profile_initial_cache` + the R replay.
        """
        if shared_labels is None:
            raise ValueError("bootstrap() needs shared_labels")
        if callable(taps):
            sems, logits = taps(shared_labels)
        else:
            sems, logits = taps
        self._shared = (sems, logits, np.asarray(shared_labels))
        if server is not None:
            return self.attach_server(server)
        server = bootstrap_server_from_taps(
            self.sim, sems, shared_labels, self._cm, r0=r0, mesh=self._mesh)
        # bootstrap_server_from_taps already sharded it; attach directly
        self._set_server(server)
        return self

    def attach_server(self, server: ServerState) -> "CocaCluster":
        """Adopt an existing ServerState (sharding it onto the mesh if any)."""
        if self._mesh is not None:
            from repro.distributed.sharding import shard_server_state
            server = shard_server_state(server, self._mesh)
        self._set_server(server)
        return self

    def _set_server(self, server: ServerState) -> None:
        self._server = server
        self._alloc_entries = None
        self._host_phi, self._host_r = jax.device_get(
            (server.phi_global, server.r_est))
        self._host_phi = np.asarray(self._host_phi)
        self._host_r = np.asarray(self._host_r)
        self._host_ups = np.asarray(jax.device_get(server.upsilon))

    def _ensure_clients(self, k_from_frames: int) -> None:
        if self._K is None:
            self._K = k_from_frames
        if self._active is None:
            self._active = np.ones(self._K, bool)
        n_active = int(self._active.sum())
        if k_from_frames != n_active:
            raise ValueError(
                f"step() got {k_from_frames} frame batches for a cluster "
                f"with {n_active} active clients ({self._K} slots)")
        if self._states is None and not self._is_engine_policy:
            self._states = _init_clients_batched(self.sim.cache, self._K)
            self._host_tau = np.asarray(jax.device_get(self._states.tau))

    # ---------------------------------------------------------------- churn
    def _require_slots(self) -> None:
        if self._K is None:
            raise RuntimeError("client count unknown: pass num_clients= at "
                               "construction or step() once first")
        if self._active is None:
            self._active = np.ones(self._K, bool)

    def _check_slot(self, client: int) -> None:
        if not 0 <= client < self._K:
            raise ValueError(f"client {client} out of range for a "
                             f"{self._K}-slot cluster")

    def add_client(self) -> int:
        """Grow the cluster by one fresh, active slot; returns its index.

        The new client starts with zeroed status vectors and, like every
        other client, receives its table from the active policy at the next
        ``step()`` — joining is an allocation event, not a protocol change.
        """
        self._require_slots()
        k = self._K
        self._K += 1
        self._active = np.append(self._active, True)
        if self._states is not None:
            fresh = init_client(self.sim.cache)
            self._states = jax.tree_util.tree_map(
                lambda s, f: jnp.concatenate([s, f[None]]),
                self._states, fresh)
            self._host_tau = np.asarray(jax.device_get(self._states.tau))
        if self._engines is not None:
            self._engines.append(None)       # built lazily at the next step
        return k

    def remove_client(self, client: int) -> None:
        """Deactivate a slot (leave / failure).  The client's state — status
        vectors, engine — is retained verbatim so :meth:`rejoin_client` can
        bring it back with a stale cache; the slot is simply masked out of
        every subsequent round (no frames, no Eq.-4/5 upload, no
        allocation)."""
        self._require_slots()
        self._check_slot(client)
        if not self._active[client]:
            raise ValueError(f"client {client} is already inactive")
        if self._active.sum() == 1:
            raise ValueError("cannot remove the last active client "
                             "(every round needs at least one)")
        self._active[client] = False

    def rejoin_client(self, client: int, *, fresh: bool = False) -> None:
        """Reactivate a previously removed slot.

        ``fresh=False`` (default) resumes with the stale status vectors the
        client left with — the paper-faithful "device comes back after an
        outage" case; the next global update cycle re-syncs it.
        ``fresh=True`` wipes the slot to a cold start (also how late
        *joiners* in a scenario schedule enter).
        """
        self._require_slots()
        self._check_slot(client)
        if self._active[client]:
            raise ValueError(f"client {client} is already active")
        self._active[client] = True
        if fresh:
            if self._states is not None:
                blank = init_client(self.sim.cache)
                self._states = jax.tree_util.tree_map(
                    lambda s, b: s.at[client].set(b), self._states, blank)
                if self._host_tau is not None:
                    # device_get arrays can be read-only; replace, not mutate
                    tau = np.array(self._host_tau)
                    tau[client] = 0
                    self._host_tau = tau
            if self._engines is not None:
                self._engines[client] = None

    # ----------------------------------------------------------- allocation
    def _gathered_entries(self) -> jax.Array:
        """The dense global table (the protocol's one collective per round).

        The cache is invalidated wherever the server table can change (merge
        steps, ``attach_server``), so repeated calls within a round — e.g.
        an external ``allocate_tables()`` followed by ``step()`` — reuse one
        gather, and with GCU off round 0's gather serves every round.
        """
        if self._mesh is None:
            return self._server.entries
        if self._alloc_entries is None:
            from repro.distributed.sharding import gather_cache
            self._alloc_entries = gather_cache(self._server.entries,
                                               self._mesh)
        return self._alloc_entries

    def gathered_entries(self) -> jax.Array:
        """Public snapshot of the dense (L, I, d) global table.

        Every *external* table cut — serving-window re-allocation, a
        topology tier cutting its own cache (:mod:`repro.topology`) — slices
        this one snapshot via :func:`allocate_subtable`, so N cuts in a
        round still cost the mesh path one collective (the
        ``_gathered_entries`` cache)."""
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before gathered_entries()")
        return self._gathered_entries()

    def allocation_context(self, client: int) -> AllocationContext:
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before allocating")
        tau = (self._host_tau[client] if self._host_tau is not None
               else np.zeros(self.sim.cache.num_classes, np.int32))
        return AllocationContext(
            round_index=self._round, client_index=client,
            phi_global=self._host_phi, tau=tau, r_est=self._host_r,
            upsilon=self._host_ups, entry_sizes=self._cm.entry_sizes(),
            mem_budget=self.sim.mem_budget,
            round_frames=self.sim.round_frames)

    def allocate_tables(self) -> list[CacheTable]:
        """Round-start tables for the *active* clients under the active
        policy, in ascending slot order (also the serving path's table
        source — see serving/engine.py).  Inactive slots get no allocation:
        a membership change re-runs the policy for the new active set at the
        very next round."""
        if self._K is None:
            raise RuntimeError("client count unknown: pass num_clients= at "
                               "construction or step() once first")
        return self._allocate(stacked=False)

    def _allocate(self, *, stacked: bool):
        """The policy's allocation per active client, then all their cuts
        in one call: the stacked table ``round_step`` takes, or its list."""
        entries = self._gathered_entries()
        xs = []
        for k in self.active_clients:
            with obs.span("coca.round.aca", client=int(k)):
                xs.append(self._policy.allocate(self.allocation_context(k)))
        with obs.span("coca.round.cut", clients=len(xs)):
            return allocate_subtables(
                entries, jnp.asarray(np.stack(xs)),
                entry_dtype=self.sim.cache.entry_dtype, stacked=stacked)

    # -------------------------------------------------- serving-loop hooks
    def set_theta(self, theta: float) -> None:
        """Override the scalar hit threshold Θ between rounds/windows — the
        online serving loop's control input (:mod:`repro.serving.loop`):
        its per-window :class:`~repro.serving.scheduler.ThetaController`
        verdict lands here, and the next allocation/lookup sees the new Θ.
        Values are quantised so a repeated Θ re-hits the jit cache."""
        if isinstance(self.sim.cache.theta, tuple):
            raise ValueError("set_theta() needs a scalar-theta cache config")
        t = round(float(theta), 6)
        if t != float(self.sim.cache.theta):
            self.sim = dataclasses.replace(
                self.sim, cache=dataclasses.replace(self.sim.cache, theta=t))

    def serving_table(self, *, client: int = 0,
                      tau: np.ndarray | None = None,
                      phi: np.ndarray | None = None,
                      round_index: int | None = None,
                      mem_budget: float | None = None) -> CacheTable:
        """Cut one serving :class:`CacheTable` from the live server with the
        active allocation policy — the online loop's **window-boundary
        re-allocation hook**.

        Unlike :meth:`allocate_tables`, the recency/frequency view can come
        from the caller: the serving session passes the ``tau`` (and
        optionally ``phi``) it observed from the *request stream*, so
        between-window ACA re-allocation tracks what is actually being
        served rather than the simulator's client states.  Defaults fall
        back to the engine's own host mirrors (zeros for a cold client).
        Reuses the one-gather-per-round entries cache on the mesh path.

        ``mem_budget`` overrides the per-client byte budget Π for this one
        cut — how a topology tier (:mod:`repro.topology`) sizes its own
        cache from the same policy and server snapshot (an edge node's cut
        at 2Π, a regional node's at 4Π, ...).  ``None`` keeps the
        configured ``sim.mem_budget`` bit-for-bit.
        """
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before serving_table()")
        if self._is_engine_policy:
            raise RuntimeError(
                "serving_table() needs a table-cutting AllocationPolicy; "
                f"{getattr(self._policy, 'name', self._policy)!r} is a "
                "client-engine baseline")
        I = self.sim.cache.num_classes
        if tau is None:
            tau = (self._host_tau[client] if self._host_tau is not None
                   else np.zeros(I, np.int32))
        ctx = AllocationContext(
            round_index=(self._round if round_index is None
                         else int(round_index)),
            client_index=client,
            phi_global=(self._host_phi if phi is None
                        else np.asarray(phi, float)),
            tau=np.asarray(tau), r_est=self._host_r, upsilon=self._host_ups,
            entry_sizes=self._cm.entry_sizes(),
            mem_budget=(self.sim.mem_budget if mem_budget is None
                        else float(mem_budget)),
            round_frames=self.sim.round_frames)
        return allocate_subtable(self._gathered_entries(),
                                 jnp.asarray(self._policy.allocate(ctx)),
                                 entry_dtype=self.sim.cache.entry_dtype)

    def serving_tables(self, taus: dict[int, np.ndarray], *,
                       round_index: int | None = None
                       ) -> dict[int, CacheTable]:
        """Per-replica serving cuts from **one** gather — the fleet
        gateway's window-boundary hook.  Each entry of ``taus`` maps a
        replica's cluster slot to the request-stream recency that replica
        observed; every cut shares the same dense global table (the
        ``_gathered_entries`` cache makes the N calls cost one collective),
        so N replicas re-allocate against an identical server snapshot —
        the fleet analogue of the round's single broadcast."""
        entries = self._gathered_entries()   # prime the cache once
        del entries
        return {k: self.serving_table(client=k, tau=tau,
                                      round_index=round_index)
                for k, tau in taus.items()}

    # ---------------------------------------------- sync / recovery hooks
    def client_upload(self, client: int) -> "ClientUpload":
        """Reconstruct the Eq.-4/5 upload slot ``client`` produced in the
        *last* round.  ``make_upload`` is a field-for-field view of the
        client state, and ``step()`` stores each round's post-round
        accumulators, so the upload a faulty link dropped (or duplicated, or
        corrupted in flight) is recoverable host-side — the chaos harness
        replays it through :meth:`merge_upload` on retry/delay."""
        if self._states is None:
            raise RuntimeError("no client states yet: step() at least once")
        self._check_slot(client)
        return make_upload(jax.tree_util.tree_map(
            lambda x: x[client], self._states))

    def merge_upload(self, upload) -> None:
        """Apply one client upload to the live server outside ``step()`` —
        the degraded-mode re-sync path: a delayed upload arriving a round
        late, or a retried transmission landing after its round's fused
        merge already ran.  Refreshes the host mirrors and invalidates the
        gathered-entries cache exactly as an in-step merge does."""
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before merge_upload()")
        from repro.core.client import ClientUpload as _CU
        upload = _CU(*(jnp.asarray(leaf) for leaf in upload))
        self._set_server(global_update(self._server, upload, self.sim.server))

    def save_checkpoint(self, mgr) -> None:
        """Checkpoint the cluster's durable state — the server's 2-D global
        cache (+Φ/R/Υ), the round index, and (when clients have stepped) the
        client status vectors and activity mask — through
        :class:`~repro.checkpoint.manager.CheckpointManager`'s atomic step
        directories.  A server crash mid-round then recovers via
        :meth:`restore_checkpoint` with hit-ratio loss bounded by the rounds
        merged since this save (the ``benchmarks/table5_chaos.py`` drill)."""
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before save_checkpoint()")
        tree = {"server": self._server,
                "round": np.asarray(self._round, np.int64)}
        if self._states is not None:
            tree["states"] = self._states
            tree["active"] = np.asarray(self._active, bool)
        mgr.save(self._round, tree)

    def restore_checkpoint(self, mgr, step: int | None = None) -> int | None:
        """Restore the cluster from ``mgr``'s latest (or explicit) step.

        Returns the restored round index, or ``None`` when the directory
        holds no checkpoint (a fresh start — the blind-restart contract of
        :func:`repro.distributed.fault_tolerance.resume`).  Requires a
        bootstrapped server for the restore template; client states are
        rebuilt only if the checkpoint recorded them."""
        if self._server is None:
            raise RuntimeError("no server: call bootstrap() or "
                               "attach_server() before restore_checkpoint()")
        if step is None:
            step = mgr.latest_step()
        if step is None:
            return None
        leaves = mgr.manifest(step)["leaves"]
        state_leaves = {n: meta for n, meta in leaves.items()
                        if n.startswith("states")}
        like = {"server": self._server,
                "round": np.asarray(0, np.int64)}
        if state_leaves:
            K = int(next(iter(state_leaves.values()))["shape"][0])
            like["states"] = _init_clients_batched(self.sim.cache, K)
            like["active"] = np.zeros(K, bool)
        out = mgr.restore(step, like)
        if state_leaves:
            self._K = K
            self._active = np.asarray(jax.device_get(out["active"]), bool)
            self._states = out["states"]
            self._host_tau = np.asarray(jax.device_get(self._states.tau))
        self._round = int(out["round"])
        server = out["server"]
        if self._mesh is not None:
            from repro.distributed.sharding import shard_server_state
            server = shard_server_state(server, self._mesh)
        self._set_server(server)
        return self._round

    # ----------------------------------------------------------------- step
    def step(self, frames: Sequence, *, tables: Sequence | None = None,
             upload_mask: Sequence | None = None) -> RoundMetrics:
        """Run one round over per-client frame batches.

        ``frames`` — K entries, each a :class:`FrameBatch` or a plain
        ``(sems, logits, labels)`` triple.  Batches may have any F; ragged
        per-client F (or ``vectorized=False``) takes the per-client
        reference path, uniform F the single-device-computation path.

        The two keyword overrides are the fault-injection seams
        (:mod:`repro.distributed.faults`); both default to the unfaulted
        behaviour bit-for-bit:

        ``tables`` — per-active-client :class:`CacheTable` list replacing the
        round-start policy allocation (a degraded client serving from its
        stale local table, a naive client holding a corrupted download).
        ``upload_mask`` — per-active-client bools; ``False`` keeps that
        client's Eq.-4/5 upload out of this round's merge (dropped, delayed,
        or quarantined-for-validation uploads).
        """
        if not frames:
            raise ValueError("step() needs at least one frame batch")
        frames = [fb if isinstance(fb, FrameBatch) else FrameBatch(*fb)
                  for fb in frames]
        with obs.span("coca.round", round=self._round):
            return self._step(frames, tables, upload_mask)

    def _step(self, frames: list[FrameBatch], tables: Sequence | None,
              upload_mask: Sequence | None) -> RoundMetrics:
        self._ensure_clients(len(frames))
        if tables is not None and len(tables) != len(frames):
            raise ValueError(f"tables= has {len(tables)} entries for "
                             f"{len(frames)} frame batches")
        if upload_mask is not None and len(upload_mask) != len(frames):
            raise ValueError(f"upload_mask= has {len(upload_mask)} entries "
                             f"for {len(frames)} frame batches")

        if self._is_engine_policy:
            if tables is not None or upload_mask is not None:
                raise ValueError("tables=/upload_mask= overrides need the "
                                 "global-cache protocol; client-engine "
                                 "baselines have neither allocation nor "
                                 "Eq.-4/5 uploads")
            metrics = self._step_engines(frames)
        else:
            if self._server is None:
                raise RuntimeError("no server: call bootstrap() or "
                                   "attach_server() before step()")
            lengths = {fb.num_frames for fb in frames}
            if self._vectorized and len(lengths) == 1:
                metrics = self._step_vectorized(frames, tables, upload_mask)
            else:
                metrics = self._step_reference(frames, tables, upload_mask)

        self._round += 1
        self._history.append(metrics)
        if self._max_history is not None:
            del self._history[:-self._max_history]
        self._agg_lat.append(metrics.latency_sum)
        self._agg_frames.append(metrics.frames)
        self._agg_correct.append(metrics.correct)
        self._agg_hits += metrics.hits
        self._agg_hit_cor += metrics.hit_correct
        self._agg_exit += metrics.exit_histogram()
        self._apply_controllers(metrics)
        return metrics

    def _apply_controllers(self, metrics: RoundMetrics) -> None:
        if self._theta_policy is not None:
            theta = self.sim.cache.theta
            if isinstance(theta, tuple):
                raise ValueError("theta_policy needs a scalar theta")
            new = self._theta_policy.update(metrics, float(theta))
            if new is not None and float(new) != float(theta):
                self.sim = dataclasses.replace(
                    self.sim, cache=dataclasses.replace(
                        self.sim.cache, theta=float(new)))
        if self._absorption_policy is not None:
            new = self._absorption_policy.update(self)
            if new is not None and new != self.sim.absorb:
                self.sim = dataclasses.replace(self.sim, absorb=new)

    def _step_vectorized(self, frames: list[FrameBatch],
                         tables_in: Sequence | None = None,
                         upload_mask: Sequence | None = None) -> RoundMetrics:
        sim = self.sim
        act = np.flatnonzero(self._active)               # ascending slots
        all_active = len(act) == self._K
        # Two preparation dispatches whatever K is: the one cut of every
        # client's table (or the stack of the caller's tables), and the one
        # stack of the taps and logits.
        if tables_in is None:
            tables = self._allocate(stacked=True)
        with obs.span("coca.round.stack"):
            if tables_in is not None:
                tables = _stack_tables(tables_in)
            sems, logits = _stack([(jnp.asarray(fb.sems),
                                    jnp.asarray(fb.logits))
                                   for fb in frames])

        # Churn masking: only the active slots enter the fused round_step —
        # inactive clients contribute no frames and no Eq.-4/5 upload, and
        # their retained (stale) state is written back untouched.
        idx = None if all_active else jnp.asarray(act)
        states_in = (self._states if all_active else
                     jax.tree_util.tree_map(lambda x: x[idx], self._states))
        mask = (None if upload_mask is None
                else jnp.asarray(np.asarray(upload_mask, bool)))
        with obs.span("coca.round.dispatch"):
            new_states, self._server, m = round_step(
                states_in, tables, sems, logits, self._server,
                cfg=sim.cache, absorb=sim.absorb, scfg=sim.server,
                cm=self._cm, global_updates=sim.global_updates,
                deadline=sim.straggler_deadline, upload_mask=mask,
                mesh=self._mesh)
        self._states = (new_states if all_active else
                        jax.tree_util.tree_map(
                            lambda full, new: full.at[idx].set(new),
                            self._states, new_states))
        if sim.global_updates:
            self._alloc_entries = None       # merges changed the table

        # The single device→host transfer of the round: metrics ride along
        # with the status vectors the next round's allocation needs.
        with obs.span("coca.round.sync"):
            m, self._host_phi, self._host_r, self._host_tau = jax.device_get(
                (m, self._server.phi_global, self._server.r_est,
                 self._states.tau))
        F = frames[0].num_frames
        return RoundMetrics(
            pred=np.asarray(m["pred"]).ravel().astype(np.int32),
            hit=np.asarray(m["hit"]).ravel(),
            exit_layer=np.asarray(m["exit_layer"]).ravel().astype(np.int32),
            latency=np.asarray(m["lat"]).ravel(),
            labels=np.concatenate([np.asarray(fb.labels) for fb in frames]),
            client=np.repeat(act.astype(np.int32), F),
            num_layers=sim.cache.num_layers)

    def _step_reference(self, frames: list[FrameBatch],
                        tables_in: Sequence | None = None,
                        upload_mask: Sequence | None = None) -> RoundMetrics:
        """Per-client Python loop — the parity oracle.  Same round semantics
        (round-start allocation for every client, Eq.-4/5 merges applied in
        client order at the round boundary); one host sync per client per
        stage instead of one per round."""
        sim = self.sim
        act = self.active_clients
        tables = (list(tables_in) if tables_in is not None
                  else self.allocate_tables())
        parts, include, new_states = [], [], []
        for i, ((t, k), fb) in enumerate(zip(zip(tables, act), frames)):
            state_k = jax.tree_util.tree_map(lambda x: x[k], self._states)
            out = run_round(reset_round(state_k), t,
                            jnp.asarray(fb.sems), jnp.asarray(fb.logits),
                            sim.cache, sim.absorb)
            new_states.append(out.state)
            n_hot = t.class_mask.sum()
            lat = np.asarray(frame_latency(self._cm, out.exit_layer,
                                           t.layer_mask, n_hot))
            parts.append(RoundMetrics.single(
                np.asarray(out.pred), np.asarray(out.hit),
                np.asarray(out.exit_layer), lat,
                num_layers=sim.cache.num_layers, labels=fb.labels, client=k))
            straggled = (sim.straggler_deadline is not None
                         and lat.sum() > sim.straggler_deadline)
            masked = upload_mask is not None and not bool(upload_mask[i])
            include.append(sim.global_updates and not straggled
                           and not masked)

        for i in range(len(act)):
            if include[i]:
                self._server = global_update(
                    self._server, make_upload(new_states[i]), sim.server)
        if sim.global_updates:
            self._alloc_entries = None       # merges changed the table
        for k, st in zip(act, new_states):
            self._states = jax.tree_util.tree_map(
                lambda full, new, k=k: full.at[k].set(new),
                self._states, st)

        self._host_phi = np.asarray(jax.device_get(self._server.phi_global))
        self._host_r = np.asarray(jax.device_get(self._server.r_est))
        self._host_tau = np.asarray(jax.device_get(self._states.tau))
        return RoundMetrics.concat(parts)

    def _step_engines(self, frames: list[FrameBatch]) -> RoundMetrics:
        if self._engines is None:
            self._engines = [None] * self._K
            if hasattr(self._policy, "reset"):   # fresh engine set
                self._policy.reset(self._K)
        if len(self._engines) < self._K:                 # add_client grew K
            self._engines += [None] * (self._K - len(self._engines))
        act = self.active_clients
        if any(self._engines[k] is None for k in act):
            entries = None
            if self._server is not None:
                entries = np.asarray(jax.device_get(self._gathered_entries()))
            for k in act:                                # ascending slots
                if self._engines[k] is None:
                    self._engines[k] = self._policy.make_engine(
                        ClientEngineContext(
                            cache=self.sim.cache, cost_model=self._cm,
                            entries=entries,
                            round_frames=self.sim.round_frames,
                            shared=self._shared, client_index=k,
                            num_clients=self._K))
        parts = []
        for k, fb in zip(act, frames):
            out = self._policy.run_round(self._engines[k], fb)
            parts.append(out._replace(
                labels=np.asarray(fb.labels).reshape(-1),
                client=np.full(out.frames, k, np.int32)))
        return RoundMetrics.concat(parts)

    # --------------------------------------------------------------- result
    def result(self) -> SimulationResult:
        """Aggregate the session's rounds into the classic summary record."""
        if not self._agg_frames:
            raise RuntimeError("result() before any step()")
        lat_sum = np.array(self._agg_lat)
        frames = np.array(self._agg_frames, np.int64)
        correct = np.array(self._agg_correct, np.int64)
        total_f = int(frames.sum())
        return SimulationResult(
            avg_latency=float(lat_sum.sum() / total_f),
            accuracy=float(correct.sum() / total_f),
            hit_ratio=self._agg_hits / total_f,
            hit_accuracy=self._agg_hit_cor / max(self._agg_hits, 1),
            per_round_latency=lat_sum / np.maximum(frames, 1),
            per_round_accuracy=correct / np.maximum(frames, 1),
            exit_histogram=self._agg_exit.copy(),
            server=self._server)
