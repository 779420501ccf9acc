"""The online serving session: admission → batched fused lookup → Θ control.

This module closes the paper's SLO loop (§Abstract, §I, §VI.D) end to end.
Where ``launch/serve.py`` used to run the cluster first and *replay* its
metrics through the batching simulator afterwards, :class:`ServingSession`
is event-driven and online:

1. **Arrivals** — an open-loop :class:`~repro.data.scenarios.RequestStream`
   (Poisson or bursty arrivals × any stream process, so a ``Drift`` workload
   rotates its hot set across serving windows) lands requests tick by tick,
   each stamped with an absolute deadline ``arrival + slo_ticks``.
2. **Admission** — the :class:`~repro.serving.scheduler.EDFScheduler` fills
   free batch slots earliest-deadline-first and sheds requests that cannot
   meet their deadline even if started immediately (at the *estimated* cost
   derived from the server's profiled first-hit CDF R).
3. **Classification** — each tick's newly admitted requests are batched and
   classified through the real fused lookup path:
   :func:`~repro.core.semantic_cache.lookup_all_layers` on the **live**
   serving table cut by :meth:`CocaCluster.serving_table
   <repro.core.engine.CocaCluster.serving_table>` — not oracle exit layers.
   The lookup's verdict (first hitting tap, or a full-depth miss) *resolves*
   the slot's true block count; early exits retire slots early and the next
   queued request refills them — continuous batching as the execution
   engine, with the same block-tick accounting as
   :mod:`repro.serving.batching` (which is exactly what makes the session
   replay-parity-testable against ``simulate``).
4. **Control** — at every window boundary the window's
   :class:`~repro.serving.scheduler.SLOStats` drive the
   :class:`~repro.serving.scheduler.ThetaController` (attainment below
   target lowers Θ for more early exits; slack raises it for accuracy) via
   ``cluster.set_theta``, **and** the observed request recency τ feeds
   between-window ACA re-allocation via ``cluster.serving_table`` — the
   cache adapts online exactly as §VI.D's Θ-per-SLO table prescribes,
   but continuously.

Latency accounting: scheduler latencies are in raw block-ticks
(queue wait + execution); the per-tap lookup overhead is applied to the
session's busy ticks exactly as ``simulate`` applies it
(``ticks * (1 + lookup_tick_fraction)``), so live and replay numbers are
directly comparable.  Idle ticks (open-loop lulls) execute no block-batch
and are excluded from the compute bill.

Drivers: ``python -m repro.launch.serve`` (synthetic taps),
``examples/serve_stream.py`` (a real transformer backbone supplying the tap
vectors), ``benchmarks/table2_slo.py`` (the load sweep behind
``BENCH_serving.json``).
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.semantic_cache import CacheTable, lookup_all_layers
from repro.data.scenarios import RequestStream
from repro.serving.batching import BatchingConfig
from repro.serving.scheduler import (EDFScheduler, Request, SLOStats,
                                     ThetaController)

# TapFn: (window_index, labels (N,)) -> (sems (N, L, d), logits (N, C)).
# The session batches each tick's admitted requests into one call.
ServeTapFn = Callable[[int, np.ndarray], tuple]


@partial(jax.jit, static_argnames=("cfg",))
def _batched_lookup(table: CacheTable, sems: jax.Array, cfg):
    """The session's per-tick lookup, compiled once per (shape, Θ): ticks
    pad their admitted batch to ``max_slots`` rows so every tick re-hits
    the same trace (Θ changes retrace, but the controller quantises)."""
    return lookup_all_layers(table, sems, cfg)


@dataclasses.dataclass(frozen=True)
class ServeLoopConfig:
    """Knobs of one online serving session.

    ``slo_ticks`` is the per-request deadline in block-ticks (the paper's
    per-task deadline, §I); ``windows`` × ``window_ticks`` is the horizon.
    Θ control and re-allocation can be frozen independently — the
    ``frozen-Θ`` baseline of ``BENCH_serving.json`` is ``adapt_theta=False,
    reallocate=False``.
    """

    batching: BatchingConfig
    windows: int = 8                 # control windows
    window_ticks: int = 64           # block-ticks per window
    slo_ticks: float = 30.0          # deadline = arrival + slo_ticks
    target: float = 0.95             # attainment target for Θ control
    margin: float = 0.02             # controller hysteresis half-width
    theta_step: float = 0.1          # multiplicative Θ step
    theta_lo: float = 0.01
    theta_hi: float = 0.5
    adapt_theta: bool = True         # drive Θ from window attainment
    reallocate: bool = True          # between-window ACA re-allocation
    drain: bool = True               # finish the backlog after the horizon
    drain_max_ticks: int = 100_000

    def __post_init__(self):
        if self.windows < 1 or self.window_ticks < 1:
            raise ValueError("windows and window_ticks must be >= 1")
        if self.slo_ticks <= 0:
            raise ValueError("slo_ticks must be > 0")


class WindowReport(NamedTuple):
    """One control window as the session saw it."""

    window: int
    theta: float              # Θ in force *during* this window
    stats: SLOStats           # idle-window safe
    arrivals: int
    hits: int                 # cache-resolved among requests admitted
    admitted: int
    reallocated: bool
    degraded: bool = False    # served from a stale/absent table (sync fault)


class SessionResult(NamedTuple):
    """The live session's outcome — no metric replay involved.

    ``ticks`` is the lookup-adjusted busy-tick bill (block-batch executions
    actually run, idle ticks excluded); ``throughput`` is served requests
    per adjusted tick, the number load-level comparisons divide.
    ``exit_blocks`` holds every admitted request's resolved block count in
    admission order — feeding it to :func:`repro.serving.batching.simulate`
    reproduces the session's tick bill exactly on a backlogged trace (the
    parity test).
    """

    stats: SLOStats
    windows: list
    ticks: float
    served: int
    shed: int
    arrivals: int
    hit_ratio: float          # of admitted requests
    accuracy: float           # of served requests with known labels
    throughput: float
    theta_trace: list
    exit_blocks: np.ndarray


class ServingSession:
    """One client's online serving loop over a live CoCa cluster.

    ``cluster`` — a bootstrapped :class:`~repro.core.engine.CocaCluster`
    whose policy cuts the serving table (any ``AllocationPolicy``).
    ``workload`` — the open-loop request stream.  ``tap_fn(window, labels)``
    supplies the semantic taps and full-model logits for a batch of
    admitted requests — synthetic taps in the launcher, a real backbone's
    taps in ``examples/serve_stream.py``.  ``use_cache=False`` runs the
    same loop with the lookup disabled (every request pays all blocks) —
    the live no-cache baseline.

    Faults: with ``faults=`` (a :class:`repro.distributed.faults.FaultSpec`)
    every window-boundary table download runs through the spec's download
    matrix and outage windows, keyed by **window index** in place of the
    engine's round index.  ``hardened=True`` retries a failed transfer
    under ``retry``'s budget and otherwise serves the window from the last
    good table (staleness-counted, cache-off past ``stale_limit``) while
    the Θ controller **holds**
    (:meth:`~repro.serving.scheduler.ThetaController.hold`) — a
    fault-induced attainment dip says nothing about Θ.  ``hardened=False``
    is the naive contrast: one attempt, a dropped table serves full-depth,
    a corrupt/truncated one is used as delivered, and Θ reacts to the dip
    it caused.  An empty spec is discarded outright, so the zero-fault
    session is the pre-fault code path bit-for-bit.
    """

    def __init__(self, cluster, cfg: ServeLoopConfig,
                 workload: RequestStream | None, tap_fn: ServeTapFn, *,
                 use_cache: bool = True, client: int = 0,
                 faults=None, retry=None, hardened: bool = True,
                 stale_limit: int = 4):
        if (workload is not None
                and workload.num_classes != cluster.sim.cache.num_classes):
            raise ValueError(
                f"workload has {workload.num_classes} classes, cluster cache "
                f"has {cluster.sim.cache.num_classes}")
        self.cluster = cluster
        self.cfg = cfg
        self.workload = workload
        self.tap_fn = tap_fn
        self.use_cache = use_cache
        self.client = client
        self._faults = None
        if faults is not None and not faults.empty:
            from repro.distributed.faults import RetryPolicy
            self._faults = faults
            self.retry = retry if retry is not None else RetryPolicy()
        self.hardened = hardened
        self.stale_limit = stale_limit
        self._good_table = None      # last successfully synced table
        self._stale = 0              # windows since a good sync
        self._pad_block = None       # device pad rows, armed by start()
        I = cluster.sim.cache.num_classes
        # request-stream recency: tau_i = admitted requests since class i
        # was last observed (the engine's Eq.-10 unit, fed back at each
        # window boundary so ACA tracks the *served* distribution)
        self._last_seen = np.full(I, -1, np.int64)
        self._seen = 0

    # ----------------------------------------------------------------- utils
    def _estimated_blocks(self) -> float:
        """Cold-start admission cost estimate: expected blocks under the
        server's profiled first-hit CDF R (full depth without a cache).
        Once windows complete, the estimate tracks the *observed* resolved
        block counts instead (EWMA at each window boundary) — a static
        estimate goes stale the moment the Θ controller moves, and a stale
        underestimate admits doomed requests the shedding valve should have
        dropped."""
        nb = self.cfg.batching.num_blocks
        if not self.use_cache:
            return float(nb)
        r = np.asarray(self.cluster.r_est, float)
        first = np.diff(np.concatenate([[0.0], np.clip(r, 0.0, 1.0)]))
        first = np.clip(first, 0.0, None)
        blocks = np.arange(1, len(r) + 1, dtype=float)
        exp = float((first * blocks).sum() + (1.0 - min(r[-1], 1.0)) * nb)
        return float(np.clip(exp, 1.0, nb))

    def _observe(self, labels: np.ndarray) -> None:
        for lab in labels:
            self._last_seen[int(lab)] = self._seen
            self._seen += 1

    def _tau(self) -> np.ndarray:
        # never-requested classes are maximally stale (Eq. 10 scores LOW tau
        # as hot); at cold start (_seen == 0) this is all-zeros, matching
        # the engine's fresh-client convention
        tau = np.where(self._last_seen < 0, self._seen,
                       self._seen - 1 - self._last_seen)
        return tau.astype(np.int32)

    def _window_table(self, w: int):
        """The serving table for window ``w``, resolved through the fault
        spec (the identity when none is armed): ``(table, degraded)``.

        The serving loop's clock is block-ticks, so the retry budget is
        honoured in *wall seconds that never hit the tick bill* — the
        window boundary is between ticks; what the budget still decides is
        how many redraws a hardened client gets before giving up.
        """
        if not self.use_cache:
            return None, False

        def cut():
            return self.cluster.serving_table(
                client=self.client, tau=self._tau(), round_index=w)

        if self._faults is None:
            return cut(), False
        from repro.distributed.faults import (_DOM_CORRUPT_DOWN, _DOM_JITTER,
                                              corrupt_table, truncate_table)
        spec = self._faults
        down = spec.server_down(w)
        fault = "drop" if down else spec.draw_download(w, self.client)
        if fault == "ok":
            table = cut()
            self._good_table, self._stale = table, 0
            return table, False
        if self.hardened:
            jit_rng = spec.rng(_DOM_JITTER, w, self.client, 2)
            spent = 0.0
            for attempt in range(self.retry.max_retries):
                wait = self.retry.backoff(attempt, jit_rng)
                if spent + wait > self.retry.timeout:
                    break
                spent += wait
                redraw = ("drop" if down else
                          spec.draw_download(w, self.client,
                                             attempt=attempt + 1))
                if redraw == "ok":
                    table = cut()
                    self._good_table, self._stale = table, 0
                    return table, False
            self._stale += 1
            if (self._good_table is not None
                    and self._stale <= self.stale_limit):
                return self._good_table, True        # bounded-stale table
            return None, True                        # cache-off
        # naive: one attempt, serve whatever the wire delivered
        self._stale += 1
        if fault == "corrupt":
            return corrupt_table(
                cut(), spec.rng(_DOM_CORRUPT_DOWN, w, self.client)), True
        if fault == "partial":
            return truncate_table(cut(), spec.partial_frac), True
        return None, True                            # dropped download

    def _classify(self, window: int, labels: np.ndarray,
                  table: CacheTable | None):
        """The per-tick batched classification: real taps, real fused
        lookup on the live table.  Returns (blocks, hit, pred)."""
        nb = self.cfg.batching.num_blocks
        n = len(labels)
        with obs.span("coca.tick.backbone"):
            sems, logits = self.tap_fn(window, labels)
        if not (self.use_cache and table is not None):
            # the no-cache tick's one bundled transfer (tap_fn may hand back
            # device arrays); explicit, so the transfer guard stays quiet
            with obs.span("coca.tick.sync"):
                logits = jax.device_get(logits)  # cocalint: disable=CL202
            model_pred = np.argmax(logits, axis=1).astype(np.int32)
            return (np.full(n, nb, np.int64), np.zeros(n, bool), model_pred)
        with obs.span("coca.tick.lookup"):
            sems = jnp.asarray(sems)     # explicit h2d — guard-legal
            pad = self.cfg.batching.max_slots - n
            if pad > 0:                  # fixed shape -> one compiled trace
                # lax.slice_in_dim, not _pad_block[:pad]: eager jnp basic
                # indexing materialises its index scalars host-side (an
                # implicit transfer); the lax slice is fully static.
                sems = jnp.concatenate(
                    [sems, jax.lax.slice_in_dim(self._pad_block, 0, pad)])
            look = _batched_lookup(table, sems, self.cluster.sim.cache)
        # The tick's ONE bundled device->host transfer: lookup verdicts and
        # model logits ride together (the serving-tick edition of PR 1's
        # one-device_get-per-round contract).
        with obs.span("coca.tick.sync"):
            # cocalint: disable=CL202
            hit, exit_layer, cache_pred, logits = jax.device_get(
                (look.hit, look.exit_layer, look.pred, logits))
        model_pred = np.argmax(logits, axis=1).astype(np.int32)
        hit = hit[:n]
        blocks = np.where(hit, np.minimum(exit_layer[:n] + 1, nb), nb)
        pred = np.where(hit, cache_pred[:n], model_pred)
        return blocks.astype(np.int64), hit, pred.astype(np.int32)

    # ----------------------------------------------- the replica-facing seam
    #
    # A gateway tier (repro.fleet.gateway.FleetGateway) drives N replica
    # sessions in lockstep through these methods instead of run():
    # start() → per window: begin_window / submit / tick / end_window →
    # report().  run() itself is written on the same seam, so a 1-replica
    # fleet that replays the same call sequence is bit-identical to a bare
    # session (the degenerate-case parity test in tests/test_fleet.py).

    def start(self) -> "ServingSession":
        """Arm the session's run state (scheduler, Θ controller, window-0
        table, admission estimate).  Idempotent per run; must precede any
        submit/tick call."""
        cfg = self.cfg
        self._sched = EDFScheduler(max_slots=cfg.batching.max_slots)
        self._ctl = ThetaController(
            theta=float(self.cluster.sim.cache.theta), target=cfg.target,
            margin=cfg.margin, step=cfg.theta_step,
            lo=cfg.theta_lo, hi=cfg.theta_hi)
        self._table, self._degraded_now = self._window_table(0)
        # Device-resident pad rows for the tick's fixed-shape lookup batch,
        # built once per run via an *explicit* device_put: padding a tick
        # with eager jnp.zeros would materialise a fresh host constant
        # every tick (an implicit transfer the sanitizer's guard forbids).
        cc = self.cluster.sim.cache
        self._pad_block = jax.device_put(
            np.zeros((cfg.batching.max_slots, cc.num_layers, cc.sem_dim),
                     np.float32))
        self._est_f = self._estimated_blocks()
        self._est = int(np.ceil(self._est_f))
        self._labels_by_rid: dict[int, int] = {}
        self._pred_by_rid: dict[int, int] = {}
        # host clock at submit, popped at admission (the queue wait); a
        # shed request's stays, as its label does
        self._submit_ns: dict[int, int] = {}
        self._exit_blocks: list[int] = []
        self._reports: list[WindowReport] = []
        self._theta_trace: list[float] = []
        self._correct = self._served_labeled = 0
        self._next_rid = 0
        self._admitted_total = self._hits_total = self._arrivals_total = 0
        self._win0 = (0, 0, 0, 0)        # window-start counter snapshot
        return self

    @property
    def estimate(self) -> float:
        """The current (EWMA-tracked) expected block cost at admission."""
        return self._est_f

    def set_estimate(self, est_f: float) -> None:
        """Override the admission cost estimate — the fleet gateway lifts
        the EWMA to fleet level (one estimate from every replica's resolved
        blocks) and pushes it back down here each window."""
        self._est_f = float(est_f)
        self._est = int(np.ceil(self._est_f))

    def submit(self, label: int, *, arrival: float | None = None,
               deadline: float | None = None) -> Request:
        """Enqueue one request.  ``arrival``/``deadline`` default to the
        session clock and the configured SLO; a gateway re-dispatching a
        spilled request passes the originals so the deadline survives the
        hop.  Returns the stamped :class:`Request`."""
        sched = self._sched
        arrival = sched.tick if arrival is None else float(arrival)
        if deadline is None:
            deadline = arrival + self.cfg.slo_ticks
        req = Request(rid=self._next_rid, arrival=arrival,
                      blocks_needed=self._est, deadline=float(deadline))
        self._labels_by_rid[req.rid] = int(label)
        self._submit_ns[req.rid] = time.perf_counter_ns()
        self._next_rid += 1
        self._arrivals_total += 1
        sched.submit(req)
        return req

    def tick(self, window: int) -> list[tuple[Request, float, bool]]:
        """One block-tick: EDF admission → batched live lookup resolves the
        admitted requests → advance.  Returns the retirements
        ``(request, latency, missed)``.  Safe on an idle (or evacuated)
        session — the clock still advances, which is what keeps a fleet's
        replicas tick-synchronised through an outage."""
        sched = self._sched
        with obs.span("coca.tick", tick=int(sched.tick)):
            with obs.span("coca.tick.admit"):
                placed = sched.admit()
            if placed:
                now = time.perf_counter_ns()
                wait_us = [(now - self._submit_ns.pop(r.rid)) // 1000
                           for _, r in placed]
                labs = np.asarray(
                    [self._labels_by_rid[r.rid] for _, r in placed], np.int32)
                with obs.span("coca.tick.classify", rows=len(placed),
                              wait_us_sum=sum(wait_us),
                              wait_us_max=max(wait_us)):
                    blocks, hit, pred = self._classify(window, labs,
                                                       self._table)
            with obs.span("coca.tick.retire"):
                if placed:
                    for (slot, req), b, p in zip(placed, blocks, pred):
                        sched.resolve(slot, int(b))
                        self._pred_by_rid[req.rid] = int(p)
                        self._exit_blocks.append(int(b))
                    self._observe(labs)
                    self._admitted_total += len(placed)
                    self._hits_total += int(hit.sum())
                retired = sched.advance()
                for req, _lat, _missed in retired:
                    lab = self._labels_by_rid[req.rid]
                    self._served_labeled += 1
                    self._correct += int(self._pred_by_rid[req.rid] == lab)
        return retired

    def begin_window(self, window: int) -> None:
        """Open control window ``window``: record the Θ in force and mark
        the scheduler's window-stat baseline."""
        self._theta_trace.append(float(self.cluster.sim.cache.theta))
        self._win0 = (self._admitted_total, self._hits_total,
                      len(self._exit_blocks), self._arrivals_total)
        self._sched.begin_window()

    def window_blocks(self) -> list[int]:
        """The block counts this window's lookups actually resolved — the
        fleet gateway pools these across replicas for the lifted estimate."""
        return self._exit_blocks[self._win0[2]:]

    def window_stats(self) -> SLOStats:
        return self._sched.window_stats()

    def refresh_estimate(self) -> None:
        """EWMA the admission estimate toward this window's resolved block
        counts (tracks the Θ controller)."""
        blocks = self.window_blocks()
        if blocks:
            self._est_f = 0.5 * self._est_f + 0.5 * float(np.mean(blocks))
            self._est = int(np.ceil(self._est_f))

    def end_window(self, window: int, *, control: bool = True,
                   reallocate: bool | None = None) -> WindowReport:
        """Close window ``window``: stats → (optionally) estimate refresh +
        Θ control → table re-allocation for the next window → report.

        ``control=False`` skips the session's own estimate/Θ updates — the
        gateway owns both at fleet level and pushes its verdicts through
        :meth:`set_estimate` / ``cluster.set_theta`` before calling this.
        ``reallocate`` overrides ``cfg.reallocate`` for this boundary (an
        outaged replica cannot download a fresh cut)."""
        cfg = self.cfg
        stats = self._sched.window_stats()
        realloc = False
        if control:
            # refresh the admission estimate from what this window's
            # lookups actually resolved (tracks the Θ controller)
            self.refresh_estimate()
            # close the loop: attainment -> Θ, observed recency -> ACA.
            # A degraded window's dip is a sync fault, not a Θ signal —
            # the hardened session holds AIMD instead of chasing it.
            if cfg.adapt_theta and stats.served + stats.shed > 0:
                if (self._degraded_now and self.hardened
                        and self._faults is not None):
                    self._ctl.hold()
                else:
                    self.cluster.set_theta(self._ctl.update(stats.attainment))
        was_degraded = self._degraded_now
        do_realloc = cfg.reallocate if reallocate is None else reallocate
        if do_realloc and self.use_cache:
            self._table, self._degraded_now = self._window_table(window + 1)
            realloc = not self._degraded_now
        report = WindowReport(
            window=window, theta=self._theta_trace[-1], stats=stats,
            arrivals=self._arrivals_total - self._win0[3],
            hits=self._hits_total - self._win0[1],
            admitted=self._admitted_total - self._win0[0],
            reallocated=realloc, degraded=was_degraded)
        self._reports.append(report)
        return report

    def resync(self, window: int) -> None:
        """Re-cut the serving table mid-horizon — a recovered fleet replica
        returning from an outage pulls a fresh allocation for ``window``."""
        if self.use_cache:
            self._table, self._degraded_now = self._window_table(window)

    def reset_recency(self) -> None:
        """Forget the observed request recency — a replica whose outage
        outlasted the churn stale limit rejoins cold (the fleet analogue of
        ``rejoin_client(fresh=True)``)."""
        self._last_seen = np.full(len(self._last_seen), -1, np.int64)
        self._seen = 0

    def evacuate(self) -> list[tuple[Request, int]]:
        """Pull every queued and in-flight request off this session — the
        outage spill: the gateway re-dispatches them to hash-ring neighbor
        replicas (partial block progress on in-flight slots is lost, which
        is exactly what a replica crash costs).  Returns ``(request,
        label)`` in deadline (EDF) order; the session is left idle but its
        clock and counters intact."""
        sched = self._sched
        out = []
        while sched.queue:
            _, _, req = heapq.heappop(sched.queue)
            self._submit_ns.pop(req.rid)
            out.append((req, self._labels_by_rid[req.rid]))
        for i, s in enumerate(sched.slots):
            if s is not None:
                req, _remaining, _start = s
                out.append((req, self._labels_by_rid[req.rid]))
                sched.slots[i] = None
        out.sort(key=lambda rl: (rl[0].deadline, rl[0].rid))
        return out

    def backlog(self) -> int:
        """Queued + in-flight requests — the gateway's load signal."""
        sched = self._sched
        return len(sched.queue) + sum(s is not None for s in sched.slots)

    @property
    def latencies(self) -> list[float]:
        """Per-request latencies retired so far (block-ticks) — the fleet
        aggregates these across replicas for fleet-level p50/p95."""
        return list(self._sched.latencies)

    def window_latencies(self) -> list[float]:
        """Latencies retired since :meth:`begin_window` (the slice behind
        :meth:`window_stats`'s percentiles)."""
        return list(self._sched.latencies[self._sched._mark[3]:])

    @property
    def hits(self) -> int:
        """Lookup hits so far (numerator of :attr:`SessionResult.hit_ratio`)."""
        return self._hits_total

    @property
    def admitted(self) -> int:
        """Requests admitted to a batch slot so far."""
        return self._admitted_total

    def drain_backlog(self, window: int | None = None) -> None:
        """Tick until the queue and slots are empty (bounded by
        ``cfg.drain_max_ticks``)."""
        cfg = self.cfg
        if window is None:
            window = cfg.windows - 1
        sched = self._sched
        t = 0
        while ((sched.queue or any(s is not None for s in sched.slots))
               and t < cfg.drain_max_ticks):
            self.tick(window)
            t += 1

    def report(self) -> SessionResult:
        """The session's outcome so far — the replica-facing counterpart of
        :meth:`run`'s return value."""
        sched = self._sched
        overhead = (1 + self.cfg.batching.lookup_tick_fraction
                    if self.use_cache else 1.0)
        ticks = sched.busy_ticks * overhead
        return SessionResult(
            stats=sched.stats(), windows=list(self._reports), ticks=ticks,
            served=sched.served, shed=sched.shed,
            arrivals=self._arrivals_total,
            hit_ratio=self._hits_total / max(self._admitted_total, 1),
            accuracy=self._correct / max(self._served_labeled, 1),
            throughput=sched.served / max(ticks, 1e-9),
            theta_trace=list(self._theta_trace),
            exit_blocks=np.asarray(self._exit_blocks, np.int64))

    # ------------------------------------------------------------------ run
    def run(self) -> SessionResult:
        """The classic closed loop, expressed on the seam."""
        if self.workload is None:
            raise RuntimeError("run() needs a workload; gateway-managed "
                               "sessions are driven through the seam "
                               "(start/submit/tick/end_window)")
        cfg = self.cfg
        self.start()
        for w in range(cfg.windows):
            self.begin_window(w)
            counts, labels = self.workload.window(w, cfg.window_ticks)
            offsets = np.concatenate([[0], np.cumsum(counts)])
            for t in range(cfg.window_ticks):
                for lab in labels[offsets[t]:offsets[t + 1]]:
                    self.submit(int(lab))
                self.tick(w)
            self.end_window(w)
        if cfg.drain:
            self.drain_backlog(cfg.windows - 1)
        return self.report()


def throughput_gain(cached: SessionResult, nocache: SessionResult) -> float:
    """Live throughput multiple: served-per-adjusted-tick ratio between a
    cached session and its no-cache twin on the same workload.  Idle-safe:
    two idle sessions gain exactly 1.0."""
    if cached.served == 0 and nocache.served == 0:
        return 1.0
    return cached.throughput / max(nocache.throughput, 1e-9)
