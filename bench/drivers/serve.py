"""Serving cells: the program's ``ServingSession`` driven in wall time.

The window drives the session through its seam (``start``, ``begin_window``,
``submit``, ``tick``, ``end_window``) with the benchmark's backbone adapter
as ``tap_fn``.  The session counts in block-ticks; the benchmark times
requests on the host clock, from the moment each was due to the return of
the tick that retires it.

Two loops, chosen by the traffic file's ``loop``:

* ``open``: Poisson due times at the cell's ``rate`` over the window; every
  request due in the window is timed to its retirement, however late.
* ``closed``: ``streams`` callers, each sending its next frame when its
  last one retires; requests retired inside the window are counted.

Θ is held fixed (the traffic's ``theta``) and the table is cut again by
ACA at every ``window_ticks`` block-ticks, from the recency the session
observed.

The world (weights, class directions, the shared set, the multiset of class
runs) comes from the traffic's ``world_seed`` and is the same for every
run; ``--seed`` orders the runs and the arrival gaps and draws each served
frame's noise, so that it does not change how much work a run holds.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import ref_cache, traffic


class Adapter:
    """The backbone adapter, the session's ``tap_fn``: a thin call of
    ``repro.models.prefill`` under one jit named ``bench_backbone``, on
    ``slots`` rows (the tick's batch padded by repeating rows).  This is
    the one place a later benchmark change repoints when the program gains
    a backbone entry of its own.

    Row r of a call is frame ``fold_in(base, r)``; with ``record`` on,
    each call keeps its rows, labels, window and outputs, and which request
    each real row served (``pending`` is set by the scheduler's ``admit``
    just before the call)."""

    def __init__(self, params, model_cfg, cfg: dict, class_dirs, slots: int,
                 spans, mix: dict):
        from repro.models import prefill
        m = cfg["model"]
        tokens, fl, vocab = cfg["tokens"], m["frontend_len"], m["vocab_size"]

        def bench_backbone(params, dirs, labels, rows, base):
            batch = traffic.row_frames(base, rows, labels, dirs,
                                       tokens, fl, vocab, mix)
            _, _, sems, logits = prefill(params, batch, model_cfg)
            return sems.astype(jnp.float32), logits.astype(jnp.float32)

        self.fn = jax.jit(bench_backbone)
        self.params, self.dirs = params, class_dirs
        self.slots, self.spans = slots, spans
        self.base = None
        self.next_row = 0
        self.record = False
        self.calls: list = []
        self.pending: list = []
        self.row_of: dict = {}
        self.dispatch: list = []

    def reset(self, base, record: bool) -> None:
        self.base, self.record, self.next_row = base, record, 0
        self.calls, self.pending, self.row_of = [], [], {}
        self.dispatch = []        # (host time, ms) of each call's dispatch

    def call(self, labels: np.ndarray):
        lab = np.resize(np.asarray(labels, np.int32), self.slots)
        rows = np.arange(self.next_row, self.next_row + self.slots,
                         dtype=np.int32)
        self.next_row += self.slots
        return rows, lab, self.fn(self.params, self.dirs, lab, rows,
                                  self.base)

    def __call__(self, window: int, labels: np.ndarray):
        n = len(labels)
        a = time.perf_counter()
        with self.spans.span("bench.backbone"):
            rows, lab, (sems, logits) = self.call(labels)
        if self.record:
            self.dispatch.append((a, (time.perf_counter() - a) * 1e3))
            for i, rid in enumerate(self.pending):
                self.row_of[rid] = (len(self.calls), i)
            self.calls.append((rows[:n], lab[:n], window, sems, logits))
        self.pending = []
        return sems[:n], logits[:n]

    def frames(self, labels: np.ndarray, base):
        """Taps and logits of ``labels`` (a multiple of ``slots``), made in
        calls of ``slots`` rows from ``base``: the bootstrap's shared set."""
        self.reset(base, record=False)
        out = [self.call(labels[i:i + self.slots])[2]
               for i in range(0, len(labels), self.slots)]
        return (jnp.concatenate([o[0] for o in out]),
                jnp.concatenate([o[1] for o in out]))


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.tr, self.wl = ctx.config, ctx.traffic, ctx.workload
        m = self.cfg["model"]
        self.I, self.d = m["num_classes"], m["d_model"]
        self.L = len(range(m["tap_every"] - 1, m["num_layers"],
                           m["tap_every"]))
        self.slots = self.tr["max_slots"]
        self.nb = self.L + 1
        self.spec = ref_cache.CacheSpec(
            num_classes=self.I, num_layers=self.L, sem_dim=m["sem_dim"],
            theta=self.tr["theta"], round_frames=self.tr["round_frames"],
            mem_budget=float(self.tr["mem_budget_entries"] * self.I
                             * m["sem_dim"]))

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import (AcaPolicy, CacheConfig, CocaCluster,
                                SimulationConfig, calibrate)
        from repro.models.config import ModelConfig
        from repro.serving.batching import BatchingConfig
        from repro.serving.loop import ServeLoopConfig, ServingSession
        cfg, tr, seed, spec = self.cfg, self.tr, self.ctx.seed, self.spec
        self.arch = importlib.import_module(f"bench.configs.{cfg['arch']}")
        world = tr["world_seed"]
        self.params = self.arch.make_params(traffic.jax_key(world, 1), cfg)
        self.class_dirs = jax.random.normal(traffic.jax_key(world, 2),
                                            (self.I, self.d))
        self.keys = {"shared": traffic.jax_key(world, 3),
                     "warm": traffic.jax_key(seed, 4),
                     "rows": traffic.jax_key(seed, 5)}
        self.adapter = Adapter(self.params, ModelConfig(**cfg["model"]), cfg,
                               self.class_dirs, self.slots, self.ctx.spans,
                               tr["frames"])
        self.shared = np.repeat(np.arange(self.I),
                                tr["shared_per_class"]).astype(np.int32)
        taps = self.adapter.frames(self.shared, self.keys["shared"])
        # the shared set's taps as served: the reference profiles R from them
        self.sh_prog = np.asarray(jax.device_get(taps[0]), np.float32)
        cache = CacheConfig(num_classes=self.I, num_layers=self.L,
                            sem_dim=spec.sem_dim, theta=spec.theta)
        cm = calibrate(np.full(self.nb, spec.block_cost),
                       np.full(self.L, spec.sem_dim),
                       head_cost=spec.head_cost)
        sim = SimulationConfig(cache=cache, round_frames=spec.round_frames,
                               mem_budget=spec.mem_budget)
        self.cluster = CocaCluster(sim, cm, policy=AcaPolicy(),
                                   num_clients=1)
        self.cluster.bootstrap(jax.random.PRNGKey(0), taps, self.shared)
        self.r_prog = np.array(self.cluster.r_est, np.float64)
        self.loop_cfg = ServeLoopConfig(
            batching=BatchingConfig(num_blocks=self.nb,
                                    max_slots=self.slots),
            windows=1, window_ticks=tr["window_ticks"],
            slo_ticks=float(tr["slo_blocks"] * self.nb), target=0.9,
            adapt_theta=False, reallocate=True)
        self._session_cls = ServingSession
        self._warm_up(ServingSession)
        self.new_session(self.wl.get("rate"))

    def new_session(self, rate) -> None:
        """A fresh session on the bootstrapped cluster, its traffic drawn
        at ``rate`` (open loop), its admissions recorded."""
        self.adapter.reset(self.keys["rows"], record=True)
        self.session = self._session_cls(self.cluster, self.loop_cfg, None,
                                         self.adapter).start()
        sched = self.session._sched
        admit = sched.admit

        def recorded_admit():
            placed = admit()
            self.adapter.pending = [r.rid for _, r in placed]
            return placed

        sched.admit = recorded_admit
        self._prepare_traffic(rate)

    def _warm_up(self, session_cls) -> None:
        """Every program and shape the window uses: the backbone at
        ``slots`` rows, the tick's lookup and padding for each batch size,
        and a window boundary's table cut."""
        self.adapter.reset(self.keys["warm"], record=False)
        s = session_cls(self.cluster, self.loop_cfg, None,
                        self.adapter).start()
        s.begin_window(0)
        for n in range(1, self.slots + 1):
            for i in range(n):
                s.submit(i % self.I)
            while s.backlog():
                s.tick(0)
        s.end_window(0)
        s.begin_window(1)
        s.report()

    def _prepare_traffic(self, rate) -> None:
        tr, seed = self.tr, self.ctx.seed
        prior = traffic.zipf_prior(self.I, tr["zipf_alpha"])
        world = traffic.rng(tr["world_seed"], 7)
        order = traffic.rng(seed, 7)

        def stream(n):
            return traffic.permute_runs(order, traffic.class_stream(
                world, prior, n, tr["stay_prob"]))

        if tr["loop"] == "open":
            self.due = traffic.poisson_due_times(seed, rate,
                                                 self.ctx.seconds)
            self.labels = stream(len(self.due))
        else:
            self.stream_labels = [stream(tr["max_per_stream"])
                                  for _ in range(tr["streams"])]

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        self.bounds = []          # admissions before each window boundary
        self.tick_ms, self.tick_admit = [], []
        self.latency = {}         # rid -> seconds from due to retirement
        self.late = []            # submit time - due time (generator lag)
        self._vticks, self._w = 0, 0
        self.session.begin_window(0)
        if self.tr["loop"] == "open":
            self._open(seconds)
        else:
            self._closed(seconds)

    def _tick(self, t0: float, due: dict):
        s = self.session
        calls = len(self.adapter.calls)
        a = time.perf_counter()
        with self.ctx.spans.span("bench.tick"):
            retired = s.tick(self._w)
        b = time.perf_counter()
        self.tick_ms.append((b - a) * 1e3)
        self.tick_admit.append(len(self.adapter.calls) > calls)
        for req, _, _ in retired:
            self.latency[req.rid] = b - t0 - due[req.rid]
        self._vticks += 1
        if self._vticks % self.tr["window_ticks"] == 0:
            with self.ctx.spans.span("bench.window_boundary"):
                self.bounds.append(sum(len(c[0]) for c in self.adapter.calls))
                s.end_window(self._w)
                self._w += 1
                s.begin_window(self._w)
        return retired, b - t0

    def _open(self, seconds: float) -> None:
        s, due_t, labels = self.session, self.due, self.labels
        due = {}
        n, i = len(due_t), 0
        t0 = time.perf_counter()
        self.t0 = t0
        while True:
            now = time.perf_counter() - t0
            while i < n and due_t[i] <= now:
                due[s.submit(int(labels[i])).rid] = due_t[i]
                self.late.append(now - due_t[i])
                i += 1
            if s.backlog():
                self._tick(t0, due)
            elif i < n:
                wait = due_t[i] - (time.perf_counter() - t0)
                if wait > 1e-3:
                    with self.ctx.spans.span("bench.wait"):
                        time.sleep(wait - 5e-4)
            else:
                break
        self.loop_s = time.perf_counter() - t0
        self.attempted = n
        self.due_of = due
        self.in_window = len(self.latency)

    def _closed(self, seconds: float) -> None:
        s = self.session
        streams = self.stream_labels
        nxt = [0] * len(streams)
        owner, due = {}, {}
        t0 = time.perf_counter()
        self.t0 = t0

        def send(k, now):
            req = s.submit(int(streams[k][nxt[k] % len(streams[k])]))
            nxt[k] += 1
            owner[req.rid], due[req.rid] = k, now

        for k in range(len(streams)):
            send(k, 0.0)
        self.in_window = 0
        while s.backlog():
            retired, now = self._tick(t0, due)
            for req, _, _ in retired:
                if now <= seconds:
                    self.in_window += 1
                    send(owner[req.rid], now)
        self.loop_s = time.perf_counter() - t0
        self.attempted = len(due)

    # ----------------------------------------------------------- results
    def metrics(self) -> dict:
        rep = self.session.report()
        self.failed = int(rep.shed)
        self.exit_blocks = np.asarray(rep.exit_blocks, np.float64)
        lat = np.array(list(self.latency.values())) * 1e3
        out = {"serve_req_per_s": self.in_window / self.ctx.seconds}
        if len(lat):
            out["serve_p95_ms"] = float(np.percentile(lat, 95))
        late = np.array(self.late or [0.0]) * 1e3
        slow = sorted(self.adapter.dispatch, key=lambda d: -d[1])[:3]
        print(f"[serve] attempted={self.attempted} retired={len(lat)} "
              f"shed={self.failed} in_window={self.in_window} "
              f"loop_s={self.loop_s:.3f} hit_ratio={rep.hit_ratio:.4f} "
              f"p50_ms={np.percentile(lat, 50) if len(lat) else 0:.3f} "
              f"generator_late_ms_p95={np.percentile(late, 95):.3f} "
              f"max={late.max():.3f} slowest_dispatch_ms="
              f"{[(round(t - self.t0, 3), round(ms, 3)) for t, ms in slow]}",
              flush=True)
        return out

    def counters(self) -> dict:
        flops = self.ctx.counts.backbone_flops(self.cfg["model"],
                                               self.cfg["tokens"])
        adm = [t for t, a in zip(self.tick_ms, self.tick_admit) if a]
        return {"tick_ms_admit": adm, "exit_blocks": self.exit_blocks,
                "call_flops": flops * self.slots,
                "window_served_flops": flops * self.in_window,
                "backbone_module": "bench_backbone"}

    def release(self) -> None:
        """Keep what the comparison needs of the served requests, then drop
        the program's state."""
        s, ad = self.session, self.adapter
        done = sorted(r for r in self.latency if r in ad.row_of)
        g = traffic.rng(self.ctx.seed, 21)
        k = min(self.wl["sample"], len(done))
        pick = set(g.choice(done, size=k, replace=False).tolist()) if k else set()
        if done:
            pick.add(max(done, key=lambda r: self.latency[r]))
        pick = sorted(pick)
        calls = [ad.row_of[r] for r in pick]
        rows = np.array([ad.calls[c][0][i] for c, i in calls], np.int32)
        labels = np.array([ad.calls[c][1][i] for c, i in calls], np.int32)
        win = np.array([ad.calls[c][2] for c, _ in calls])
        taps = np.stack([np.asarray(ad.calls[c][3][i]) for c, i in calls])
        cls = np.stack([np.asarray(ad.calls[c][4][i]) for c, i in calls])
        # admission order: row k of the session is the k-th real adapter row
        order = [r for r, _ in sorted(ad.row_of.items(),
                                      key=lambda kv: kv[1])]
        pos = {r: k for k, r in enumerate(order)}
        blocks = np.asarray(s.report().exit_blocks)[[pos[r] for r in pick]]
        hit = blocks < self.nb
        self.sample = {
            "rows": rows, "labels": labels, "window": win, "taps": taps,
            "cls": cls, "hit": hit, "exit": np.where(hit, blocks - 1, self.L),
            "pred": np.array([s._pred_by_rid[r] for r in pick]),
            "admitted": np.concatenate([c[1] for c in ad.calls]),
        }
        self.session = self.adapter = self.cluster = None
        gc.collect()

    # -------------------------------------------------------- comparison
    def _taus(self, admitted) -> list:
        """The recency τ the session fed ACA at each window's cut."""
        out = [np.zeros(self.I, np.int64)]
        for b in self.bounds:
            last = np.full(self.I, -1)
            for k, lab in enumerate(admitted[:b]):
                last[lab] = k
            out.append(np.where(last < 0, b, b - 1 - last))
        return out

    def _reference(self, quant: str = "none"):
        cfg, m = self.cfg, self.cfg["model"]
        dirs, S = self.class_dirs, self.sample

        def batches(base, rows, labels):
            def fn(lo, hi):
                return traffic.row_frames(
                    base, jnp.asarray(rows[lo:hi]),
                    jnp.asarray(labels[lo:hi]), dirs, cfg["tokens"],
                    m["frontend_len"], m["vocab_size"], self.tr["frames"])
            return fn

        block = self.wl["ref_block"]
        sh_rows = np.arange(len(self.shared), dtype=np.int32)
        sh_taps, _ = self.arch.forward(
            self.params, batches(self.keys["shared"], sh_rows, self.shared),
            len(sh_rows), cfg, quant=quant, block=block)
        taps, cls = self.arch.forward(
            self.params, batches(self.keys["rows"], S["rows"], S["labels"]),
            len(S["rows"]), cfg, quant=quant, block=block)
        entries, phi, _ = ref_cache.bootstrap(sh_taps, self.shared, self.spec)
        return taps, cls, sh_taps, entries, phi

    def _lookups(self, taps, entries, phi, r, windows):
        """Eq. 1/2 of ``taps`` on the table each window was cut: the
        reference's centroids, ACA from Φ, the recency the session fed it
        and the profile ``r``.  Yields (rows, Lookup) per window."""
        taus = self._taus(self.sample["admitted"])
        for w in np.unique(windows):
            idx = np.flatnonzero(windows == w)
            cmask, lmask = ref_cache.masks(
                ref_cache.aca(phi, taus[w], r, self.spec))
            yield idx, ref_cache.Lookup(ref_cache.cosines(taps[idx], entries),
                                        cmask, lmask, self.spec.theta,
                                        self.spec.alpha)

    def _numbers(self, got: dict, ref, r_got, sh_got) -> dict:
        """``got``'s served requests, its profile ``r_got`` and its shared
        set's taps ``sh_got`` against the reference ``ref``.  The reference
        profiles R itself, by its own replay over ``sh_got`` (a bfloat16
        backbone moves a shared frame across Θ now and then, and R decides
        which layers are cached), and cuts its tables from that R."""
        taps, cls, sh_ref, entries, phi = ref
        r_follow = ref_cache.bootstrap(sh_got, self.shared, self.spec)[2]
        gaps = np.zeros(len(taps))
        for idx, look in self._lookups(taps, entries, phi, r_follow,
                                       got["window"]):
            gaps[idx] = ref_cache.decision_gap(look, got["hit"][idx],
                                               got["exit"][idx],
                                               got["pred"][idx], cls[idx])
        return {
            "tap_err": float(max(
                np.linalg.norm(got["taps"] - taps, axis=-1).max(),
                np.linalg.norm(sh_got - sh_ref, axis=-1).max())),
            "cls_err": float(np.abs(np.asarray(got["cls"]) - cls).max()),
            "lookup_gap": float(gaps.max()),
            "r_gap": float(np.abs(np.asarray(r_got) - r_follow).max()),
        }

    def check(self) -> dict:
        """The served requests against the plain reference."""
        self._ref = self._reference()
        return self._numbers(self.sample, self._ref, self.r_prog, self.sh_prog)

    def control(self) -> dict:
        """The reference in float8 weights in the program's place: its own
        taps, logits, table and decisions, judged as the program's are."""
        taps, cls, sh, entries, phi = self._reference(quant="fp8")
        r0 = ref_cache.bootstrap(sh, self.shared, self.spec)[2]
        S = dict(self.sample, taps=taps, cls=cls, hit=np.zeros(len(taps), bool),
                 exit=np.full(len(taps), self.L), pred=cls.argmax(axis=1))
        for idx, look in self._lookups(taps, entries, phi, r0, S["window"]):
            S["hit"][idx], S["exit"][idx] = look.hit, look.exit
            S["pred"][idx] = np.where(look.hit, look.pred, S["pred"][idx])
        return self._numbers(S, self._ref, r0, sh)
