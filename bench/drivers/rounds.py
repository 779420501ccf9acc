"""Collaborative-round cells: ``CocaCluster.step`` back to back.

Each round is ``clients`` x ``frames`` taps from the benchmark's copy of the
synthetic tap model, Dirichlet non-IID class streams, made on the device.
The world (tap model, client priors and contexts) comes from the traffic
file's ``world_seed`` and is the same for every run; ``--seed`` draws the
class streams and the tap noise.

Set-up builds the cluster, bootstraps it from a domain-shifted calibration
set and drives it through its first ``checked_rounds`` rounds with the
window's own call; the comparison replays those rounds in the plain
reference.  The window then runs rounds until ``--seconds`` have passed;
``round_accuracy`` is taken over its first ``accuracy_rounds`` rounds,
which a run completes after the window if it has not within it.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import ref_cache, traffic


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.tr, self.wl = ctx.config, ctx.traffic, ctx.workload
        m, tr = self.cfg["model"], self.tr
        self.I = m["num_classes"]
        self.L = len(range(m["tap_every"] - 1, m["num_layers"],
                           m["tap_every"]))
        self.d = m["sem_dim"]
        self.K, self.F = tr["clients"], tr["frames"]
        self.spec = ref_cache.CacheSpec(
            num_classes=self.I, num_layers=self.L, sem_dim=self.d,
            theta=tr["theta"], round_frames=self.F,
            mem_budget=float(tr["mem_budget_entries"] * self.I * self.d))

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import (AcaPolicy, CacheConfig, CocaCluster,
                                FrameBatch, SimulationConfig, calibrate)
        tr, seed, spec = self.tr, self.ctx.seed, self.spec
        self.FrameBatch = FrameBatch
        scfg = traffic.StreamConfig(num_classes=self.I, num_layers=self.L,
                                    sem_dim=self.d)
        wk = jax.random.PRNGKey(tr["world_seed"])
        tm = traffic.make_tap_model(jax.random.fold_in(wk, 0), scfg)
        tm_cal = traffic.perturb_tap_model(jax.random.fold_in(wk, 1), tm,
                                           tr["calib_shift"])
        group = jax.random.fold_in(wk, 2)
        ctxs = jnp.stack([traffic.client_context(
            jax.random.fold_in(wk, 10 + k), scfg, group)
            for k in range(self.K)])
        wg = np.random.default_rng(tr["world_seed"])
        priors = traffic.dirichlet_priors(wg, self.K, self.I,
                                          tr["dirichlet_p"])
        g = traffic.rng(seed, 7)
        n = tr["label_rounds"]
        self.labels = np.stack([traffic.class_stream(
            g, priors[k], n * self.F, tr["stay_prob"]).reshape(n, self.F)
            for k in range(self.K)], axis=1)              # (n, K, F)
        run_key = traffic.jax_key(seed, 8)

        @jax.jit
        def bench_frames(r, labels, run_key, ctxs):
            def one(k, lab, ctx):
                key = jax.random.fold_in(jax.random.fold_in(run_key, r), k)
                return traffic.synthesize_taps(key, tm, lab, scfg, ctx)
            sems, logits = jax.vmap(one)(jnp.arange(self.K), labels, ctxs)
            return ([sems[k] for k in range(self.K)],
                    [logits[k] for k in range(self.K)])

        self._frames_fn = bench_frames
        self._frames_args = (run_key, ctxs)
        self.shared = np.tile(np.arange(self.I), tr["calib_per_class"])
        self.cal = jax.jit(lambda lab: traffic.synthesize_taps(
            jax.random.fold_in(wk, 3), tm_cal, lab, scfg))(
                jnp.asarray(self.shared))
        cm = calibrate(np.full(self.L + 1, spec.block_cost),
                       np.full(self.L, self.d), head_cost=spec.head_cost)
        sim = SimulationConfig(
            cache=CacheConfig(num_classes=self.I, num_layers=self.L,
                              sem_dim=self.d, theta=spec.theta),
            round_frames=self.F, mem_budget=spec.mem_budget)
        self.cluster = CocaCluster(sim, cm, policy=AcaPolicy(),
                                   num_clients=self.K)
        self.cluster.bootstrap(jax.random.PRNGKey(0), self.cal, self.shared)
        self.round = 0
        self.checked = [self._checked_round()
                        for _ in range(tr["checked_rounds"])]

    def frames(self, r: int):
        lab = self.labels[r % len(self.labels)]
        sems, logits = self._frames_fn(r, lab, *self._frames_args)
        return [self.FrameBatch(s, lo, lab[k])
                for k, (s, lo) in enumerate(zip(sems, logits))]

    def _checked_round(self) -> dict:
        """One round through ``step``, with what the comparison needs: the
        tables each client was cut, the served decisions and the server
        after the merge."""
        fr = self.frames(self.round)
        tables = self.cluster.allocate_tables()
        masks = jax.device_get([(t.class_mask, t.layer_mask) for t in tables])
        m = self.cluster.step(fr)
        self.round += 1
        srv = self.cluster.server
        ent, phi, r = jax.device_get((srv.entries, srv.phi_global, srv.r_est))
        return {"frames": [(np.asarray(f.sems), np.asarray(f.logits),
                            np.asarray(f.labels)) for f in fr],
                "masks": masks, "hit": m.hit.reshape(self.K, self.F),
                "exit": m.exit_layer.reshape(self.K, self.F),
                "pred": m.pred.reshape(self.K, self.F),
                "entries": np.asarray(ent, np.float64),
                "phi": np.asarray(phi, np.float64),
                "r": np.asarray(r, np.float64)}

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        spans, acc_n = self.ctx.spans, self.wl["accuracy_rounds"]
        self.correct = []
        done = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds and len(self.correct) >= acc_n:
                break
            fr = self.frames(self.round)
            with spans.span("bench.step"):
                m = self.cluster.step(fr)
            self.round += 1
            if time.perf_counter() - t0 <= seconds:
                done += 1
            if len(self.correct) < acc_n:
                self.correct.append(int(m.correct))
        self.rounds_in_window = done
        self.loop_s = time.perf_counter() - t0
        self.attempted = done * self.K * self.F

    def metrics(self) -> dict:
        self.failed = 0
        frames = len(self.correct) * self.K * self.F
        acc = sum(self.correct) / frames
        print(f"[rounds] rounds_in_window={self.rounds_in_window} "
              f"loop_s={self.loop_s:.3f} accuracy_rounds={len(self.correct)}"
              f" hit_ratio={self.cluster.result().hit_ratio:.4f}",
              flush=True)
        return {"round_frames_per_s":
                self.rounds_in_window * self.K * self.F / self.ctx.seconds,
                "round_accuracy": 100.0 * acc}

    def counters(self) -> dict:
        return {"rounds": self.rounds_in_window, "K": self.K, "F": self.F,
                "L": self.L, "I": self.I, "d": self.d}

    def release(self) -> None:
        self.cluster = None
        gc.collect()

    # -------------------------------------------------------- comparison
    def replay(self, run, precision="highest", follow: bool = True) -> dict:
        """The checked rounds in the reference at ``precision``.  With
        ``follow`` it takes the served decisions of ``run`` (the program's
        or the control's) and measures how far each lies from its own
        scores; without, it makes its own and returns them as a run."""
        spec, K, L = self.spec, self.K, self.L
        sems_cal, _ = self.cal
        entries, phi, r = ref_cache.bootstrap(np.asarray(sems_cal),
                                              self.shared, spec, precision)
        clients = [ref_cache.Client(tau=np.zeros(self.I)) for _ in range(K)]
        out, gaps, fragile_n = [], [], 0
        cut_diff = server_gap = 0.0
        for i, rnd in enumerate(self.checked):
            masks, hit, exit_, pred = [], [], [], []
            fragile = np.zeros((L, self.I), bool)
            looks = []
            for k, cl in enumerate(clients):
                cm, lm = ref_cache.masks(ref_cache.aca(phi, cl.tau, r, spec))
                masks.append((cm, lm))
                sems, logits, _ = rnd["frames"][k]
                look = ref_cache.Lookup(
                    ref_cache.cosines(sems, entries, precision), cm, lm,
                    spec.theta, spec.alpha)
                looks.append(look)
                if follow:
                    h, e, p = rnd["hit"][k], rnd["exit"][k], rnd["pred"][k]
                    pc, lc = run[i]["masks"][k]
                    cut_diff += int((np.asarray(pc) != cm).sum()
                                    + (np.asarray(lc) != lm).sum())
                    gaps.append(ref_cache.decision_gap(look, h, e, p,
                                                       logits).max())
                else:
                    h, e = look.hit, look.exit
                    p = np.where(h, look.pred, np.asarray(logits).argmax(1))
                hit.append(h)
                exit_.append(e)
                pred.append(p)
                fragile |= ref_cache.client_round(
                    cl, look, sems, logits, h, e, p, spec,
                    self.wl["fragile_eps"])
            for cl in clients:
                entries, phi, r = ref_cache.merge(entries, phi, r, cl, spec)
            out.append({"masks": masks, "hit": np.stack(hit),
                        "exit": np.stack(exit_), "pred": np.stack(pred),
                        "entries": entries, "phi": phi, "r": r,
                        "frames": rnd["frames"]})
            if follow:
                keep = ~fragile
                fragile_n += int(fragile.sum())
                de = np.abs(run[i]["entries"] - entries)[keep].max()
                dr = np.abs(run[i]["r"] - r).max()
                server_gap = max(server_gap, float(de), float(dr))
                cut_diff += int((run[i]["phi"] != phi).sum())
        if not follow:
            return out
        print(f"[rounds] fragile_cells={fragile_n}", flush=True)
        return {"lookup_gap": float(max(gaps)), "server_gap": server_gap,
                "cut_diff": float(cut_diff)}

    def check(self) -> dict:
        return self.replay(self.checked)

    def control(self) -> dict:
        """The reference at ``high`` (three bfloat16 passes) in the
        program's place, judged as the program is."""
        ctl = self.replay(self.checked, precision="high", follow=False)
        saved, self.checked = self.checked, ctl
        try:
            return self.replay(ctl)
        finally:
            self.checked = saved
