"""Serving launcher: the closed-loop CoCa serving session, live.

``python -m repro.launch.serve --arch coca-ast --smoke`` bootstraps the
global cache from a shared set, then runs the **online** serving loop
(:mod:`repro.serving.loop`): open-loop Poisson arrivals feed the
EDF+shedding scheduler, admitted requests classify through the real fused
lookup on the live ACA-cut serving table, early exits retire and refill
batch slots, and each window's SLO attainment drives the ThetaController Θ
update plus between-window ACA re-allocation.  A no-cache twin session runs
the identical workload, so the reported SLO attainment, p50/p95 and
throughput gain come from the live sessions — no metric replay.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import AcaPolicy, CacheConfig, CocaCluster, SimulationConfig, \
    calibrate
from repro.data import (PoissonArrivals, RequestStream, StreamConfig,
                        Stationary, longtail_prior, make_client_context,
                        make_tap_model, perturb_tap_model, synthesize_taps)
from repro.launch.compile_cache import use_compile_cache
from repro.serving.batching import BatchingConfig
from repro.serving.loop import ServeLoopConfig, ServingSession, \
    throughput_gain


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="coca-ast")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--window-ticks", type=int, default=60)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="requests per block-tick (0 = 1.2x the no-cache "
                         "saturation rate max_slots/num_blocks)")
    ap.add_argument("--slo", type=float, default=0.0,
                    help="deadline in block-ticks (0 = 3x model depth)")
    ap.add_argument("--theta", type=float, default=0.10)
    ap.add_argument("--target", type=float, default=0.9,
                    help="SLO attainment target for the Θ controller")
    args = ap.parse_args()
    use_compile_cache()

    model_cfg = get_config(args.arch, smoke=args.smoke)
    n_taps = max(len(model_cfg.tap_layers()), 4)
    num_blocks = n_taps + 1
    I = model_cfg.num_classes or 50
    scfg = StreamConfig(num_classes=I, num_layers=n_taps,
                        sem_dim=model_cfg.sem_dim if not args.smoke else 32)
    cache = CacheConfig(num_classes=I, num_layers=n_taps,
                        sem_dim=scfg.sem_dim, theta=args.theta)
    tm = make_tap_model(jax.random.PRNGKey(0), scfg)
    tm_cal = perturb_tap_model(jax.random.PRNGKey(42), tm, 0.35)

    cm = calibrate(np.full(num_blocks, 5.0), np.full(n_taps, scfg.sem_dim),
                   head_cost=1.0)
    sim = SimulationConfig(cache=cache, round_frames=150,
                           mem_budget=float(8 * I * scfg.sem_dim))
    cluster = CocaCluster(sim, cm, policy=AcaPolicy(), num_clients=1)
    shared = np.tile(np.arange(I), 20)
    cluster.bootstrap(
        jax.random.PRNGKey(0),
        lambda lab: synthesize_taps(jax.random.PRNGKey(1), tm_cal,
                                    jnp.asarray(lab), scfg),
        shared)

    rate = args.rate or 1.2 * args.slots / num_blocks
    slo = args.slo or 3.0 * num_blocks
    workload = RequestStream(num_classes=I,
                             arrivals=PoissonArrivals(rate=rate),
                             process=Stationary(
                                 prior=longtail_prior(I, rho=50.0)),
                             seed=0)
    loop_cfg = ServeLoopConfig(
        batching=BatchingConfig(num_blocks=num_blocks, max_slots=args.slots),
        windows=args.windows, window_ticks=args.window_ticks, slo_ticks=slo,
        target=args.target)

    ctx = make_client_context(jax.random.PRNGKey(100), scfg)
    ctr = [0]

    def tap_fn(w, lab):
        ctr[0] += 1
        return synthesize_taps(jax.random.PRNGKey(1000 + ctr[0]), tm,
                               jnp.asarray(lab), scfg, context=ctx)

    print(f"[serve] {args.arch} I={I} taps={n_taps} slots={args.slots} "
          f"rate={rate:.2f}/tick slo={slo:.0f} ticks "
          f"({args.windows}x{args.window_ticks} tick windows)")
    res = ServingSession(cluster, loop_cfg, workload, tap_fn).run()
    for rep in res.windows:
        s = rep.stats
        print(f"[serve] window {rep.window}: theta={rep.theta:.4f} "
              f"attainment={s.attainment:.3f} p95={s.p95:.1f} "
              f"served={s.served} shed={s.shed} "
              f"hits={rep.hits}/{rep.admitted}")

    # the live no-cache twin: identical arrivals, lookup disabled
    base_cluster = CocaCluster(sim, cm, policy=AcaPolicy(), num_clients=1)
    base_cluster.bootstrap(
        jax.random.PRNGKey(0),
        lambda lab: synthesize_taps(jax.random.PRNGKey(1), tm_cal,
                                    jnp.asarray(lab), scfg),
        shared)
    ctr[0] = 0
    base = ServingSession(base_cluster, loop_cfg, workload, tap_fn,
                          use_cache=False).run()

    gain = throughput_gain(res, base)
    s, b = res.stats, base.stats
    print(f"[serve] coca:    attainment={s.attainment:.3f} p50={s.p50:.1f} "
          f"p95={s.p95:.1f} served={res.served} shed={res.shed} "
          f"hit_ratio={res.hit_ratio:.3f} accuracy={res.accuracy:.3f}")
    print(f"[serve] no-cache: attainment={b.attainment:.3f} p50={b.p50:.1f} "
          f"p95={b.p95:.1f} served={base.served} shed={base.shed} "
          f"accuracy={base.accuracy:.3f}")
    print(f"[serve] live throughput gain x{gain:.2f} "
          f"(theta {res.theta_trace[0]:.3f} -> {res.theta_trace[-1]:.4f} "
          f"across {len(res.theta_trace)} windows)")
    if gain < 1.0:
        raise SystemExit(f"throughput gain {gain:.2f} < 1 vs no-cache")


if __name__ == "__main__":
    main()
