#!/usr/bin/env python3
"""Chip smoke test: CoCa at the full width of ``coca-ast`` on one TPU.

One process drives the main path once, through the entry points a user
calls, with random weights and data made from a fixed seed, and checks each
phase against the repository's own references:

  a. the platform is a TPU — there is no CPU fallback;
  b. ``coca-ast`` at full width (12 blocks × d_model 768, frontend_len 512):
     ``init_params`` and a jitted ``prefill`` over class-structured frames;
  c. a ``CocaCluster`` bootstrapped from those taps;
  d. a ``ServingSession`` for a few windows, fed by the backbone's taps;
  e. collaborative rounds (``CocaCluster.step``) with the paper's 5 clients,
     50 classes and 150 frames per round;
  f. one lookup on a 16,384-class table, float32 and int8 — the
     class-tiled kernel.

Each lookup is checked against ``lookup_all_layers_ref`` (identical hits,
exit layers and predictions; Eq.-2 scores within ``SCORE_RTOL``/
``SCORE_ATOL``), the round's fused merge against the scanned merge (entries
within ``ENTRY_ATOL``, Φ exact), and the lowered tick and round programs
must hold a Mosaic kernel (``tpu_custom_call``), so no reference quietly
took a kernel's place.

    python chip_smoke.py             # one chip: phases a-f
    python chip_smoke.py --chips 4   # class-sharded rounds on 4 chips
                                     # against the same rounds on one

Earlier lines give each phase's compile seconds, wall seconds (compile
included, every result waited for) and parity maxima.  The last line is one
JSON object naming the device.  A failed check raises: the process exits
non-zero and prints no such line.  The persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
# Fused kernel vs reference, both float32 with full-precision dots: the
# same arithmetic summed in a different order.
SCORE_RTOL, SCORE_ATOL = 1e-4, 1e-5     # Eq.-2 scores
ENTRY_ATOL = 1e-5                       # merged unit-norm cache entries

# Lowering and backend compile of every program a phase builds (tracing is
# left out: nested jits would count twice).
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_kernel(text: str, what: str, at_least: int = 1) -> int:
    """The lowered program holds ``at_least`` Mosaic kernel calls."""
    n = text.count("tpu_custom_call")
    check(n >= at_least, f"{what}: {n} Mosaic kernel calls in the lowered "
                         f"program, expected at least {at_least}")
    return n


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment the one-chip run drives (the paper's, §VI)."""

    arch: str = "coca-ast"
    tokens: int = 8             # text tokens after the 512 frontend patches
    chunk: int = 50             # frames per backbone call
    shared_per_class: int = 4   # bootstrap (shared) frames per class
    clients: int = 5
    frames: int = 150           # per client per round
    rounds: int = 3
    slots: int = 8              # serving batch slots
    windows: int = 3
    window_ticks: int = 24
    theta: float = 0.05
    big_classes: int = 16_384   # phase f: over the single-pass VMEM budget
    big_batch: int = 128


class Phases:
    """Times each phase on the host clock and JAX's compile events."""

    def __init__(self):
        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self._compile_s += secs

    def run(self, name: str, fn):
        c0, t0 = self._compile_s, time.perf_counter()
        out, stats = fn()
        wall = time.perf_counter() - t0
        fields = " ".join(f"{k}={v}" for k, v in stats.items())
        print(f"[{name}] ok compile_s={self._compile_s - c0:.3f} "
              f"wall_s={wall:.3f} {fields}", flush=True)
        return out


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------


def lookup_parity(table, sems, cfg) -> dict:
    """The dispatched lookup (the fused kernel on a TPU) against the
    ``lax.scan`` reference with full-precision matmuls."""
    from repro.core.semantic_cache import lookup_all_layers
    fused = lookup_all_layers(table, sems, cfg)
    with jax.default_matmul_precision("highest"):
        ref = lookup_all_layers(table, sems, cfg, impl="ref")
    f, r = jax.device_get((fused, ref))
    for field in ("hit", "exit_layer", "pred"):
        a, b = np.asarray(getattr(f, field)), np.asarray(getattr(r, field))
        check(np.array_equal(a, b),
              f"lookup {field}: {int((a != b).sum())} of {a.size} differ "
              f"from lookup_all_layers_ref")
    fs, rs = np.asarray(f.scores), np.asarray(r.scores)
    check(np.isfinite(fs).all(), "lookup scores are not finite")
    check(np.allclose(fs, rs, rtol=SCORE_RTOL, atol=SCORE_ATOL),
          f"lookup scores off the reference by {np.abs(fs - rs).max():.3g}")
    return {"score_max_abs_diff": f"{np.abs(fs - rs).max():.3g}",
            "hit_ratio": f"{np.asarray(f.hit).mean():.3f}"}


def server_parity(a, b, what: str) -> dict:
    """Two ServerStates after the same merges: entries within
    ``ENTRY_ATOL``, Φ exact (integer counts in float32), R close."""
    a, b = jax.device_get((a, b))
    e = float(np.abs(np.asarray(a.entries) - np.asarray(b.entries)).max())
    check(np.isfinite(np.asarray(a.entries)).all(), f"{what}: entries")
    check(e <= ENTRY_ATOL, f"{what}: entries off by {e:.3g}")
    check(np.array_equal(np.asarray(a.phi_global), np.asarray(b.phi_global)),
          f"{what}: global class frequencies differ")
    r = float(np.abs(np.asarray(a.r_est) - np.asarray(b.r_est)).max())
    check(r <= ENTRY_ATOL, f"{what}: hit-ratio estimate off by {r:.3g}")
    return {"entry_max_abs_diff": f"{e:.3g}", "r_est_max_abs_diff": f"{r:.3g}"}


def stack_tables(tables):
    return jax.tree_util.tree_map(lambda *x: jnp.stack(x), *tables)


def fresh_client_states(cfg, num_clients: int):
    """Round-0 client states, as ``CocaCluster`` creates them."""
    from repro.core.client import init_client
    one = init_client(cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (num_clients,) + x.shape), one)


def round_kwargs(cluster) -> dict:
    sim = cluster.sim
    return dict(cfg=sim.cache, absorb=sim.absorb, cm=cluster.cost_model,
                global_updates=sim.global_updates,
                deadline=sim.straggler_deadline)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


class Backbone:
    """``prefill`` over class-structured frames: each frame's frontend
    patches carry a class direction and its tokens come from a
    class-specific vocabulary block, so frames of one class look alike
    (``examples/serve_stream.py``).  Frames are drawn on the device."""

    def __init__(self, cfg, params, sizes: Sizes, key):
        from repro.models import prefill
        self.cfg, self.params, self.sizes = cfg, params, sizes
        self._key, k_dir = jax.random.split(key)
        self._calls = 0
        class_dirs = jax.random.normal(k_dir, (cfg.num_classes, cfg.d_model))
        span = cfg.vocab_size - 8

        @jax.jit
        def taps(params, labels, key):
            k_tok, k_fe = jax.random.split(key)
            n = labels.shape[0]
            toks = ((labels * 37) % span)[:, None] + jax.random.randint(
                k_tok, (n, sizes.tokens), 0, 8)
            fe = (0.3 * jax.random.normal(
                k_fe, (n, cfg.frontend_len, cfg.d_model))
                + 2.0 * class_dirs[labels][:, None, :])
            _, _, sems, logits = prefill(
                params, {"tokens": toks.astype(jnp.int32), "frontend": fe},
                cfg)
            return sems.astype(jnp.float32), logits.astype(jnp.float32)

        self._taps = taps

    def __call__(self, labels) -> tuple[jax.Array, jax.Array]:
        """(N,) labels -> ((N, L, sem_dim) taps, (N, C) logits), in calls of
        ``chunk`` frames (N must divide into them)."""
        labels = np.asarray(labels, np.int32)
        c = self.sizes.chunk
        check(len(labels) % c == 0, f"{len(labels)} frames vs chunk {c}")
        outs = [self.call(labels[i:i + c]) for i in range(0, len(labels), c)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))

    def call(self, labels):
        self._calls += 1
        return self._taps(self.params, jnp.asarray(labels, jnp.int32),
                          jax.random.fold_in(self._key, self._calls))


def phase_backbone(model_cfg, sizes: Sizes):
    from repro.models import init_params
    key = jax.random.PRNGKey(SEED)
    params = init_params(jax.random.fold_in(key, 1), model_cfg)
    bb = Backbone(model_cfg, params, sizes, jax.random.fold_in(key, 2))
    labels = np.arange(sizes.chunk) % model_cfg.num_classes
    sems, logits = jax.block_until_ready(bb.call(labels))
    L = len(model_cfg.tap_layers())
    check(sems.shape == (sizes.chunk, L, model_cfg.sem_dim),
          f"taps shape {sems.shape}")
    check(logits.shape == (sizes.chunk, model_cfg.num_classes),
          f"logits shape {logits.shape}")
    check(bool(jnp.isfinite(sems).all() & jnp.isfinite(logits).all()),
          "backbone taps or logits are not finite")
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return bb, {"params": n_params, "layers": model_cfg.num_layers,
                "d_model": model_cfg.d_model,
                "seq": model_cfg.frontend_len + sizes.tokens,
                "taps": f"{L}x{model_cfg.sem_dim}"}


def phase_bootstrap(bb: Backbone, sizes: Sizes):
    from repro.core import (AcaPolicy, CacheConfig, CocaCluster,
                            SimulationConfig, calibrate)
    from repro.core.semantic_cache import CacheTable
    mc = bb.cfg
    I, L = mc.num_classes, len(mc.tap_layers())
    shared = np.repeat(np.arange(I), sizes.shared_per_class)
    pad = -len(shared) % sizes.chunk
    shared = np.concatenate([shared, shared[:pad]])
    sems, logits = bb(shared)
    cache = CacheConfig(num_classes=I, num_layers=L, sem_dim=mc.sem_dim,
                        theta=sizes.theta)
    cm = calibrate(np.full(L + 1, 5.0), np.full(L, mc.sem_dim),
                   head_cost=1.0)
    sim = SimulationConfig(cache=cache, round_frames=sizes.frames,
                           mem_budget=float(8 * I * mc.sem_dim))
    cluster = CocaCluster(sim, cm, policy=AcaPolicy(),
                          num_clients=sizes.clients)
    cluster.bootstrap(jax.random.PRNGKey(SEED), (sems, logits), shared)
    full = CacheTable(cluster.server.entries, jnp.ones(I, bool),
                      jnp.ones(L, bool))
    stats = lookup_parity(full, sems, cache)
    return cluster, {"shared_frames": len(shared), **stats}


def phase_serving(bb: Backbone, cluster, sizes: Sizes):
    from repro.data import PoissonArrivals, RequestStream, Stationary
    from repro.serving.batching import BatchingConfig
    from repro.serving.loop import (ServeLoopConfig, ServingSession,
                                    _batched_lookup)
    L = cluster.sim.cache.num_layers
    num_blocks = L + 1

    def tap_fn(_window, labels):
        n = len(labels)
        sems, logits = bb.call(np.resize(np.asarray(labels), sizes.slots))
        return sems[:n], logits[:n]

    workload = RequestStream(
        num_classes=cluster.sim.cache.num_classes,
        arrivals=PoissonArrivals(rate=1.2 * sizes.slots / num_blocks),
        process=Stationary(), seed=SEED)
    loop_cfg = ServeLoopConfig(
        batching=BatchingConfig(num_blocks=num_blocks,
                                max_slots=sizes.slots),
        windows=sizes.windows, window_ticks=sizes.window_ticks,
        slo_ticks=3.0 * num_blocks, target=0.9)
    res = ServingSession(cluster, loop_cfg, workload, tap_fn).run()
    check(res.served > 0 and res.served + res.shed == res.arrivals,
          f"session served {res.served} + shed {res.shed} of "
          f"{res.arrivals} arrivals")

    # The tick program on the live serving table: a kernel, and the
    # reference's answer.
    cfg = cluster.sim.cache
    table = cluster.serving_table(client=0)
    sems, _ = bb.call(np.arange(sizes.slots) % cfg.num_classes)
    n = require_kernel(_batched_lookup.lower(table, sems, cfg=cfg).as_text(),
                       "serving tick")
    stats = lookup_parity(table, sems, cfg)
    return res, {"windows": len(res.windows), "arrivals": res.arrivals,
                 "served": res.served, "shed": res.shed,
                 "session_hit_ratio": f"{res.hit_ratio:.3f}",
                 "tick_kernels": n, **stats}


def round_frames(bb: Backbone, sizes: Sizes, num_classes: int, rng):
    """One round's frames: Dirichlet non-IID (p=2) Markov class streams,
    the paper's setup (benchmarks/common.py)."""
    from repro.core import FrameBatch
    from repro.data import dirichlet_client_priors, sample_class_sequence
    priors = dirichlet_client_priors(rng, sizes.clients, num_classes, 2.0)
    out = []
    for k in range(sizes.clients):
        labels = sample_class_sequence(rng, priors[k], sizes.frames, 0.9)
        sems, logits = bb(labels)
        out.append(FrameBatch(sems, logits, labels))
    return out


def phase_rounds(bb: Backbone, cluster, sizes: Sizes):
    from repro.core.engine import round_step
    from repro.core.semantic_cache import lookup_all_layers
    I = cluster.sim.cache.num_classes
    rng = np.random.default_rng(np.random.SeedSequence((SEED, 5)))
    frames = round_frames(bb, sizes, I, rng)
    server0, tables0 = cluster.server, cluster.allocate_tables()
    m0 = cluster.step(frames, tables=tables0)
    server1 = cluster.server

    # Round 0 again, straight through round_step: its program holds the
    # lookup and merge kernels, and the scanned merge agrees with the fused.
    kw = round_kwargs(cluster)
    args = (fresh_client_states(kw["cfg"], sizes.clients),
            stack_tables(tables0),
            jnp.stack([jnp.asarray(fb.sems) for fb in frames]),
            jnp.stack([jnp.asarray(fb.logits) for fb in frames]), server0)
    scfg = cluster.sim.server
    n = require_kernel(round_step.lower(*args, scfg=scfg, **kw).as_text(),
                       "collaborative round", at_least=2)
    _, ref_server, ref_m = round_step(
        *args, scfg=dataclasses.replace(scfg, merge_impl="ref"), **kw)
    merge = server_parity(server1, ref_server, "fused vs scanned merge")
    for field in ("hit", "exit_layer"):
        check(np.array_equal(getattr(m0, field),
                             np.asarray(ref_m[field]).ravel()),
              f"round 0 {field} differs between the cluster and round_step")
    look = lookup_parity(args[1], args[2], kw["cfg"])
    fused = lookup_all_layers(args[1], args[2], kw["cfg"])
    check(np.array_equal(m0.exit_layer, np.asarray(fused.exit_layer).ravel()),
          "round 0 exit layers differ from the stacked lookup")

    for _ in range(1, sizes.rounds):
        m = cluster.step(round_frames(bb, sizes, I, rng))
        check(np.isfinite(m.latency).all(), "round latency not finite")
    res = cluster.result()
    return res, {"rounds": sizes.rounds, "clients": sizes.clients,
                 "frames_per_round": sizes.frames * sizes.clients,
                 "round_kernels": n, "hit_ratio": f"{res.hit_ratio:.3f}",
                 "accuracy": f"{res.accuracy:.3f}",
                 **{f"lookup_{k}": v for k, v in look.items()}, **merge}


def phase_large_table(sizes: Sizes, L: int, d: int):
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers,
                                           quantize_table)
    from repro.kernels.common import single_pass_fits
    I, B = sizes.big_classes, sizes.big_batch
    key = jax.random.PRNGKey(SEED + 16)
    k_e, k_m, k_l, k_n = jax.random.split(key, 4)
    entries = l2_normalize(jax.random.normal(k_e, (L, I, d)))
    table = CacheTable(entries, jax.random.bernoulli(k_m, 0.9, (I,)),
                       jnp.ones(L, bool))
    labels = jax.random.randint(k_l, (B,), 0, I)
    # Noisy at shallow taps, clean at deep ones: a spread of exit layers.
    noise = jnp.linspace(0.3, 0.05, L)[None, :, None]
    sems = (jnp.swapaxes(entries[:, labels], 0, 1)
            + noise * jax.random.normal(k_n, (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.3)
    stats = {}
    for dtype, t in (("float32", table), ("int8", quantize_table(table))):
        check(not single_pass_fits(L, I, d, entry_dtype=dtype),
              f"I={I} {dtype} fits the single-pass kernel")
        text = jax.jit(lambda t, s: lookup_all_layers(t, s, cfg)).lower(
            t, sems).as_text()
        require_kernel(text, f"I={I} {dtype} lookup")
        for k, v in lookup_parity(t, sems, cfg).items():
            stats[f"{dtype}_{k}"] = v
    return None, {"classes": I, "batch": B, **stats}


def one_chip(sizes: Sizes = Sizes()) -> None:
    from repro.configs import get_config
    model_cfg = get_config(sizes.arch)
    phases = Phases()
    bb = phases.run("b backbone", lambda: phase_backbone(model_cfg, sizes))
    cluster = phases.run("c bootstrap", lambda: phase_bootstrap(bb, sizes))
    phases.run("d serving", lambda: phase_serving(bb, cluster, sizes))
    phases.run("e rounds", lambda: phase_rounds(bb, cluster, sizes))
    phases.run("f large table", lambda: phase_large_table(
        sizes, len(model_cfg.tap_layers()), model_cfg.sem_dim))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedSizes:
    """Class-sharded rounds: coca-ast's cache widths (12 taps × 256) over
    the repository's synthetic tap model, with a class count that splits
    over four chips (50 does not, and an unsplit table is simply
    replicated) and at which the seeded streams hit the cache."""

    classes: int = 64
    layers: int = 12
    sem_dim: int = 256
    clients: int = 5
    frames: int = 150
    rounds: int = 3
    theta: float = 0.05


def sharded_rounds(mesh, sz: ShardedSizes):
    """The same seeded rounds on ``mesh`` (class-sharded server) and on one
    device; returns both clusters, each round's metrics and the round
    program compiled on the mesh."""
    from repro.core import (CacheConfig, CocaCluster, FrameBatch,
                            SimulationConfig, calibrate)
    from repro.core.engine import round_step
    from repro.data import (StreamConfig, dirichlet_client_priors,
                            make_tap_model, perturb_tap_model,
                            sample_class_sequence, synthesize_taps)
    scfg = StreamConfig(num_classes=sz.classes, num_layers=sz.layers,
                        sem_dim=sz.sem_dim)
    tm = make_tap_model(jax.random.PRNGKey(SEED), scfg)
    tm_cal = perturb_tap_model(jax.random.PRNGKey(SEED + 42), tm, 0.35)
    cm = calibrate(np.full(sz.layers + 1, 5.0),
                   np.full(sz.layers, sz.sem_dim), head_cost=1.0)
    shared = np.tile(np.arange(sz.classes), 2)
    cal = synthesize_taps(jax.random.PRNGKey(SEED + 1), tm_cal,
                          jnp.asarray(shared), scfg)
    sim = SimulationConfig(
        cache=CacheConfig(num_classes=sz.classes, num_layers=sz.layers,
                          sem_dim=sz.sem_dim, theta=sz.theta),
        round_frames=sz.frames, mem_budget=float(8 * 50 * sz.sem_dim))
    rng = np.random.default_rng(np.random.SeedSequence((SEED, 4)))
    priors = dirichlet_client_priors(rng, sz.clients, sz.classes, 2.0)
    labels = [[sample_class_sequence(rng, priors[k], sz.frames, 0.9)
               for k in range(sz.clients)] for _ in range(sz.rounds)]
    frames = [[FrameBatch(*synthesize_taps(
        jax.random.PRNGKey(1000 * r + k), tm, jnp.asarray(lab), scfg),
        labels=lab) for k, lab in enumerate(labs)]
        for r, labs in enumerate(labels)]

    out = {}
    for name, m in (("one", None), ("mesh", mesh)):
        cl = CocaCluster(sim, cm, num_clients=sz.clients, mesh=m)
        cl.bootstrap(jax.random.PRNGKey(SEED), cal, shared)
        text = None
        metrics = []
        for r in range(sz.rounds):
            if m is not None and r == 0:
                kw = round_kwargs(cl)
                tables = cl.allocate_tables()
                text = round_step.lower(
                    fresh_client_states(kw["cfg"], sz.clients),
                    stack_tables(tables),
                    jnp.stack([jnp.asarray(fb.sems) for fb in frames[r]]),
                    jnp.stack([jnp.asarray(fb.logits) for fb in frames[r]]),
                    cl.server, scfg=cl.sim.server, mesh=m,
                    **kw).compile().as_text()
            metrics.append(cl.step(frames[r]))
        out[name] = (cl, metrics, text)
    return out


def four_chips() -> None:
    from jax.sharding import AxisType
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,),
                         devices=devs[:4])
    sz = ShardedSizes()
    phases = Phases()

    def run():
        out = sharded_rounds(mesh, sz)
        (one, m1, _), (sh, m4, text) = out["one"], out["mesh"]
        spec = sh.server.entries.sharding.spec
        check(len(sh.server.entries.sharding.device_set) == 4
              and "model" in str(spec),
              f"server table is not class-sharded: {spec}")
        for r, (a, b) in enumerate(zip(m1, m4)):
            for f in ("pred", "hit", "exit_layer"):
                check(np.array_equal(getattr(a, f), getattr(b, f)),
                      f"round {r} {f}: 4-chip sharded != one device")
        stats = server_parity(sh.server, one.server, "sharded vs one device")
        n = require_kernel(text, "sharded round", at_least=2)
        table = f"f32[{sz.layers},{sz.classes},{sz.sem_dim}]"
        gathers = [ln for ln in text.splitlines()
                   if "all-gather" in ln and table in ln]
        check(not gathers, f"round program gathers the table: {gathers[:2]}")
        return None, {"devices": 4, "classes": sz.classes,
                      "clients": sz.clients, "rounds": sz.rounds,
                      "spec": str(spec).replace(" ", ""),
                      "round_kernels": n, "table_gathers": 0,
                      "hit_ratio": f"{sh.result().hit_ratio:.3f}",
                      **stats}

    phases.run("sharded rounds", run)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-f on one chip; 4: class-sharded "
                         "rounds on four chips against one")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache(ROOT)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"[a platform] ok platform={dev.platform} "
          f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
