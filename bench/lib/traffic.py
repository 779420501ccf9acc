"""The benchmark's own traffic generators.

Copied from the program (``repro.data.streams``, ``repro.data.scenarios``
and ``chip_smoke.py``'s frame generator) so that no later change to the
program can move the yardstick.  Everything is drawn from a seed; the same
seed gives the same traffic.

* class priors: Zipf, Dirichlet non-IID per client;
* class streams: a Markov chain that stays with ``stay_prob`` and otherwise
  redraws from the prior (vectorised, same law as the program's loop);
* wall-clock arrivals: Poisson due times whose gaps are one fixed set for
  every seed, in a seed-drawn order;
* backbone frames: class-structured frontend patches and tokens;
* the synthetic tap model of the collaborative rounds.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# A seed is any whole number up to a little over 2**31; numpy's SeedSequence
# takes it whole, JAX keys take it folded to 32 bits.


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence((int(seed), *stream)))


def jax_key(seed: int, *stream: int) -> jax.Array:
    word = np.random.SeedSequence((int(seed), *stream)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


# --------------------------------------------------------------------------
# class priors and streams
# --------------------------------------------------------------------------


def zipf_prior(num_classes: int, alpha: float) -> np.ndarray:
    """p(i) ∝ (i+1)^-alpha; alpha 0 is uniform."""
    w = (1.0 + np.arange(num_classes)) ** -float(alpha)
    return w / w.sum()


def dirichlet_priors(g: np.random.Generator, clients: int, num_classes: int,
                     p: float) -> np.ndarray:
    """Per-client class priors at non-IID level p = 1/eps (p 0: IID)."""
    if p <= 0:
        return np.full((clients, num_classes), 1.0 / num_classes)
    pri = g.dirichlet(np.full(num_classes, 1.0 / p), size=clients)
    return pri / pri.sum(axis=1, keepdims=True)


def class_stream(g: np.random.Generator, prior: np.ndarray, length: int,
                 stay_prob: float) -> np.ndarray:
    """Markov class stream: frame 0 draws from the prior, each later frame
    keeps the class with probability ``stay_prob`` and redraws otherwise."""
    draws = g.choice(len(prior), size=length, p=prior).astype(np.int32)
    redraw = g.random(length) >= stay_prob
    redraw[0] = True
    last = np.maximum.accumulate(np.where(redraw, np.arange(length), 0))
    return draws[last]


def permute_runs(g: np.random.Generator, seq: np.ndarray) -> np.ndarray:
    """The same runs of equal classes in a ``g``-drawn order."""
    cut = np.flatnonzero(np.diff(seq)) + 1
    runs = np.split(seq, cut)
    return np.concatenate([runs[i] for i in g.permutation(len(runs))])


def poisson_due_times(seed: int, rate: float, seconds: float,
                      gap_seed: int = 0) -> np.ndarray:
    """Due times (s) of an open loop at ``rate`` per second over
    ``seconds``.  The gaps are one fixed set, drawn from ``gap_seed``
    whatever the run's seed, and the run's seed only orders them: every
    seed offers the same number of requests and the same burstiness."""
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum() * n / (n + 1)       # last one due before T
    return np.cumsum(rng(seed, 11).permutation(gaps))


# --------------------------------------------------------------------------
# backbone frames (chip_smoke.py's class-structured generator)
# --------------------------------------------------------------------------


def frame_inputs(key: jax.Array, labels: jax.Array, class_dirs: jax.Array,
                 tokens: int, frontend_len: int, vocab: int,
                 mix: dict | None = None):
    """One frame per label, drawn from ``key``: frontend patches carry the
    class direction and the tokens come from a class vocabulary block, so
    frames of one class look alike.  ``mix`` scales the three parts of a
    patch: ``patch`` (fresh per patch), ``cls`` (the class direction) and
    ``frame`` (one direction per frame, shared by its patches, which the
    pooled taps keep and so makes frames of a class differ); without it,
    0.3 / 2.0 / 0 as in the repository's chip smoke test.  Returns
    ``{"tokens", "frontend"}`` with frontend in float32."""
    mix = {"patch": 0.3, "cls": 2.0, "frame": 0.0, **(mix or {})}
    k_tok, k_fe, k_fr = jax.random.split(key, 3)
    n, d = labels.shape[0], class_dirs.shape[1]
    span = vocab - 8
    toks = ((labels * 37) % span)[:, None] + jax.random.randint(
        k_tok, (n, tokens), 0, 8)
    fe = (mix["patch"] * jax.random.normal(k_fe, (n, frontend_len, d))
          + mix["cls"] * class_dirs[labels][:, None, :]
          + mix["frame"] * jax.random.normal(k_fr, (n, 1, d)))
    return {"tokens": toks.astype(jnp.int32), "frontend": fe}


def row_frames(base: jax.Array, rows: jax.Array, labels: jax.Array,
               class_dirs: jax.Array, tokens: int, frontend_len: int,
               vocab: int, mix: dict | None = None):
    """Frames of global row indices ``rows``: row r is drawn from
    ``fold_in(base, r)``, so any row can be made again alone."""
    def one(r, lab):
        f = frame_inputs(jax.random.fold_in(base, r), lab[None], class_dirs,
                         tokens, frontend_len, vocab, mix)
        return f["tokens"][0], f["frontend"][0]
    toks, fe = jax.vmap(one)(rows, labels)
    return {"tokens": toks, "frontend": fe}


# --------------------------------------------------------------------------
# synthetic tap model (repro.data.streams)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    num_classes: int
    num_layers: int
    sem_dim: int
    noise_shallow: float = 3.0
    noise_deep: float = 0.8
    logit_scale: float = 10.0
    logit_noise: float = 1.1
    burst_coherence: float = 0.8
    ctx_frac: float = 0.45
    burst_frac: float = 0.35
    easy_frac: float = 0.35
    easy_scale: float = 0.35
    hard_scale: float = 1.25
    stages: int = 4
    stage_corr: float = 0.85


class TapModel(NamedTuple):
    centroids: jax.Array      # (L, I, d)
    noise: jax.Array          # (L,)
    head_centroids: jax.Array  # (I, d)


def _l2n(x, axis=-1, eps=1e-8):
    return x / (jnp.linalg.norm(x, axis=axis, keepdims=True) + eps)


def make_tap_model(key, cfg: StreamConfig) -> TapModel:
    k1, k2 = jax.random.split(key)
    cent = _l2n(jnp.abs(jax.random.normal(
        k1, (cfg.num_layers, cfg.num_classes, cfg.sem_dim))))
    if cfg.stages > 1 and cfg.num_layers >= cfg.stages:
        levels = jnp.geomspace(cfg.noise_shallow, cfg.noise_deep, cfg.stages)
        reps = -(-cfg.num_layers // cfg.stages)
        noise = jnp.repeat(levels, reps)[:cfg.num_layers]
    else:
        noise = jnp.linspace(cfg.noise_shallow, cfg.noise_deep,
                             cfg.num_layers)
    head = _l2n(jnp.abs(jax.random.normal(k2, (cfg.num_classes,
                                               cfg.sem_dim))))
    return TapModel(cent, noise, head)


def perturb_tap_model(key, model: TapModel, scale: float) -> TapModel:
    """Domain-shifted copy: the server's generic calibration set."""
    L, I, d = model.centroids.shape
    eps = jax.random.normal(key, (L, I, d)) * scale / jnp.sqrt(d)
    cent = _l2n(jax.nn.relu(model.centroids + eps) + 1e-6)
    k2 = jax.random.fold_in(key, 1)
    head = _l2n(jax.nn.relu(model.head_centroids + jax.random.normal(
        k2, (I, d)) * scale / jnp.sqrt(d)) + 1e-6)
    return TapModel(cent, model.noise, head)


def _stage_ids(cfg: StreamConfig):
    reps = -(-cfg.num_layers // cfg.stages)
    return jnp.repeat(jnp.arange(cfg.stages), reps)[:cfg.num_layers]


def stage_correlated_normal(key, cfg: StreamConfig, suffix: tuple):
    ks, kl = jax.random.split(key)
    stage = jax.random.normal(ks, (cfg.stages,) + suffix)[_stage_ids(cfg)]
    layer = jax.random.normal(kl, (cfg.num_layers,) + suffix)
    c = cfg.stage_corr
    return jnp.sqrt(c) * stage + jnp.sqrt(1 - c) * layer


def client_context(key, cfg: StreamConfig, group_key, shared_frac=0.7):
    suffix = (cfg.num_classes, cfg.sem_dim)
    own = stage_correlated_normal(key, cfg, suffix)
    shared = stage_correlated_normal(group_key, cfg, suffix)
    return jnp.sqrt(shared_frac) * shared + jnp.sqrt(1 - shared_frac) * own


def synthesize_taps(key, model: TapModel, labels, cfg: StreamConfig,
                    context=None):
    """(F,) labels -> ((F, L, d) taps, (F, I) logits)."""
    F = labels.shape[0]
    L, I, d = model.centroids.shape
    k1, k2, k3 = jax.random.split(key, 3)
    burst_id = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum((labels[1:] != labels[:-1]).astype(jnp.int32))])
    if context is None:
        f_ctx, f_burst = 0.0, cfg.burst_frac
        ctx = jnp.zeros((L, F, d))
    else:
        f_ctx, f_burst = cfg.ctx_frac, cfg.burst_frac
        ctx = context[:, labels]
    f_fresh = max(1.0 - f_ctx - f_burst, 0.0)
    eps_burst = stage_correlated_normal(k3, cfg, (F, d))[:, burst_id]
    eps_fresh = stage_correlated_normal(k1, cfg, (F, d))
    easy = jax.random.bernoulli(jax.random.fold_in(key, 4), cfg.easy_frac,
                                (F,))[burst_id]
    diff = jnp.where(easy, cfg.easy_scale, cfg.hard_scale)
    eps = ((jnp.sqrt(f_ctx) * ctx + jnp.sqrt(f_burst) * eps_burst
            + jnp.sqrt(f_fresh) * eps_fresh)
           * diff[None, :, None] * model.noise[:, None, None] / jnp.sqrt(d))
    taps = jax.nn.relu(model.centroids[:, labels] + eps) + 1e-6
    sems = jnp.swapaxes(_l2n(taps), 0, 1)
    coh = cfg.burst_coherence
    head_eps = (coh * jax.random.normal(k2, (F, d))[burst_id]
                + jnp.sqrt(1 - coh ** 2)
                * jax.random.normal(jax.random.fold_in(k2, 1), (F, d)))
    feat = _l2n(jax.nn.relu(model.head_centroids[labels]
                            + cfg.logit_noise / jnp.sqrt(d) * head_eps)
                + 1e-6)
    logits = cfg.logit_scale * jnp.dot(feat, model.head_centroids.T,
                                       precision="highest")
    return sems, logits
