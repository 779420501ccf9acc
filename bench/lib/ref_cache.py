"""The plain reference of CoCa's cache: Eq. 1/2 lookups, the shared-set
bootstrap, Algorithm 1 (ACA), the client round with its Eq.-3 absorption,
and the Eq.-4/5 merge.

Written from the paper's equations in float64 NumPy, except the dot
products, which run in ``jax.numpy`` float32 at a stated matmul precision
(``highest`` for the reference; the control lowers it).  Nothing here
imports the program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9


def l2n(x, axis=-1, eps=1e-8):
    return x / (np.linalg.norm(x, axis=axis, keepdims=True) + eps)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What the deployment states about the cache."""

    num_classes: int
    num_layers: int
    sem_dim: int
    theta: float
    alpha: float = 0.5
    round_frames: int = 150
    mem_budget: float = 0.0
    block_cost: float = 5.0
    head_cost: float = 1.0
    gamma_hit: float = 0.15
    delta_miss: float = 0.25
    beta: float = 0.95
    gamma: float = 0.99
    r_ema: float = 0.5

    def upsilon(self) -> np.ndarray:
        """Seconds saved by a hit at layer j: the blocks after it + head."""
        L = self.num_layers
        return self.block_cost * (L - np.arange(L)) + self.head_cost

    def entry_sizes(self) -> np.ndarray:
        return np.full(self.num_layers, 4.0 * self.sem_dim)


# --------------------------------------------------------------------------
# Eq. 1/2
# --------------------------------------------------------------------------


def einsum(spec: str, a, b, precision="highest"):
    """A float32 einsum at ``precision``: ``"highest"`` as XLA gives it,
    ``"high"`` written out as three bfloat16 passes (hi*hi + hi*lo + lo*hi,
    accumulated in float32), so that the control computes the same on
    every backend."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if precision != "high":
        return jnp.einsum(spec, a, b, precision=precision)
    bf = jnp.bfloat16

    def split(x):
        hi = x.astype(bf)
        return hi, (x - hi.astype(jnp.float32)).astype(bf)

    (ah, al), (bh, bl) = split(a), split(b)

    def f(x, y):        # bfloat16 products are exact in float32
        return jnp.einsum(spec, x.astype(jnp.float32), y.astype(jnp.float32),
                          precision="highest")

    return f(ah, bh) + f(ah, bl) + f(al, bh)


def cosines(sems, entries, precision="highest") -> np.ndarray:
    """(..., L, d) taps against (L, I, d) unit rows -> (..., L, I)."""
    s = jnp.asarray(np.asarray(sems, np.float32))
    s = s / (jnp.linalg.norm(s, axis=-1, keepdims=True) + 1e-8)
    c = einsum("...ld,lid->...li", s, np.asarray(entries, np.float32),
               precision)
    return np.asarray(jax.device_get(c), np.float64)


class Lookup:
    """Eq. 1/2 over all layers for a batch: per-layer scores ``D`` (B, L),
    accumulators ``A`` (B, L, I), first-hit ``exit`` (B,) (L: none),
    ``hit`` and the top-1 class at the exit ``pred``."""

    def __init__(self, cos, class_mask, layer_mask, theta, alpha):
        B, L, I = cos.shape
        cm = np.asarray(class_mask, bool)
        a = np.where(cm, 0.0, NEG)[None].repeat(B, 0)
        self.D = np.zeros((B, L))
        self.A = np.zeros((B, L, I))
        top1 = np.zeros((B, L), np.int64)
        for j in range(L):
            new = np.where(cm, np.where(cm, cos[:, j], NEG) + alpha * a, NEG)
            if layer_mask[j]:
                a = new
            order = np.argsort(-new, axis=1, kind="stable")
            a1 = np.take_along_axis(new, order[:, :1], 1)[:, 0]
            a2 = np.take_along_axis(new, order[:, 1:2], 1)[:, 0]
            d = np.where(a2 > 1e-6, (a1 - a2) / np.maximum(a2, 1e-6), 0.0)
            d = np.where(a2 <= NEG / 2, 0.0, d)
            self.D[:, j] = d if layer_mask[j] else 0.0
            self.A[:, j] = a
            top1[:, j] = order[:, 0]
        hits = self.D > theta
        self.hit = hits.any(axis=1)
        self.exit = np.where(self.hit, hits.argmax(axis=1), L)
        self.pred = top1[np.arange(B), np.minimum(self.exit, L - 1)]
        self.layer_mask = np.asarray(layer_mask, bool)
        self.theta = theta


def decision_gap(ref: Lookup, hit, exit_layer, pred,
                 logits=None) -> np.ndarray:
    """How far the program's decisions (hit, exit layer, cache prediction)
    lie from the reference's own scores, per request: 0 where the reference
    makes the same decision, small where a score sat at the threshold or
    two classes tied, large where the decision is wrong.

    * an active layer before the program's exit where the reference clears
      Θ: by how much it clears it;
    * the program's exit layer: by how much the reference falls short of Θ
      there (1 if that layer is not active in the reference's table);
    * the program's cache prediction: the reference's best accumulated
      score less that of the predicted class, over the best;
    * with ``logits`` (the model outputs the reference shares with the
      program), a miss's served answer: the best class probability less
      that of the answer."""
    B, L = ref.D.shape
    gap = np.zeros(B)
    if logits is not None:
        lg = np.asarray(logits, np.float64)
        p = np.exp(lg - lg.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        miss = ~np.asarray(hit, bool)
        gap[miss] = (p.max(axis=1) - p[np.arange(B), np.asarray(pred)])[miss]
    for b in range(B):
        e = int(exit_layer[b]) if hit[b] else L
        before = [j for j in range(min(e, L)) if ref.layer_mask[j]]
        if before:
            gap[b] = max(gap[b], max(ref.D[b, j] - ref.theta
                                     for j in before))
        if hit[b]:
            if not ref.layer_mask[e]:
                gap[b] = max(gap[b], 1.0)
                continue
            gap[b] = max(gap[b], ref.theta - ref.D[b, e])
            a = ref.A[b, e]
            best, got = a.max(), a[int(pred[b])]
            rel = (1.0 if got <= NEG / 2
                   else (best - got) / max(abs(best), 1e-12))
            gap[b] = max(gap[b], rel)
    return gap


# --------------------------------------------------------------------------
# bootstrap and Algorithm 1
# --------------------------------------------------------------------------


def bootstrap(sems, labels, spec: CacheSpec, precision="highest"):
    """Server warm start from the shared set: unit per-class centroids at
    every layer, class counts Φ, and R, the cumulative share of the shared
    frames that first hit at or before each layer of the full table."""
    I, L = spec.num_classes, spec.num_layers
    onehot = jax.nn.one_hot(jnp.asarray(labels), I, dtype=jnp.float32)
    sums = einsum("nld,ni->lid", sems, onehot, precision)
    sums = np.asarray(jax.device_get(sums), np.float64)
    counts = np.bincount(np.asarray(labels), minlength=I).astype(np.float64)
    entries = l2n(l2n(sums / np.maximum(counts, 1.0)[None, :, None]))
    look = Lookup(cosines(sems, entries, precision), np.ones(I, bool),
                  np.ones(L, bool), spec.theta, spec.alpha)
    first = np.bincount(look.exit, minlength=L + 1)[:L]
    r0 = np.cumsum(first) / max(len(labels), 1)
    return entries, counts, r0


def aca(phi, tau, r, spec: CacheSpec) -> np.ndarray:
    """Algorithm 1: the (L, I) allocation.  Hot classes are the shortest
    prefix by s_i = Φ_i · 0.2^⌊τ_i/F⌋ that reaches 95 % of the total; layers
    are picked greedily by Υ·R under the byte budget, R[j] -= R[b] for j >=
    b after each pick."""
    L, I = spec.num_layers, spec.num_classes
    s = np.asarray(phi, np.float64) * 0.2 ** np.floor(
        np.asarray(tau, np.float64) / spec.round_frames)
    order = np.argsort(-s, kind="stable")
    if s.sum() <= 0:
        hot = order[:1]
    else:
        k = int(np.searchsorted(np.cumsum(s[order]), 0.95 * s.sum()) + 1)
        hot = order[:k]
    ups, sizes = spec.upsilon(), spec.entry_sizes()
    r = np.asarray(r, np.float64).copy()
    layers, mem = [], 0.0
    while mem <= spec.mem_budget:
        z = ups * r
        z[layers] = -np.inf
        b = int(np.argmax(z))
        if not np.isfinite(z[b]) or z[b] <= 0:
            break
        mem += sizes[b] * len(hot)
        if mem >= spec.mem_budget:
            break
        layers.append(b)
        r[b:] = np.maximum(r[b:] - r[b], 0.0)
    x = np.zeros((L, I), bool)
    for b in layers:
        x[b, hot] = True
    return x


def masks(x: np.ndarray):
    """(class mask (I,), layer mask (L,)) of an (L, I) allocation."""
    return x.any(axis=0), x.any(axis=1)


# --------------------------------------------------------------------------
# the client round and the merge
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Client:
    tau: np.ndarray
    u: np.ndarray = None
    touched: np.ndarray = None
    phi: np.ndarray = None
    hit_counts: np.ndarray = None
    lookup_counts: np.ndarray = None


def client_round(cl: Client, look: Lookup, sems, logits, hit, exit_layer,
                 pred, spec: CacheSpec, eps: float):
    """One client's round on its table, following the served decisions
    (``hit``, ``exit_layer``, ``pred``), with Eq.-3 absorption.  Returns the
    (L, I) cells that a frame whose absorption rule sits within ``eps`` of
    its threshold would touch: their merged entries are not compared."""
    F, L, d = sems.shape
    I = spec.num_classes
    sems = np.asarray(sems, np.float64)
    logits = np.asarray(logits, np.float64)
    cl.u = np.zeros((L, I, d))
    cl.touched = np.zeros((L, I), bool)
    fragile = np.zeros((L, I), bool)
    model_pred = logits.argmax(axis=1)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    top2 = -np.sort(-p, axis=1)[:, :2]
    margin2 = top2[:, 0] - top2[:, 1]
    for f in range(F):
        e = min(int(exit_layer[f]), L - 1)
        if hit[f]:
            d_exit = look.D[f, e]
            absorb = d_exit > spec.gamma_hit
            near = abs(d_exit - spec.gamma_hit) < eps
            sel = look.layer_mask & (np.arange(L) <= exit_layer[f])
            cls = int(pred[f])
        else:
            absorb = margin2[f] > spec.delta_miss
            near = abs(margin2[f] - spec.delta_miss) < eps
            sel = np.ones(L, bool)
            cls = int(model_pred[f])
        if near:
            fragile[sel, cls] = True
        if absorb:
            col = sems[f][sel] + spec.beta * cl.u[sel, cls]
            cl.u[sel, cls] = l2n(col)
            cl.touched[sel, cls] = True
    onehot = np.zeros((F, I), bool)
    onehot[np.arange(F), np.asarray(pred)] = True
    seen = onehot.any(axis=0)
    last = np.where(onehot, np.arange(F)[:, None], -1).max(axis=0)
    cl.tau = np.where(seen, F - 1 - last, cl.tau + F)
    cl.phi = onehot.sum(axis=0).astype(np.float64)
    cl.hit_counts = np.bincount(np.asarray(exit_layer)[np.asarray(hit)],
                                minlength=L)[:L].astype(np.float64)
    visited = look.layer_mask[None, :] & (
        np.arange(L)[None, :] <= np.minimum(exit_layer, L - 1)[:, None])
    cl.lookup_counts = visited.sum(axis=0)
    return fragile


def merge(entries, phi_g, r, cl: Client, spec: CacheSpec):
    """Eq. 4/5 for one client's upload, and the EMA of R."""
    denom = np.maximum(phi_g + cl.phi, 1e-6)
    wg = (spec.gamma * phi_g / denom)[None, :, None]
    wl = (cl.phi / denom)[None, :, None]
    merged = l2n(wg * entries + wl * l2n(cl.u))
    entries = np.where(cl.touched[..., None], merged, entries)
    obs = np.cumsum(cl.hit_counts) / max(cl.phi.sum(), 1.0)
    r = np.where(cl.lookup_counts > 0,
                 (1 - spec.r_ema) * r + spec.r_ema * obs, r)
    return entries, phi_g + cl.phi, r
