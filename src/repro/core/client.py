"""Client-side CoCa: status vectors, absorption rules (Eq. 3), round runner.

A client holds
  * ``tau``  — (I,) inferences since a sample of class *i* last appeared (§V.B),
  * ``phi``  — (I,) per-round class occurrence counts (§IV.C),
  * ``u``    — (L, I, d) cache-update table accumulated with decay ``beta``
               (Eq. 3) and L2-normalised after every absorption,
  * ``u_touched`` — (L, I) which cells absorbed anything this round,
  * per-layer hit/lookup counters feeding the server's hit-ratio estimate R.

Within a round the allocated cache is *fixed* (the server only re-allocates at
round boundaries, §IV.A), so the F frames of a round are processed as one
batched, jit-compiled computation: the full tap tensor is produced once, the
Eq. (1)/(2) oracle derives per-frame exit layers, and the only sequential part
— the Eq. (3) normalise-after-update recurrence on ``u`` — runs as a
``lax.scan`` over frames.  This is bit-exact w.r.t. the paper's per-frame
semantics because nothing a frame writes is read again before the round ends.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.semantic_cache import (
    CacheConfig, CacheTable, LookupResult, l2_normalize, lookup_all_layers,
)


@dataclasses.dataclass(frozen=True)
class AbsorptionConfig:
    """Sample-selection thresholds for global-cache updates (§IV.C)."""

    gamma_hit: float = 0.15    # Γ — confident-hit reinforcement threshold
    delta_miss: float = 0.25   # Δ — confident-miss expansion threshold
    beta: float = 0.95         # Eq. (3) decay
    # Γ/Δ calibrated on the synthetic-tap landscape for ≥97 % absorption
    # accuracy at ~10-25 % absorption ratio — the paper's own Fig. 6 recipe
    # (it recommends Γ=0.1, Δ=0.25 for *its* ResNet landscape).


class ClientState(NamedTuple):
    tau: jax.Array            # (I,) int32
    phi: jax.Array            # (I,) int32
    u: jax.Array              # (L, I, d) float32
    u_touched: jax.Array      # (L, I) bool
    hit_counts: jax.Array     # (L,) int32 — hits observed at each layer
    lookup_counts: jax.Array  # (L,) int32 — lookups performed at each layer


def init_client(cfg: CacheConfig) -> ClientState:
    return ClientState(
        tau=jnp.zeros((cfg.num_classes,), jnp.int32),
        phi=jnp.zeros((cfg.num_classes,), jnp.int32),
        u=jnp.zeros((cfg.num_layers, cfg.num_classes, cfg.sem_dim), jnp.float32),
        u_touched=jnp.zeros((cfg.num_layers, cfg.num_classes), bool),
        hit_counts=jnp.zeros((cfg.num_layers,), jnp.int32),
        lookup_counts=jnp.zeros((cfg.num_layers,), jnp.int32),
    )


def reset_round(state: ClientState) -> ClientState:
    """Zero the per-round accumulators (phi, U, counters); tau persists."""
    return state._replace(
        phi=jnp.zeros_like(state.phi),
        u=jnp.zeros_like(state.u),
        u_touched=jnp.zeros_like(state.u_touched),
        hit_counts=jnp.zeros_like(state.hit_counts),
        lookup_counts=jnp.zeros_like(state.lookup_counts),
    )


class RoundOutput(NamedTuple):
    state: ClientState
    pred: jax.Array           # (F,) final predictions (cache or full model)
    hit: jax.Array            # (F,) bool
    exit_layer: jax.Array     # (F,) int32 (== L when no hit)
    lookup: LookupResult


def _absorb_scan(u0: jax.Array, touched0: jax.Array, sems: jax.Array,
                 classes: jax.Array, layer_sel: jax.Array, beta: float):
    """Sequential Eq. (3) absorption: U[i,j] <- normalize(V + beta * U[i,j]).

    ``sems``      — (F, L, d) tap vectors per frame,
    ``classes``   — (F,) absorbed class per frame (−1 = not absorbed),
    ``layer_sel`` — (F, L) bool, which layers this frame contributes to.

    A frame only ever touches the (L, d) column of its absorbed class, so
    each scan step gathers that one column, normalises it, and scatters it
    back — O(F·L·d) instead of the dense O(F·L·I·d)
    normalise-the-whole-table update.
    """
    I = u0.shape[1]

    def step(carry, inp):
        u, touched = carry
        sem_f, cls_f, lay_f = inp
        valid = cls_f >= 0
        idx = jnp.clip(cls_f, 0, I - 1)
        u_col = jax.lax.dynamic_index_in_dim(u, idx, axis=1,
                                             keepdims=False)          # (L, d)
        upd = l2_normalize(sem_f + beta * u_col)                      # (L, d)
        write = lay_f & valid                                         # (L,)
        new_col = jnp.where(write[:, None], upd, u_col)
        u = jax.lax.dynamic_update_index_in_dim(u, new_col, idx, axis=1)
        t_col = jax.lax.dynamic_index_in_dim(touched, idx, axis=1,
                                             keepdims=False)          # (L,)
        touched = jax.lax.dynamic_update_index_in_dim(
            touched, t_col | write, idx, axis=1)
        return (u, touched), None

    (u, touched), _ = jax.lax.scan(step, (u0, touched0), (sems, classes, layer_sel))
    return u, touched


@partial(jax.jit, static_argnames=("cfg", "absorb"))
def run_round(state: ClientState, table: CacheTable, sems: jax.Array,
              logits: jax.Array, cfg: CacheConfig,
              absorb: AbsorptionConfig,
              look: LookupResult | None = None) -> RoundOutput:
    """Process one round of F frames with a fixed allocated cache.

    ``sems``   — (F, L, d) pooled semantic taps (model forward already done —
                 the simulator owns the latency accounting via exit layers),
    ``logits`` — (F, C) full-model outputs (used on cache miss + absorption).
    ``look``   — the Eq. (1)/(2) lookup of ``sems`` in ``table`` when the
                 caller already ran it (the round engine looks up every
                 client in one batched launch); ``None`` runs it here.
    """
    F = sems.shape[0]
    L = cfg.num_layers
    if look is None:
        look = lookup_all_layers(table, sems, cfg)

    model_pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pred = jnp.where(look.hit, look.pred, model_pred)

    # --- absorption rule 1: confident hits reinforce (D at exit > Γ) -------
    exit_clamped = jnp.minimum(look.exit_layer, L - 1)
    d_at_exit = jnp.take_along_axis(look.scores, exit_clamped[:, None], axis=1)[:, 0]
    type1 = look.hit & (d_at_exit > absorb.gamma_hit)
    # "collected semantic vectors are limited to the point of the cache hit":
    # active layers with index <= exit layer.
    lrange = jnp.arange(L)
    lay1 = table.layer_mask[None, :] & (lrange[None, :] <= look.exit_layer[:, None])

    # --- absorption rule 2: confident misses expand (prob1 - prob2 > Δ) ----
    probs = jax.nn.softmax(logits, axis=-1)
    top2 = jax.lax.top_k(probs, 2)[0]
    type2 = (~look.hit) & ((top2[:, 0] - top2[:, 1]) > absorb.delta_miss)
    lay2 = jnp.ones((F, L), bool)  # full tap row supplements the global cache

    absorbed_cls = jnp.where(type1, pred, jnp.where(type2, model_pred, -1))
    layer_sel = jnp.where(type1[:, None], lay1, jnp.where(type2[:, None], lay2, False))
    u, touched = _absorb_scan(state.u, state.u_touched, sems, absorbed_cls,
                              layer_sel, absorb.beta)

    # --- status vectors -----------------------------------------------------
    # tau: after the round, tau_i = F-1-last_pos(i) if class i appeared,
    # else tau_i + F.  (Per-frame: reset-to-0 then +1 per subsequent frame.)
    onehots = jax.nn.one_hot(pred, cfg.num_classes, dtype=bool)       # (F, I)
    seen = onehots.any(axis=0)
    pos = jnp.arange(F)[:, None]
    last_pos = jnp.max(jnp.where(onehots, pos, -1), axis=0)           # (I,)
    tau = jnp.where(seen, F - 1 - last_pos, state.tau + F).astype(jnp.int32)
    phi = state.phi + onehots.sum(axis=0).astype(jnp.int32)

    # --- per-layer hit statistics (feed server's R estimate) ---------------
    first_hit = jax.nn.one_hot(look.exit_layer, L, dtype=jnp.int32)   # rows of no-hit frames one-hot L -> dropped
    hit_counts = state.hit_counts + jnp.where(look.hit[:, None], first_hit, 0).sum(axis=0)
    visited = table.layer_mask[None, :] & (lrange[None, :] <= exit_clamped[:, None])
    lookup_counts = state.lookup_counts + visited.sum(axis=0).astype(jnp.int32)

    new_state = ClientState(tau=tau, phi=phi, u=u, u_touched=touched,
                            hit_counts=hit_counts, lookup_counts=lookup_counts)
    # Drop the (F, L, I) accumulator from the carried result: nothing after
    # the round reads it, and keeping it live would force the unfused ref
    # path to materialise it in HBM (XLA DCEs it once unreferenced).
    return RoundOutput(state=new_state, pred=pred, hit=look.hit,
                       exit_layer=look.exit_layer,
                       lookup=look._replace(acc=None))


class ClientUpload(NamedTuple):
    """What a client sends at the end of a round (§IV.A step 4)."""

    tau: jax.Array
    phi: jax.Array
    u: jax.Array
    u_touched: jax.Array
    hit_counts: jax.Array
    lookup_counts: jax.Array


def make_upload(state: ClientState) -> ClientUpload:
    return ClientUpload(state.tau, state.phi, state.u, state.u_touched,
                        state.hit_counts, state.lookup_counts)
