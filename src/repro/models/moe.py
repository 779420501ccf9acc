"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch is **row-local** (per batch element): ranks/capacity are computed
with a cumulative sum over each row's (S·K) assignment list only.  This is the
GSPMD-friendly form — every dispatch tensor keeps the batch dim leading, so
the whole path shards over the "data" axes with zero cross-shard dependencies
(a global flat-token cumsum would force XLA to replicate the dispatch and
multiply FLOPs by the device count; we measured exactly that before switching
— see EXPERIMENTS.md §Perf).  The expert einsum carries the experts on the
"model" axis (EP); GSPMD materialises the token exchange as the all-to-all at
that sharding boundary.

Capacity semantics: C = S·K/E · capacity_factor per row; over-capacity tokens
fall through (residual passes unchanged) — per-row capacity is what real
frameworks use (per-device capacity).  Decode (S=1) is naturally lossless:
a token's top-k experts are distinct, so per-expert assignments ≤ 1 ≤ C.

The auxiliary load-balance loss (Switch-style: E · Σ fraction_e · prob_e) is
returned for the training loss.

A layer told which experts it holds (``MoEConfig.held``, expert
parallelism's local part) routes every token over all ``num_experts`` and
computes only its held experts' gated part, **dropless**: the (token,
expert) assignments are sorted by held expert and run through one
``jax.lax.ragged_dot`` per projection, so each held expert computes exactly
the tokens routed to it and nothing is padded to a capacity.  The absent
experts' part is not computed and nothing stands in for it.  A shared
expert (``d_shared``) is added for every token on either path.

The held rows come back to their tokens in one of two ways.  Prefill on a
TPU runs the Pallas ``held_combine`` kernel, which reads only the buffer
blocks that hold a token tile's held rows and multiplies and sums the gates
in float32.  Training and decode keep the jnp combine, which gathers a row
for every (token, k) assignment and zeroes the absent ones: it is
differentiable (Pallas gives the kernel no backward pass) and it is the
CPU path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


from repro.models.config import ModelConfig
from repro.models.layers import mlp_fwd, mlp_init, truncated_normal


def moe_init(key, cfg: ModelConfig):
    """Router over all experts; expert weights for the held ones only."""
    m = cfg.moe
    d, ff, e, n = cfg.d_model, m.d_expert, m.num_experts, m.num_held
    ks = jax.random.split(key, 5)
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {"router": truncated_normal(ks[0], (d, e), s_in),
         "wi_gate": truncated_normal(ks[1], (n, d, ff), s_in),
         "wi_up": truncated_normal(ks[2], (n, d, ff), s_in),
         "wo": truncated_normal(ks[3], (n, ff, d), s_out)}
    if m.d_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=m.d_shared)
    return p


class MoEOut(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array


def moe_fwd(p, x: jax.Array, cfg: ModelConfig,
            use_kernel: bool = False) -> MoEOut:
    """x (B, S, d) -> (B, S, d) + load-balance aux loss.

    ``use_kernel`` combines the held experts' rows with the Pallas
    ``held_combine`` kernel, which has no backward pass; the default is
    the differentiable jnp combine."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k

    with jax.named_scope("router"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                # (B, S, E)
        gate, expert_idx = jax.lax.top_k(probs, K)             # (B, S, K)
        # = the softmax over the top-k logits (granite's form)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if m.held is not None:
        with jax.named_scope("held_experts"):
            y = _held_fwd(p, x, gate, expert_idx, cfg, use_kernel)
    else:
        y = _capacity_fwd(p, x, gate, expert_idx, cfg)
    if m.d_shared:
        with jax.named_scope("shared_expert"):
            y = y + mlp_fwd(p["shared"], x, cfg)

    # ---- Switch-style load-balance loss -------------------------------------
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (B, S, K, E)
    frac = onehot.sum(axis=2).mean(axis=(0, 1))                # tokens/expert
    imp = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(frac * imp) * m.router_aux_weight
    return MoEOut(y=y, aux_loss=aux)


def _held_fwd(p, x, gate, expert_idx, cfg: ModelConfig, use_kernel: bool):
    """The held experts' gated part of the layer, dropless.

    Assignments to held experts are sorted by expert into a buffer of
    ``T·min(K, held)`` rows, the most that tokens' top-k can put on them,
    and each projection is one ``ragged_dot`` over the held groups; rows
    past the held total belong to no group and are never combined."""
    m = cfg.moe
    B, S, d = x.shape
    K, (lo, n) = m.top_k, m.held
    T = B * S
    local = expert_idx.reshape(-1) - lo                        # (T*K,)
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)                            # absent last
    order = jnp.argsort(key, stable=True)
    M = T * min(K, n)
    sizes = (key[:, None] == jnp.arange(n)).sum(0, dtype=jnp.int32)
    xs = x.reshape(T, d)[order[:M] // K]                       # (M, d)

    def grouped(a, w):      # one pass at the weights' width, as the einsums
        return jax.lax.ragged_dot(a, w.astype(x.dtype), sizes,
                                  precision=jax.lax.Precision.DEFAULT)

    h = jax.nn.silu(grouped(xs, p["wi_gate"])) * grouped(xs, p["wi_up"])
    out = grouped(h, p["wo"])                                  # (M, d)
    pos = jnp.argsort(order).reshape(T, K)        # each assignment's row
    gate = gate.reshape(T, K)
    if use_kernel:
        from repro.kernels.ops import held_combine
        y = held_combine(out, pos, gate, sizes)
    else:
        y = _gather_combine(out, pos, gate, held.reshape(T, K))
    return y.reshape(B, S, d)


def _gather_combine(out, pos, gate, held):
    """(T, K) assignments -> (T, d): each assignment's row gathered back
    (no scatter), weighted by its gate in ``out``'s dtype, absent ones
    zeroed, summed over k."""
    rows = jnp.take(out, jnp.minimum(pos, out.shape[0] - 1), axis=0)
    rows = jnp.where(held[..., None], rows * gate[..., None].astype(out.dtype),
                     0)
    return rows.sum(axis=1)


def _capacity_fwd(p, x, gate, expert_idx, cfg: ModelConfig):
    """Every expert, row-local capacity dispatch (module docstring)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k

    # ---- row-local capacity ranks ------------------------------------------
    C = int(max(1, round(S * K / E * m.capacity_factor)))
    flat = expert_idx.reshape(B, S * K)                        # (B, S*K)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)          # (B, S*K, E)
    rank = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(rank, flat[..., None], axis=2)[..., 0]
    keep = pos < C                                             # (B, S*K)
    slot = flat * C + jnp.minimum(pos, C - 1)                  # in [0, E*C)

    # ---- dispatch -----------------------------------------------------------
    # Scatter only the NARROW token indices into expert slots, then gather
    # the wide activations.  (A direct payload scatter makes XLA materialise
    # u32 indices at (B, S*K, d) — two 137 GB all-gathers per layer on
    # qwen3-moe before this rewrite; see EXPERIMENTS.md §Perf.)
    token_of = jnp.broadcast_to(jnp.repeat(jnp.arange(S), K), (B, S * K))
    safe_slot = jnp.where(keep, slot, E * C)                   # OOB rows drop
    barange = jnp.arange(B)[:, None]
    slot_token = jnp.full((B, E * C), S, jnp.int32)            # S = sentinel
    slot_token = slot_token.at[barange, safe_slot].set(
        token_of.astype(jnp.int32), mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
    expert_in = jnp.take_along_axis(
        x_pad, slot_token[..., None], axis=1).reshape(B, E, C, d)

    # ---- expert compute (E sharded over "model" => EP all-to-all) ----------
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in,
                                p["wi_gate"].astype(x.dtype)))
         * jnp.einsum("becd,edf->becf", expert_in, p["wi_up"].astype(x.dtype)))
    expert_out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(x.dtype))

    # ---- combine: gather back, weight by gates, reduce over k ---------------
    # token_of groups are contiguous (i -> i // K), so the scatter-add is a
    # static reshape + sum over the top-k axis — no scatter at all.
    flat_out = expert_out.reshape(B, E * C, d)
    gathered = jnp.take_along_axis(
        flat_out, jnp.minimum(slot, E * C - 1)[..., None], axis=1)
    # NOTE(§Perf, refuted): constraining this gather to token-major layout
    # added 40% collective bytes (GSPMD then reshards flat_out wholesale).
    w = (gate.reshape(B, S * K) * keep).astype(x.dtype)
    return (gathered.reshape(B, S, K, d)
            * w.reshape(B, S, K, 1)).sum(axis=2)
