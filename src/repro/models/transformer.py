"""Model stacks for the architecture zoo: decoder-only (dense/MoE/SSM/hybrid),
encoder-decoder (seamless-m4t) and modality-stub variants (phi-3-vision).
Audio/vision frontends are *stubs per the assignment*: ``input_specs``
supplies precomputed frame/patch embeddings.

Layer iteration is a ``lax.scan`` over *periods* (stacked parameter groups):
uniform models have period 1; jamba's period is 8 (one attention layer at
offset 4, seven Mamba layers, MoE on odd layers).  The period body is unrolled
inside the scan, so the HLO contains each distinct layer *kind* exactly once —
compile time stays flat in depth (MaxText-style).

Public API (pure functions over param pytrees):
    init_params(key, cfg)                     -> params
    forward_train(params, batch, cfg)         -> TrainOut(logits, aux, taps, cls)
    prefill(params, batch, cfg)               -> (logits, Caches, taps, cls)
    decode_step(params, tokens, caches, cfg)  -> (logits, Caches, taps, cls)

``taps`` are CoCa semantic vectors (B, n_taps, sem_dim) when ``tap_every>0``;
``cls`` are stream-classification logits when ``num_classes>0`` (the paper's
serving task).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import mamba2
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig
from repro.models.layers import (embed_fwd, embed_init, mlp_fwd, mlp_init,
                                 norm_fwd, norm_init, tap_init, tap_project,
                                 truncated_normal, unembed_fwd)


# ---------------------------------------------------------------------------
# period/group structure
# ---------------------------------------------------------------------------

def _period(cfg: ModelConfig) -> int:
    return cfg.attn_every if cfg.attn_every > 0 else 1


def _kinds(cfg: ModelConfig) -> list[str]:
    return [cfg.layer_kind(i) for i in range(_period(cfg))]


def _moes(cfg: ModelConfig) -> list[bool]:
    return [cfg.layer_is_moe(i) for i in range(_period(cfg))]


def _num_groups(cfg: ModelConfig) -> int:
    p = _period(cfg)
    assert cfg.num_layers % p == 0, (cfg.num_layers, p)
    return cfg.num_layers // p


def _init_group(key, cfg: ModelConfig):
    layers = []
    for i, (kind, is_moe) in enumerate(zip(_kinds(cfg), _moes(cfg))):
        k = jax.random.fold_in(key, i)
        p: dict[str, Any] = {"norm1": norm_init(cfg)}
        if kind == "attn":
            p["attn"] = attn.attn_init(jax.random.fold_in(k, 1), cfg)
        else:
            p["ssm"] = mamba2.mamba_init(jax.random.fold_in(k, 2), cfg)
        if is_moe:
            p["norm2"] = norm_init(cfg)
            p["moe"] = moe_mod.moe_init(jax.random.fold_in(k, 3), cfg)
        elif cfg.d_ff > 0:
            p["norm2"] = norm_init(cfg)
            p["mlp"] = mlp_init(jax.random.fold_in(k, 4), cfg)
        layers.append(p)
    return {"layers": layers}


def _regroup(tree, n_per: int, G: int):
    """(kind_layers, ...) leaves -> (G, n_per, ...) for scan xs."""
    return jax.tree.map(lambda a: a.reshape((G, n_per) + a.shape[1:]), tree)


def _flatten_groups(tree):
    """(G, n_per, ...) leaves -> (G*n_per, ...)."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), tree)


# ---------------------------------------------------------------------------
# one decoder layer
# ---------------------------------------------------------------------------

def _layer_fwd(lp, h, cfg: ModelConfig, kind: str, *, mode: str,
               positions=None, kv_cache=None, pos=None, ssm_state=None,
               cross=None):
    """Returns (h, aux, new_kv, new_ssm).  ``cross`` = (params, kv) or None."""
    aux = jnp.zeros((), jnp.float32)
    new_kv = new_ssm = None
    hn = norm_fwd(lp["norm1"], h, cfg)
    if kind == "attn":
        with jax.named_scope("attention"):
            if mode == "decode":
                a, new_kv = attn.decode_attention(lp["attn"], hn, cfg,
                                                  kv_cache, pos)
            else:
                a, kv = attn.full_attention(lp["attn"], hn, cfg, positions,
                                            causal=True)
                if mode == "prefill":
                    new_kv = attn.KVCache(*kv)
    else:
        with jax.named_scope("mamba"):
            if mode == "decode":
                a, new_ssm = mamba2.mamba_decode(lp["ssm"], hn, cfg,
                                                 ssm_state)
            else:
                # the Pallas scan serves prefill on a TPU; training keeps
                # the differentiable jnp scan
                a, fin = mamba2.mamba_fwd(
                    lp["ssm"], hn, cfg, return_state=True,
                    use_kernel=(mode == "prefill"
                                and jax.default_backend() == "tpu"))
                if mode == "prefill":
                    new_ssm = fin

    if cfg.parallel_block and "mlp" in lp:
        return h + a + mlp_fwd(lp["mlp"], hn, cfg), aux, new_kv, new_ssm

    h = h + _residual(a, cfg)
    if cross is not None:
        cp, ckv = cross
        cn = norm_fwd(cp["norm"], h, cfg)
        h = h + attn.cross_attention(cp["attn"], cn, cfg, ckv)
    if "moe" in lp:
        out = moe_mod.moe_fwd(lp["moe"], norm_fwd(lp["norm2"], h, cfg), cfg)
        h = h + _residual(out.y, cfg)
        aux = aux + out.aux_loss
    elif "mlp" in lp:
        h = h + mlp_fwd(lp["mlp"], norm_fwd(lp["norm2"], h, cfg), cfg)
    return h, aux, new_kv, new_ssm


def _residual(branch, cfg: ModelConfig):
    """A residual branch, scaled where the model scales it (granite)."""
    if cfg.residual_multiplier == 1.0:
        return branch
    return branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)


def _stack(ts):
    return jax.tree.map(lambda *a: jnp.stack(a), *ts) if ts else None


def _maybe_scan(body, carry, xs, cfg: ModelConfig):
    """lax.scan over groups, or an unrolled python loop (roofline costing)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    G = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for g in range(G):
        xg = jax.tree.map(lambda a: a[g], xs)
        carry, y = body(carry, xg)
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


# ---------------------------------------------------------------------------
# scan drivers
# ---------------------------------------------------------------------------

def _scan_full(params, h, cfg: ModelConfig, mode: str, positions,
               cross_kv=None):
    """Train / prefill pass.  Returns (h, aux, pooled (L,B,d), kv, ssm)."""
    kinds, moes = _kinds(cfg), _moes(cfg)
    G, P = _num_groups(cfg), _period(cfg)
    has_cross = cfg.is_encdec and cross_kv is not None
    xs: dict[str, Any] = {"g": params["decoder"]}
    if has_cross:
        xs["cross"] = _regroup(params["cross"], P, G)
        xs["cross_kv"] = _regroup(cross_kv, P, G)

    def body(carry, x):
        h, aux = carry
        pooled, kvs, ssms = [], [], []
        for li, kind in enumerate(kinds):
            lp = x["g"]["layers"][li]
            cross = ((jax.tree.map(lambda a: a[li], x["cross"]),
                      jax.tree.map(lambda a: a[li], x["cross_kv"]))
                     if has_cross else None)
            h, a, nkv, nssm = _layer_fwd(lp, h, cfg, kind, mode=mode,
                                         positions=positions, cross=cross)
            h = constrain(h, "residual")
            aux = aux + a
            pooled.append(h.mean(axis=1))
            if nkv is not None:
                kvs.append(nkv)
            if nssm is not None:
                ssms.append(nssm)
        return (h, aux), (jnp.stack(pooled), _stack(kvs), _stack(ssms))

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), (pooled, kv, ssm) = _maybe_scan(
        body, (h, jnp.zeros((), jnp.float32)), xs, cfg)
    pooled = pooled.reshape((-1,) + pooled.shape[2:])          # (L, B, d)
    kv = _flatten_groups(kv) if kv is not None else None
    ssm = _flatten_groups(ssm) if ssm is not None else None
    return h, aux, pooled, kv, ssm


def _scan_decode(params, h, cfg: ModelConfig, caches, cross_kv=None):
    """Single-token pass.  Returns (h, aux, pooled (L,B,d), kv, ssm)."""
    kinds, moes = _kinds(cfg), _moes(cfg)
    G, P = _num_groups(cfg), _period(cfg)
    n_attn_per = sum(k == "attn" for k in kinds)
    n_ssm_per = P - n_attn_per
    has_cross = cfg.is_encdec and cross_kv is not None
    pos = caches.pos

    xs: dict[str, Any] = {"g": params["decoder"]}
    if caches.kv is not None:
        xs["kv"] = _regroup(caches.kv, n_attn_per, G)
    if caches.ssm is not None:
        xs["ssm"] = _regroup(caches.ssm, n_ssm_per, G)
    if has_cross:
        xs["cross"] = _regroup(params["cross"], P, G)
        xs["cross_kv"] = _regroup(cross_kv, P, G)

    def body(carry, x):
        h, aux = carry
        pooled, kvs, ssms = [], [], []
        ai = si = 0
        for li, kind in enumerate(kinds):
            lp = x["g"]["layers"][li]
            cross = ((jax.tree.map(lambda a: a[li], x["cross"]),
                      jax.tree.map(lambda a: a[li], x["cross_kv"]))
                     if has_cross else None)
            kv_l = (jax.tree.map(lambda a: a[ai], x["kv"])
                    if kind == "attn" else None)
            ssm_l = (jax.tree.map(lambda a: a[si], x["ssm"])
                     if kind != "attn" else None)
            h, a, nkv, nssm = _layer_fwd(lp, h, cfg, kind, mode="decode",
                                         kv_cache=kv_l, pos=pos,
                                         ssm_state=ssm_l, cross=cross)
            aux = aux + a
            pooled.append(h[:, 0, :])
            if kind == "attn":
                kvs.append(nkv)
                ai += 1
            else:
                ssms.append(nssm)
                si += 1
        return (h, aux), (jnp.stack(pooled), _stack(kvs), _stack(ssms))

    (h, aux), (pooled, kv, ssm) = _maybe_scan(
        body, (h, jnp.zeros((), jnp.float32)), xs, cfg)
    pooled = pooled.reshape((-1,) + pooled.shape[2:])
    kv = _flatten_groups(kv) if kv is not None else None
    ssm = _flatten_groups(ssm) if ssm is not None else None
    return h, aux, pooled, kv, ssm


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    groups = jax.vmap(lambda k: _init_group(k, cfg))(
        jax.random.split(ks[0], _num_groups(cfg)))
    params: dict[str, Any] = {
        "embed": embed_init(ks[1], cfg),
        "decoder": groups,
        "final_norm": norm_init(cfg),
    }
    if cfg.is_encdec:
        params["encoder"] = jax.vmap(lambda k: _init_enc_layer(k, cfg))(
            jax.random.split(ks[2], cfg.enc_layers))
        params["enc_final_norm"] = norm_init(cfg)
        params["cross"] = jax.vmap(
            lambda k: {"norm": norm_init(cfg),
                       "attn": attn.attn_init(k, cfg)})(
            jax.random.split(ks[3], cfg.num_layers))
    t = tap_init(ks[4], cfg)
    if t is not None:
        params["taps"] = t
    if cfg.num_classes > 0:
        params["cls_head"] = truncated_normal(
            ks[5], (cfg.d_model, cfg.num_classes), cfg.d_model ** -0.5)
    return params


def _init_enc_layer(key, cfg: ModelConfig):
    return {"norm1": norm_init(cfg),
            "attn": attn.attn_init(jax.random.fold_in(key, 1), cfg),
            "norm2": norm_init(cfg),
            "mlp": mlp_init(jax.random.fold_in(key, 2), cfg)}


# ---------------------------------------------------------------------------
# encoder (bidirectional; the audio stub feeds it precomputed embeddings)
# ---------------------------------------------------------------------------

def encode(params, enc_embeds: jax.Array, cfg: ModelConfig) -> jax.Array:
    positions = jnp.broadcast_to(jnp.arange(enc_embeds.shape[1]),
                                 enc_embeds.shape[:2])

    def body(h, lp):
        hn = norm_fwd(lp["norm1"], h, cfg)
        a, _ = attn.full_attention(lp["attn"], hn, cfg, positions, causal=False)
        h = h + a
        h = h + mlp_fwd(lp["mlp"], norm_fwd(lp["norm2"], h, cfg), cfg)
        return h, None

    h, _ = _maybe_scan(lambda c, lp: body(c, lp), enc_embeds,
                       params["encoder"], cfg)
    return norm_fwd(params["enc_final_norm"], h, cfg)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

class Caches(NamedTuple):
    kv: Any                 # attn.KVCache stacked (n_attn, B, S, Hkv, hd) | None
    ssm: Any                # mamba2.SSMState stacked (n_ssm, ...) | None
    cross_kv: Any           # stacked per-layer (k, v) | None
    pos: jax.Array          # (B,) next write position


class TrainOut(NamedTuple):
    logits: jax.Array       # (B, S, V)
    aux_loss: jax.Array
    taps: jax.Array | None  # (B, n_taps, sem_dim)
    cls_logits: jax.Array | None


def _embed_inputs(params, batch, cfg: ModelConfig):
    h = embed_fwd(params["embed"], batch["tokens"], cfg)
    if cfg.frontend != "none" and not cfg.is_encdec:
        fe = batch["frontend"].astype(h.dtype)       # (B, Fl, d) patch embeds
        h = jnp.concatenate([fe, h], axis=1)
    h = constrain(_scale_embeds(h, cfg), "residual")
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return h, positions


def _scale_embeds(h, cfg: ModelConfig):
    """The input embeddings, scaled where the model scales them (granite)."""
    if cfg.embedding_multiplier == 1.0:
        return h
    return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)


def _taps_out(params, cfg: ModelConfig, pooled):
    tl = cfg.tap_layers()
    if cfg.tap_every <= 0 or "taps" not in params or not tl:
        return None
    sel = pooled[jnp.asarray(tl, dtype=jnp.int32)]   # (n_taps, B, d)
    return tap_project(params["taps"], jnp.swapaxes(sel, 0, 1))


def _cls_out(params, cfg: ModelConfig, h_final):
    if cfg.num_classes <= 0 or "cls_head" not in params:
        return None
    pooled = h_final.mean(axis=1).astype(jnp.float32)
    return pooled @ params["cls_head"]


def forward_train(params, batch, cfg: ModelConfig) -> TrainOut:
    h, positions = _embed_inputs(params, batch, cfg)
    cross_kv = None
    if cfg.is_encdec:
        enc_out = encode(params, batch["enc_embeds"].astype(h.dtype), cfg)
        cross_kv = jax.vmap(
            lambda cp: attn.precompute_cross_kv(cp["attn"], enc_out, cfg)
        )(params["cross"])
    h, aux, pooled, _, _ = _scan_full(params, h, cfg, "train", positions,
                                      cross_kv)
    h = norm_fwd(params["final_norm"], h, cfg)
    logits = unembed_fwd(params["embed"], h, cfg)
    return TrainOut(logits=logits, aux_loss=aux,
                    taps=_taps_out(params, cfg, pooled),
                    cls_logits=_cls_out(params, cfg, h))


def prefill(params, batch, cfg: ModelConfig, max_len: int | None = None):
    """Full-sequence prefill.  Returns (last-pos logits, Caches, taps, cls)."""
    h, positions = _embed_inputs(params, batch, cfg)
    B, S = h.shape[0], h.shape[1]
    cross_kv = None
    if cfg.is_encdec:
        enc_out = encode(params, batch["enc_embeds"].astype(h.dtype), cfg)
        cross_kv = jax.vmap(
            lambda cp: attn.precompute_cross_kv(cp["attn"], enc_out, cfg)
        )(params["cross"])
    h, aux, pooled, kv, ssm = _scan_full(params, h, cfg, "prefill", positions,
                                         cross_kv)
    if kv is not None and max_len is not None and max_len > S:
        pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0))
        kv = attn.KVCache(k=jnp.pad(kv.k, pad), v=jnp.pad(kv.v, pad))
    h = norm_fwd(params["final_norm"], h, cfg)
    logits = unembed_fwd(params["embed"], h[:, -1:, :], cfg)
    caches = Caches(kv=kv, ssm=ssm, cross_kv=cross_kv,
                    pos=jnp.full((B,), S, jnp.int32))
    return logits, caches, _taps_out(params, cfg, pooled), _cls_out(params, cfg, h)


def decode_step(params, tokens: jax.Array, caches: Caches, cfg: ModelConfig):
    """One decode step.  tokens (B, 1) -> (logits (B,1,V), Caches, taps, cls)."""
    h = _scale_embeds(embed_fwd(params["embed"], tokens, cfg), cfg)
    h, aux, pooled, kv, ssm = _scan_decode(params, h, cfg, caches,
                                           caches.cross_kv)
    h = norm_fwd(params["final_norm"], h, cfg)
    logits = unembed_fwd(params["embed"], h, cfg)
    new = Caches(kv=kv if kv is not None else caches.kv,
                 ssm=ssm if ssm is not None else caches.ssm,
                 cross_kv=caches.cross_kv, pos=caches.pos + 1)
    taps = _taps_out(params, cfg, pooled)
    return logits, new, taps, _cls_out(params, cfg, h)
