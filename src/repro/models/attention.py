"""Grouped-query attention: training/prefill (full sequence) and decode (KV cache).

Layout conventions (sharding-friendly, see distributed/sharding.py):
  activations  (B, S, d_model)           — B over ("pod","data"), d replicated
  q/k/v        (B, S, H, head_dim)       — H over "model"
  KV cache     (B, S_max, H_kv, head_dim) — H_kv over "model" when divisible,
               else replicated with the sequence axis sharded (flash-decode
               partial-softmax combine happens in serving/decode_sharded).

GQA repeats each KV head over ``num_heads // kv_heads`` query heads via
reshape-free einsum grouping (no materialised repeat).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rope_freqs, truncated_normal

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hk = cfg.num_heads, cfg.kv_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {"wq": truncated_normal(ks[0], (d, h, hd), s),
         "wk": truncated_normal(ks[1], (d, hk, hd), s),
         "wv": truncated_normal(ks[2], (d, hk, hd), s),
         "wo": truncated_normal(ks[3], (h, hd, d), (h * hd) ** -0.5)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd))
        p["bk"] = jnp.zeros((hk, hd))
        p["bv"] = jnp.zeros((hk, hd))
    return p


def _head_pad(cfg: ModelConfig) -> int:
    """Query-head padding target (0 = no padding): when H doesn't divide the
    model axis, padding with zero heads lets attention shard 16-way at
    H_pad/H extra FLOPs instead of full replication (§Perf, starcoder2)."""
    from repro.distributed.sharding import attn_context
    t = attn_context()["pad_heads_to"]
    if t and cfg.num_heads % t != 0:
        return -(-cfg.num_heads // t) * t
    return 0


def _pad_groups(w, cfg: ModelConfig, hp: int, head_axis: int):
    """Pad query heads to ``hp`` *within each KV group* so the GQA mapping
    (head h -> kv head h // G) stays aligned after padding."""
    Hkv = cfg.kv_heads
    G = cfg.num_heads // Hkv
    Gp = hp // Hkv
    shape = w.shape
    grouped = w.reshape(shape[:head_axis] + (Hkv, G) + shape[head_axis + 1:])
    pad = [(0, 0)] * grouped.ndim
    pad[head_axis + 1] = (0, Gp - G)
    padded = jnp.pad(grouped, pad)
    return padded.reshape(shape[:head_axis] + (hp,) + shape[head_axis + 1:])


def _qkv(p, x, cfg: ModelConfig, positions):
    wq, bq = p["wq"], p.get("bq")
    hp = _head_pad(cfg)
    if hp:
        assert hp % cfg.kv_heads == 0, (hp, cfg.kv_heads)
        wq = _pad_groups(wq, cfg, hp, 1)
        if bq is not None:
            bq = _pad_groups(bq, cfg, hp, 0)
    q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + bq.astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.position == "rope":
        cos, sin = rope_freqs(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _out_proj(p, out, cfg: ModelConfig, x_dtype):
    """(B, S, H[+pad], hd) @ wo -> (B, S, d); padded heads contribute 0."""
    wo = p["wo"]
    H = out.shape[2]
    if H != cfg.num_heads:   # padded (within KV groups, matching _qkv)
        wo = _pad_groups(wo, cfg, H, 0)
    return jnp.einsum("bshk,hkd->bsd", out, wo.astype(x_dtype))


def _gqa_scores(q, k, cfg: ModelConfig):
    """(B,S,H,hd) x (B,T,Hkv,hd) -> (B, Hkv, G, S, T) grouped scores."""
    B, S, H, hd = q.shape
    g = H // cfg.kv_heads
    qg = q.reshape(B, S, cfg.kv_heads, g, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k)
    if cfg.attn_scale is not None:
        return s * jnp.asarray(cfg.attn_scale, q.dtype)
    return s / jnp.sqrt(hd).astype(q.dtype)


def _gqa_out(scores, v, cfg: ModelConfig):
    out = jnp.einsum("bkgst,btkd->bskgd", scores, v)
    B, S = out.shape[0], out.shape[1]
    return out.reshape(B, S, -1, cfg.resolved_head_dim)


def chunked_attention(q, k, v, positions, blocks, causal: bool,
                      scale: float | None = None):
    """Flash-semantics attention in pure XLA: scan over (q, kv) blocks with
    online softmax — the (S, T) score matrix never materialises in HBM.
    This is the compile-anywhere counterpart of kernels/flash_attention.py
    (same math; the Pallas version is the TPU-kernel form).

    q (B,S,H,hd); k/v (B,T,H,hd) — KV heads pre-expanded to match q.
    """
    qb, kb = blocks
    B, S, H, hd = q.shape
    T = k.shape[1]
    qb, kb = min(qb, S), min(kb, T)
    assert S % qb == 0 and T % kb == 0, (S, qb, T, kb)
    nq, nk = S // qb, T // kb
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    qs = jnp.moveaxis(q.reshape(B, nq, qb, H, hd), 1, 0)
    ks = jnp.moveaxis(k.reshape(B, nk, kb, H, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nk, kb, H, hd), 1, 0)

    def outer(_, qi):
        qblk, i = qi

        def inner(carry, kj):
            m, l, acc = carry
            kblk, vblk, j = kj
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            if causal:
                rows = i * qb + jnp.arange(qb)
                cols = j * kb + jnp.arange(kb)
                s = jnp.where(rows[None, None, :, None]
                              >= cols[None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p_ = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p_.sum(-1)
            acc = (acc * alpha[..., None]
                   + jnp.einsum("bhqk,bkhd->bhqd", p_,
                                vblk.astype(jnp.float32)))
            return (m_new, l, acc), None

        init = (jnp.full((B, H, qb), NEG_INF, jnp.float32),
                jnp.zeros((B, H, qb), jnp.float32),
                jnp.zeros((B, H, qb, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(inner, init, (ks, vs, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-20)[..., None]        # (B,H,qb,hd)
        return None, out

    _, outs = jax.lax.scan(outer, None, (qs, jnp.arange(nq)))  # (nq,B,H,qb,hd)
    out = jnp.moveaxis(outs, 0, 1)                             # (B,nq,H,qb,hd)
    out = jnp.moveaxis(out, 2, 3).reshape(B, S, H, hd)
    return out


def full_attention(p, x, cfg: ModelConfig, positions, *, causal: bool):
    """Training / prefill self-attention over the full sequence."""
    from repro.distributed.sharding import attn_context
    q, k, v = _qkv(p, x, cfg, positions)
    blocks = attn_context()["chunked"]
    if blocks is not None:
        rep = q.shape[2] // k.shape[2]
        kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
        vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
        out = chunked_attention(q, kx, vx, positions, blocks, causal,
                                cfg.attn_scale)
        return _out_proj(p, out.astype(x.dtype), cfg, x.dtype), (k, v)
    scores = _gqa_scores(q, k, cfg).astype(jnp.float32)   # (B, Hkv, G, S, T)
    if causal:
        mask = positions[:, :, None] >= positions[:, None, :]   # (B, S, T)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_out(att, v, cfg)
    y = _out_proj(p, out, cfg, x.dtype)
    return y, (k, v)


def precompute_cross_kv(p, enc_out: jax.Array, cfg: ModelConfig):
    """Project encoder output to (k, v) once per request (no RoPE on cross)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    return k, v


def cross_attention(p, x, cfg: ModelConfig, cross_kv):
    """Decoder cross-attention over precomputed encoder (k, v)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k, v = cross_kv
    scores = _gqa_scores(q, k.astype(x.dtype), cfg).astype(jnp.float32)
    att = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_out(att, v.astype(x.dtype), cfg)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


class KVCache(NamedTuple):
    k: jax.Array      # (B, S_max, H_kv, hd)
    v: jax.Array      # (B, S_max, H_kv, hd)


def decode_attention(p, x, cfg: ModelConfig, cache: KVCache, pos: jax.Array):
    """Single-token decode.  ``x`` (B, 1, d); ``pos`` (B,) current index.

    Writes the new KV at ``pos`` and attends over the valid prefix.  Under a
    sequence-sharded KV policy (kv_fallback="sequence") this delegates to the
    distributed flash-decode path.
    """
    from repro.distributed.sharding import kv_seq_context
    ctx = kv_seq_context()
    if ctx is not None:
        from repro.serving.decode_sharded import decode_attention_seq_sharded
        mesh, seq_axis, dp = ctx
        return decode_attention_seq_sharded(p, x, cfg, cache, pos,
                                            mesh, seq_axis, dp)
    q, k_new, v_new = _qkv(p, x, cfg, pos[:, None])
    B = x.shape[0]
    # NOTE(§Perf, refuted hypothesis): replacing this where-mask write with a
    # batched scatter (.at[arange(B), pos].set) made GSPMD reshard the
    # replicated-over-model cache around the scatter, adding 0.2 s/step of
    # collectives on glm4 decode_32k.  The where-write keeps the update local;
    # the real fix for KV-write bytes is the sequence-sharded decode path
    # (serving/decode_sharded.py), which updates a 1/16 local shard.
    idx = pos[:, None, None, None]
    oh = (jnp.arange(cache.k.shape[1])[None, :, None, None] == idx)
    k = jnp.where(oh, k_new.astype(cache.k.dtype), cache.k)
    v = jnp.where(oh, v_new.astype(cache.v.dtype), cache.v)

    scores = _gqa_scores(q, k.astype(q.dtype), cfg).astype(jnp.float32)
    valid = (jnp.arange(k.shape[1])[None, :] <= pos[:, None])   # (B, T)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_out(att, v.astype(x.dtype), cfg)
    y = _out_proj(p, out, cfg, x.dtype)
    return y, KVCache(k=k, v=v)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: int) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (n_layers, batch, max_len, cfg.kv_heads, hd)
    dt = jnp.dtype(cfg.dtype)
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))
