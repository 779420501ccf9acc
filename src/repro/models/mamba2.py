"""Mamba-2 (SSD — state-space duality) blocks: chunked train/prefill scan and
O(1)-state recurrent decode.

The SSD parameterisation (arXiv:2405.21060): per head h with scalar decay
``a_t = exp(-softplus(A) · dt_t)``, input/output projections B_t, C_t shared
across the head's channels:

    h_t = a_t · h_{t-1} + dt_t · B_t ⊗ x_t          state (d_state × head_dim)
    y_t = C_tᵀ h_t + D ⊙ x_t

Training/prefill uses the chunked algorithm (intra-chunk quadratic attention-
like term + inter-chunk state recurrence over chunk summaries) — this is the
form the Pallas ``ssd_scan`` kernel implements; the pure-jnp version here is
its oracle and the CPU path.  Decode carries (B, heads, d_state, head_dim)
state — constant memory, which is why the SSM/hybrid archs run ``long_500k``.

Projections are kept SEPARATE (w_x/w_z/w_b/w_c/w_dt rather than one fused
in-proj) so each output dimension shards cleanly: d_inner and heads over the
"model" mesh axis, B/C (d_state-sized) replicated.  Conv states likewise stay
per-component so their shardings match.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, SSMConfig
from repro.models.layers import truncated_normal


def _scfg(cfg: ModelConfig) -> SSMConfig:
    return cfg.ssm or SSMConfig()


def mamba_init(key, cfg: ModelConfig):
    s = _scfg(cfg)
    d = cfg.d_model
    d_in = s.expand * d
    nheads = d_in // s.head_dim
    ks = jax.random.split(key, 8)
    sc = d ** -0.5
    return {
        "w_x": truncated_normal(ks[0], (d, d_in), sc),
        "w_z": truncated_normal(ks[1], (d, d_in), sc),
        "w_b": truncated_normal(ks[2], (d, s.d_state), sc),
        "w_c": truncated_normal(ks[3], (d, s.d_state), sc),
        "w_dt": truncated_normal(ks[4], (d, nheads), sc),
        "conv_x": truncated_normal(ks[5], (s.d_conv, d_in), 0.3),
        "conv_b": truncated_normal(ks[6], (s.d_conv, s.d_state), 0.3),
        "conv_c": truncated_normal(ks[7], (s.d_conv, s.d_state), 0.3),
        **({"conv_x_bias": jnp.zeros((d_in,)),
            "conv_b_bias": jnp.zeros((s.d_state,)),
            "conv_c_bias": jnp.zeros((s.d_state,))} if s.conv_bias else {}),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)),
        "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, nheads))),
        "d_skip": jnp.ones((nheads,)),
        "norm_scale": jnp.ones((d_in,)),
        "w_out": truncated_normal(jax.random.fold_in(key, 9), (d_in, d),
                                  d_in ** -0.5),
    }


def _conv_full(x, w, b=None):
    """Depthwise causal conv over (B, S, ch) with taps (K, ch) [+ bias] +
    SiLU."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1]] * w[i].astype(x.dtype) for i in range(K))
    if b is not None:
        out = out + b.astype(x.dtype)
    return jax.nn.silu(out)


def _conv_step(x_t, w, state, b=None):
    """Decode-step conv: state (B, K-1, ch), x_t (B, 1, ch)."""
    window = jnp.concatenate([state, x_t], axis=1)           # (B, K, ch)
    out = jnp.einsum("bkc,kc->bc", window, w.astype(x_t.dtype))[:, None]
    if b is not None:
        out = out + b.astype(x_t.dtype)
    return jax.nn.silu(out), window[:, 1:]


class SSMState(NamedTuple):
    h: jax.Array            # (B, nheads, d_state, head_dim) float32
    conv_x: jax.Array       # (B, d_conv-1, d_in)
    conv_b: jax.Array       # (B, d_conv-1, d_state)
    conv_c: jax.Array       # (B, d_conv-1, d_state)


def init_ssm_state(cfg: ModelConfig, batch: int, n_layers: int) -> SSMState:
    s = _scfg(cfg)
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    dt = jnp.dtype(cfg.dtype)
    return SSMState(
        h=jnp.zeros((n_layers, batch, nheads, s.d_state, s.head_dim), jnp.float32),
        conv_x=jnp.zeros((n_layers, batch, s.d_conv - 1, d_in), dt),
        conv_b=jnp.zeros((n_layers, batch, s.d_conv - 1, s.d_state), dt),
        conv_c=jnp.zeros((n_layers, batch, s.d_conv - 1, s.d_state), dt))


def ssd_chunked_ref(x, dt, a_decay, B, C, chunk: int):
    """Pure-jnp chunked SSD (oracle for the Pallas kernel).

    x (B, S, H, P), dt (B, S, H), a_decay (B, S, H) = exp(-softplus(A)·dt),
    B/C (B, S, N).  Returns (y (B, S, H, P), final state (B, H, N, P)).
    Requires S % chunk == 0 (callers pad; a padded tail with x=0, a=1 is
    state-neutral).
    """
    Bsz, S, H, P = x.shape
    assert S % chunk == 0, (S, chunk)
    N = B.shape[-1]
    nc = S // chunk
    xc = x.reshape(Bsz, nc, chunk, H, P)
    dtc = dt.reshape(Bsz, nc, chunk, H)
    ac = a_decay.reshape(Bsz, nc, chunk, H)
    Bc = B.reshape(Bsz, nc, chunk, N)
    Cc = C.reshape(Bsz, nc, chunk, N)

    la = jnp.cumsum(jnp.log(jnp.maximum(ac, 1e-20)), axis=2)   # (B,nc,c,H)
    seg = jnp.exp(la[:, :, :, None, :] - la[:, :, None, :, :]) # (B,nc,c,c,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = jnp.where(causal[None, None, :, :, None], seg, 0.0)

    # intra-chunk (quadratic within chunk)
    cb = jnp.einsum("bnci,bnki->bnck", Cc, Bc)
    y_intra = jnp.einsum("bnck,bnckh,bnkh,bnkhp->bnchp", cb, seg, dtc, xc)

    # chunk summaries -> inter-chunk recurrence over states (B,nc,H,N,P)
    decay_to_end = jnp.exp(la[:, :, -1:, :] - la)              # (B,nc,c,H)
    chunk_state = jnp.einsum("bnki,bnkh,bnkh,bnkhp->bnhip",
                             Bc, decay_to_end, dtc, xc)
    a_chunk = jnp.exp(la[:, :, -1, :])                         # (B,nc,H)

    def scan_fn(h, inp):
        st, ach = inp                                          # (B,H,N,P),(B,H)
        return h * ach[:, :, None, None] + st, h
    h0 = jnp.zeros((Bsz, H, N, P), x.dtype)
    h_final, h_prev = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(a_chunk, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                        # state entering chunk

    decay_from_start = jnp.exp(la)                             # (B,nc,c,H)
    y_inter = jnp.einsum("bnci,bnch,bnhip->bnchp", Cc, decay_from_start, h_prev)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, h_final


def _project(p, u, cfg: ModelConfig):
    z = u @ p["w_z"].astype(u.dtype)
    x_raw = u @ p["w_x"].astype(u.dtype)
    b_raw = u @ p["w_b"].astype(u.dtype)
    c_raw = u @ p["w_c"].astype(u.dtype)
    dt_raw = (u @ p["w_dt"].astype(u.dtype)).astype(jnp.float32)
    return z, x_raw, b_raw, c_raw, dt_raw


def mamba_fwd(p, u, cfg: ModelConfig, use_kernel: bool = False,
              return_state: bool = False):
    """Full-sequence SSD forward.  u (B, S, d_model) -> (B, S, d_model).

    ``use_kernel`` runs the chunked scan as the Pallas ``ssd_scan`` kernel,
    which has no backward pass; the default is the differentiable jnp
    oracle.
    ``return_state=True`` additionally returns the :class:`SSMState` after the
    last position (prefill -> decode handoff).
    """
    s = _scfg(cfg)
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    Bsz, S, _ = u.shape
    z, x_raw, b_raw, c_raw, dt_raw = _project(p, u, cfg)
    x = _conv_full(x_raw, p["conv_x"], p.get("conv_x_bias"))
    B = _conv_full(b_raw, p["conv_b"], p.get("conv_b_bias"))
    C = _conv_full(c_raw, p["conv_c"], p.get("conv_c_bias"))
    x = x.reshape(Bsz, S, nheads, s.head_dim)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(dt_raw.dtype))  # (B,S,H)
    a = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32)) * dt.astype(jnp.float32))

    chunk = min(s.chunk, S)
    pad = (-S) % chunk
    xp, dtp, ap, Bp, Cp = x, dt, a, B, C
    if pad:
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        ap = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        Bp = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    with jax.named_scope("ssd"):
        if use_kernel:
            from repro.kernels.ops import ssd_scan
            y = ssd_scan(xp, dtp, ap, Bp, Cp, chunk=chunk)[:, :S]
            h_final = None
            if return_state:
                _, h_final = ssd_chunked_ref(
                    xp.astype(jnp.float32), dtp.astype(jnp.float32), ap,
                    Bp.astype(jnp.float32), Cp.astype(jnp.float32), chunk)
        else:
            y, h_final = ssd_chunked_ref(
                xp.astype(jnp.float32), dtp.astype(jnp.float32), ap,
                Bp.astype(jnp.float32), Cp.astype(jnp.float32), chunk)
            y = y[:, :S]
    y = y.astype(u.dtype) + x * p["d_skip"].astype(u.dtype)[None, None, :, None]
    y = y.reshape(Bsz, S, d_in)
    # gated RMSNorm (Mamba-2's out norm)
    y = y * jax.nn.silu(z)
    var = (y.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-5)
         * p["norm_scale"]).astype(u.dtype)
    out = y @ p["w_out"].astype(u.dtype)
    if not return_state:
        return out
    K = s.d_conv
    state = SSMState(
        h=h_final,
        conv_x=_tail(x_raw, K), conv_b=_tail(b_raw, K), conv_c=_tail(c_raw, K))
    return out, state


def _tail(x_raw, K: int):
    if K <= 1:
        return jnp.zeros((x_raw.shape[0], 0, x_raw.shape[-1]), x_raw.dtype)
    return x_raw[:, -(K - 1):]


def mamba_decode(p, u, cfg: ModelConfig, state: SSMState):
    """Single-token recurrent step.  u (B, 1, d) -> (B, 1, d) + new state."""
    s = _scfg(cfg)
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    Bsz = u.shape[0]
    z, x_raw, b_raw, c_raw, dt_raw = _project(p, u, cfg)
    x, conv_x = _conv_step(x_raw, p["conv_x"], state.conv_x,
                           p.get("conv_x_bias"))
    B, conv_b = _conv_step(b_raw, p["conv_b"], state.conv_b,
                           p.get("conv_b_bias"))
    C, conv_c = _conv_step(c_raw, p["conv_c"], state.conv_c,
                           p.get("conv_c_bias"))
    x = x.reshape(Bsz, nheads, s.head_dim)
    B, C = B[:, 0], C[:, 0]                                    # (B, N)
    dt = jax.nn.softplus(dt_raw[:, 0] + p["dt_bias"].astype(dt_raw.dtype))
    a = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32))
                * dt.astype(jnp.float32))                      # (B,H)
    h = (state.h * a[:, :, None, None]
         + jnp.einsum("bn,bh,bhp->bhnp", B.astype(jnp.float32),
                      dt.astype(jnp.float32), x.astype(jnp.float32)))
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), h).astype(u.dtype)
    y = y + x * p["d_skip"].astype(u.dtype)[None, :, None]
    y = y.reshape(Bsz, 1, d_in)
    y = y * jax.nn.silu(z)
    var = (y.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-5)
         * p["norm_scale"]).astype(u.dtype)
    out = y @ p["w_out"].astype(u.dtype)
    return out, SSMState(h=h, conv_x=conv_x, conv_b=conv_b, conv_c=conv_c)
