"""Mamba-2 SSD chunked scan kernel.

Grid = (batch, head groups, n_chunks) with chunks innermost; each step
takes ``hb`` heads of one chunk (side by side on the lanes, in the layout
the projections give, so nothing is transposed or padded), and the
inter-chunk SSM states of those heads (hb × d_state × head_dim) live in
VMEM scratch and carry across chunk iterations.  Per step, with B/C shared
by every head (one group):

    cb        = C·Bᵀ                                 (chunk, chunk), once
  and per head, with la = cumsum(log a) over the chunk and xdt = x·dt:
    seg       = exp(la_i − la_j) · causal            (chunk, chunk)
    y_intra   = (cb ∘ seg) @ xdt                     MXU matmul
    y_inter   = (C ∘ exp(la)) @ h_state
    h_state   = exp(la_last)·h_state + (B ∘ exp(la_last − la))ᵀ @ xdt

This is the TPU-native layout of the SSD algorithm: intra-chunk quadratic
work maps to (chunk × N)·(N × chunk) and (chunk × chunk)·(chunk × P) MXU
matmuls; the recurrence touches VMEM only.  The matmuls take the inputs'
dtype (bfloat16 on the model's path) and accumulate in float32; the
carried state is float32.  The custom call is named ``ssd_scan`` so a
device trace finds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

HEAD_BLOCK = 8          # heads per grid step (sublane-aligned)
_NT = (((1,), (1,)), ((), ()))     # a @ bᵀ


def _kernel(xdt_ref, la_ref, b_ref, c_ref, y_ref, h_ref, *, heads: int,
            p: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = xdt_ref.dtype
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    Bm = b_ref[0]                                                  # (c, N)
    Cm = c_ref[0]
    cb = jax.lax.dot_general(Cm, Bm, _NT, precision=prec,
                             preferred_element_type=jnp.float32)   # (c, c)
    c = cb.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1)
    causal = rows >= cols
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    Bf, Cf = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    la_t = la_ref[0].T                                             # (c, hb)
    for j in range(heads):
        la_row = la_ref[0, j:j + 1, :]                             # (1, c)
        la_col = la_t[:, j:j + 1]                                  # (c, 1)
        seg = jnp.where(causal, jnp.exp(la_col - la_row), 0.0)
        x = xdt_ref[0, :, j * p:(j + 1) * p]                       # (c, P)
        y = jnp.dot((cb * seg).astype(dt), x, precision=prec,
                    preferred_element_type=jnp.float32)
        h = h_ref[j]                                               # (N, P)
        y = y + jnp.dot((Cf * jnp.exp(la_col)).astype(dt), h.astype(dt),
                        precision=prec, preferred_element_type=jnp.float32)
        y_ref[0, :, j * p:(j + 1) * p] = y.astype(y_ref.dtype)
        last = jnp.sum(jnp.where(at_last, la_row, 0.0))            # scalar
        bt = (Bf * jnp.exp(last - la_col)).T.astype(dt)            # (N, c)
        h_ref[j] = jnp.exp(last) * h + jnp.dot(
            bt, x, precision=prec, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a_decay: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """x (B, S, H, P), dt/a (B, S, H), B/C (B, S, N) -> y (B, S, H, P).

    Requires S % chunk == 0 (mamba_fwd pads with the state-neutral tail:
    x = 0, a = 1).  On a TPU the chunk is a multiple of 128 or all of S.
    """
    interpret = resolve_interpret(interpret)
    Bsz, S, H, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    # per-chunk cumulative log decay, heads leading; x·dt with heads
    # side by side on the lanes, as the projections lay them out
    la = jnp.log(jnp.maximum(a_decay.astype(jnp.float32), 1e-20))
    la = jnp.cumsum(la.reshape(Bsz, nc, chunk, H), axis=2).reshape(Bsz, S, H)
    la = jnp.moveaxis(la, 2, 1)                                    # (B, H, S)
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    xdt = xdt.astype(x.dtype).reshape(Bsz, S, H * P)
    y = pl.pallas_call(
        functools.partial(_kernel, heads=hb, p=P),
        grid=(Bsz, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hb * P), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, hb, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hb * P),
                               lambda b, h, c: (b, c, h)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, H * P), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xdt, la, B.astype(x.dtype), C.astype(x.dtype))
    return y.reshape(Bsz, S, H, P)
