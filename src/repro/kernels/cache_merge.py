"""Fused Eq. (4)/(5) server merge kernel — a whole round in one launch.

The round driver used to fold the per-client merges through a ``lax.scan``
over :func:`repro.core.server.global_update_body`: K sequential XLA
gather/scatter programs, each reading and re-writing the full ``(L, I, d)``
global table through HBM.  This kernel consumes the **whole round's upload
batch in one ``pallas_call``**:

    grid = (⌈I/I_TILE⌉, K)            # client axis minor
    for class block i:                 # major grid axis
        scratch ← entries[.., i, ..], Φ[i]        (k == 0)
        for client k in upload order:  # minor grid axis — revisits the block
            φ      = uploads.phi[k, i]
            denom  = max(Φ + φ, 1e-6)
            merged = l2_normalize(γ·Φ/denom · E + φ/denom · l2_normalize(Uₖ))
            E      = where(u_touched[k], merged, E)        (Eq. 4)
            Φ      = Φ + φ                                 (Eq. 5)
            (both gated on the round's include mask)
        entries[.., i, ..], Φ[i] ← scratch        (k == K-1)

The running ``(L, I_TILE, d)`` entries block and ``(1, I_TILE)`` frequency
block live in VMEM scratch across the K revisits, so the table crosses HBM
exactly twice per round (one read, one write) instead of 2·K times — round
boundaries stop being host-visible scan steps.

Every op inside the revisit loop is the *same expression* as
``global_update_body`` (including reusing :func:`l2_normalize` itself), and
Eq. 4/5 are elementwise in the class axis, so the kernel is **bit-for-bit**
against the scanned reference in interpret mode (tests/test_merge_kernel.py).
The R-estimate EMA is (L,)-shaped — O(K·L) work — and stays a tiny ``jnp``
scan in :func:`repro.core.server.merge_round`, which also owns the
fused-on-TPU / scan-ref-on-CPU dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semantic_cache import l2_normalize
from repro.kernels.common import I_TILE
from repro.kernels.common import default_interpret  # noqa: F401  (re-export)
from repro.kernels.common import resolve_interpret as _resolve_interpret


def _kernel_merge(entries0_ref, phi0_ref, u_ref, phik_ref, touched_ref,
                  inc_ref,                                    # inputs
                  ent_out, phi_out,                           # outputs
                  ent_s, phi_s,                               # scratch
                  *, gamma: float, num_clients: int):
    k = pl.program_id(1)

    # First client of a class block: seed the running state from the server.
    @pl.when(k == 0)
    def _():
        ent_s[...] = entries0_ref[...]
        phi_s[...] = phi0_ref[...]

    # One client's Eq.-4/5 update — identical ops to global_update_body.
    phi_l = phik_ref[0].astype(jnp.float32)                   # (1, I_t)
    phi_g = phi_s[...]
    denom = jnp.maximum(phi_g + phi_l, 1e-6)
    w_g = (gamma * phi_g / denom)[..., None]                  # (1, I_t, 1)
    w_l = (phi_l / denom)[..., None]
    ent = ent_s[...]                                          # (L, I_t, d)
    merged = l2_normalize(w_g * ent + w_l * l2_normalize(u_ref[0]))
    # Broadcast the (L, I_t) touched mask over d as f32: Mosaic cannot
    # reshape a boolean vector.
    touched = touched_ref[0].astype(jnp.float32)[..., None] > 0
    new_ent = jnp.where(touched, merged, ent)

    # Straggler/fault gating: an excluded client's upload is a no-op.
    inc = inc_ref[k] > 0                                      # SMEM scalar
    ent_s[...] = jnp.where(inc, new_ent, ent)
    phi_s[...] = jnp.where(inc, phi_g + phi_l, phi_g)

    # Last client: the block's final state leaves VMEM exactly once.
    @pl.when(k == num_clients - 1)
    def _():
        ent_out[...] = ent_s[...]
        phi_out[...] = phi_s[...]


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def cache_merge_round(entries: jax.Array, phi_global: jax.Array,
                      u: jax.Array, phi: jax.Array, u_touched: jax.Array,
                      include: jax.Array, *, gamma: float,
                      interpret: bool | None = None):
    """Merge a round's K uploads into the global cache in one ``pallas_call``.

    ``entries`` (L, I, d) f32 / ``phi_global`` (I,) f32 — server state;
    ``u`` (K, L, I, d) f32, ``phi`` (K, I) int, ``u_touched`` (K, L, I) bool
    — the stacked round uploads in client order; ``include`` (K,) bool —
    which uploads merge (straggler deadline / fault masking).

    Returns ``(entries', phi_global')``.  Class-axis padding is benign by
    construction: padded φ is 0 → merge weight 0, padded ``u_touched`` is
    False → the (garbage-normalised) merged value is never selected.
    """
    interpret = _resolve_interpret(interpret)
    L, I, d = entries.shape
    K = u.shape[0]
    Ip = -(-I // I_TILE) * I_TILE
    pad_i = Ip - I
    ep = jnp.pad(entries, ((0, 0), (0, pad_i), (0, 0)))
    # Φ and φ ride as rows, (1, I) and (K, 1, I): their blocks' last two
    # dims are then (1, I_TILE), a shape the TPU accepts for any K; the
    # include mask is read as SMEM scalars.
    pp = jnp.pad(phi_global.astype(jnp.float32), (0, pad_i))[None, :]
    up_ = jnp.pad(u, ((0, 0), (0, 0), (0, pad_i), (0, 0)))
    phip = jnp.pad(phi, ((0, 0), (0, pad_i)))[:, None, :]
    tp = jnp.pad(u_touched.astype(jnp.int32), ((0, 0), (0, 0), (0, pad_i)))
    incp = include.astype(jnp.int32)
    n_i = Ip // I_TILE

    out_shapes = (
        jax.ShapeDtypeStruct((L, Ip, d), jnp.float32),   # merged entries
        jax.ShapeDtypeStruct((1, Ip), jnp.float32),      # merged Φ
    )
    ent, phi_out = pl.pallas_call(
        functools.partial(_kernel_merge, gamma=gamma, num_clients=K),
        grid=(n_i, K),
        in_specs=[
            pl.BlockSpec((L, I_TILE, d), lambda i, k: (0, i, 0)),
            pl.BlockSpec((1, I_TILE), lambda i, k: (0, i)),
            pl.BlockSpec((1, L, I_TILE, d), lambda i, k: (k, 0, i, 0)),
            pl.BlockSpec((1, 1, I_TILE), lambda i, k: (k, 0, i)),
            pl.BlockSpec((1, L, I_TILE), lambda i, k: (k, 0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((L, I_TILE, d), lambda i, k: (0, i, 0)),
            pl.BlockSpec((1, I_TILE), lambda i, k: (0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((L, I_TILE, d), jnp.float32),     # running entries
            pltpu.VMEM((1, I_TILE), jnp.float32),        # running Φ
        ],
        out_shape=out_shapes,
        interpret=interpret,
    )(ep, pp, up_, phip, tp, incp)
    return ent[:, :I, :], phi_out[0, :I]
