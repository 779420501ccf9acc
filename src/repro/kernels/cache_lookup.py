"""Fused semantic-cache lookup kernels (the paper's hot spot, §III.1).

Two kernels live here:

``cache_lookup_layer`` — one tap-layer lookup, fused end-to-end in VMEM
(kept for incremental/streaming callers and as the original reference
kernel).

``cache_lookup_all_layers`` — the full Eq. (1)/(2) pipeline for **all L
cache layers in a single ``pallas_call``**.  This is what the round
simulator dispatches to (:func:`repro.core.semantic_cache.lookup_all_layers`).

    for j in 0..L-1:                      # unrolled inside the kernel
        sem_n = sem_j / ||sem_j||                     (VPU)
        for t in class tiles:                         # rolled fori_loop
            C_t   = sem_n @ entries[j, t]ᵀ            (MXU matmul)
            A_t   = C_t + α·A_prev_t  (masked)        (Eq. 1)
            merge running top-2 / argmax              (VREG-resident)
        D_j   = (A₁ − A₂)/A₂                          (Eq. 2)
        hit_j = active_j ∧ D_j > Θ_j  →  first-hit exit layer

Design / tiling (recorded per the PR-1 plan):

* **Grid = (tables, batch tiles)** ``(K, ⌈B/B_TILE⌉)`` — K stacked
  client tables, 1 for a single table.  Layers and class tiles are
  iterated *inside* the kernel body so the Eq.-1 accumulator ``A``
  (``(I_pad/I_TILE, B_TILE, I_TILE)`` f32 scratch), the normalised tap
  vector, and the running top-2/argmax state all stay **VMEM-resident for
  the whole L-layer sweep** — the ``(B, L, I)`` accumulator tensor that the
  unfused ``lax.scan`` round-trips through HBM on every round is never
  materialised.  Only ``(B, L)`` scores and ``(B, L+1)`` per-layer argmax
  classes plus the first-hit exit layer leave the kernel.
* **VMEM budget**: entries ``(L, I_pad, d)`` + accumulator
  ``(B_TILE, I_pad)`` + taps ``(B_TILE, L, d)``.  At paper scale
  (L=24, I≤1024, d=64, B_TILE=128) that is ≈6.5 MB < the ~16 MB/core
  budget.  Very large ``L·I·d`` tables overflow this — that regime is
  served by ``cache_lookup_all_layers_tiled`` below, which adds a second
  (minor) grid dimension over class blocks so only one ``(L, I_BLOCK, d)``
  entries slab is VMEM-resident at a time.  The budget model that picks
  between the two lives in :mod:`repro.kernels.common`; dispatch happens
  in :func:`repro.core.semantic_cache.lookup_all_layers`.  See
  ``docs/architecture.md`` for the full tiling story.
* Class tiles are ``I_TILE = 128`` wide (MXU-lane aligned); ``B`` and
  ``I`` are zero/NEG-padded to tile multiples, padded classes are masked
  to ``NEG`` so they never enter the top-2, and padded batch rows are
  sliced off on return.
* ``interpret`` defaults to auto-detection: interpreted on CPU, compiled
  by Mosaic on a TPU backend.  ``tests/test_tpu_compile.py`` compiles every
  kernel here for a described TPU v5e at serving widths; ``chip_smoke.py``
  runs them on the chip against the reference.

The paper measures the *unfused* all-layer lookup bill at 56 % of a
no-cache forward; the win here is (a) one kernel launch instead of L
scan iterations, (b) no HBM traffic for C/A between Eq.-1/Eq.-2 stages,
and (c) MXU-shaped ``(B_tile × d) · (d × I_tile)`` matmuls per class
tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import B_TILE, I_TILE
from repro.kernels.common import default_interpret  # noqa: F401  (re-export)
from repro.kernels.common import pick_class_block
from repro.kernels.common import resolve_interpret as _resolve_interpret

NEG = -1e9

# f32 cosine scores contract in full fp32 on the MXU, not in one bf16 pass:
# hit decisions compare score gaps against Θ, and the reference path
# (lookup_all_layers_ref) is exact f32.  Interpret mode ignores it.
_DOT_PRECISION = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# single-layer kernel (streaming callers; original PR-0 kernel)
# ---------------------------------------------------------------------------

def _kernel(sem_ref, entries_ref, mask_ref, aprev_ref,       # inputs
            anew_ref, score_ref, pred_ref,                   # outputs
            semn_ref, m1_ref, m2_ref, a1_ref,                # scratch
            *, alpha: float, n_i_tiles: int):
    it = pl.program_id(1)

    # --- first class tile: normalise the pooled vectors once ---------------
    @pl.when(it == 0)
    def _():
        s = sem_ref[...].astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(s * s, axis=1, keepdims=True)) + 1e-8
        semn_ref[...] = s / norm
        m1_ref[...] = jnp.full_like(m1_ref, NEG)
        m2_ref[...] = jnp.full_like(m2_ref, NEG)
        a1_ref[...] = jnp.zeros_like(a1_ref)

    # --- cosine scores for this class tile (MXU) ---------------------------
    e = entries_ref[...].astype(jnp.float32)                 # (I_t, d)
    c = jnp.dot(semn_ref[...], e.T,
                preferred_element_type=jnp.float32)          # (B_t, I_t)
    mask = mask_ref[...] > 0                                 # (I_t,)
    a = c + alpha * aprev_ref[...].astype(jnp.float32)       # Eq. (1)
    a = jnp.where(mask[None, :], a, NEG)
    anew_ref[...] = a

    # --- running top-2 merge ------------------------------------------------
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) + it * I_TILE
    b1 = jnp.max(a, axis=1)
    ba1 = jnp.argmax(a, axis=1) + it * I_TILE
    masked = jnp.where(cols == ba1[:, None], NEG, a)
    b2 = jnp.max(masked, axis=1)

    m1, m2, a1 = m1_ref[...], m2_ref[...], a1_ref[...]
    new_m1 = jnp.maximum(m1, b1)
    new_a1 = jnp.where(b1 > m1, ba1, a1)
    new_m2 = jnp.maximum(jnp.maximum(m2, b2), jnp.minimum(m1, b1))
    m1_ref[...] = new_m1
    m2_ref[...] = new_m2
    a1_ref[...] = new_a1

    # --- last tile: Eq. (2) discriminative score ----------------------------
    @pl.when(it == n_i_tiles - 1)
    def _():
        m1v, m2v, a1v = m1_ref[...], m2_ref[...], a1_ref[...]
        d = jnp.where(m2v > 1e-6, (m1v - m2v) / jnp.maximum(m2v, 1e-6), 0.0)
        d = jnp.where(m2v <= NEG / 2, 0.0, d)
        score_ref[...] = d
        pred_ref[...] = a1v.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "interpret"))
def cache_lookup_layer(sem: jax.Array, entries: jax.Array, class_mask: jax.Array,
                       a_prev: jax.Array, *, alpha: float = 0.5,
                       interpret: bool | None = None):
    """One tap-layer lookup for a batch.

    sem (B, d) raw pooled vectors; entries (I, d) unit rows; class_mask (I,)
    bool; a_prev (B, I) running Eq.-1 accumulator.
    Returns (a_new (B, I), d_score (B,), pred (B,)).
    """
    interpret = _resolve_interpret(interpret)
    B, d = sem.shape
    I = entries.shape[0]
    Bp = -(-B // B_TILE) * B_TILE
    Ip = -(-I // I_TILE) * I_TILE
    semp = jnp.pad(sem, ((0, Bp - B), (0, 0)))
    ep = jnp.pad(entries, ((0, Ip - I), (0, 0)))
    mp = jnp.pad(class_mask.astype(jnp.int32), (0, Ip - I))
    ap = jnp.pad(a_prev, ((0, Bp - B), (0, Ip - I)), constant_values=NEG)
    n_i = Ip // I_TILE

    out_shapes = (
        jax.ShapeDtypeStruct((Bp, Ip), jnp.float32),   # a_new
        jax.ShapeDtypeStruct((Bp,), jnp.float32),      # d_score
        jax.ShapeDtypeStruct((Bp,), jnp.int32),        # pred
    )
    grid = (Bp // B_TILE, n_i)
    a_new, d_score, pred = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, n_i_tiles=n_i),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B_TILE, d), lambda b, i: (b, 0)),
            pl.BlockSpec((I_TILE, d), lambda b, i: (i, 0)),
            pl.BlockSpec((I_TILE,), lambda b, i: (i,)),
            pl.BlockSpec((B_TILE, I_TILE), lambda b, i: (b, i)),
        ],
        out_specs=(
            pl.BlockSpec((B_TILE, I_TILE), lambda b, i: (b, i)),
            pl.BlockSpec((B_TILE,), lambda b, i: (b,)),
            pl.BlockSpec((B_TILE,), lambda b, i: (b,)),
        ),
        scratch_shapes=[
            pltpu.VMEM((B_TILE, d), jnp.float32),   # normalised sem vectors
            pltpu.VMEM((B_TILE,), jnp.float32),     # running top-1
            pltpu.VMEM((B_TILE,), jnp.float32),     # running top-2
            pltpu.VMEM((B_TILE,), jnp.int32),       # running argmax
        ],
        out_shape=out_shapes,
        interpret=interpret,
    )(semp, ep, mp, ap)
    return a_new[:B, :I], d_score[:B], pred[:B]


# ---------------------------------------------------------------------------
# fused all-layer kernel (the simulator hot path)
# ---------------------------------------------------------------------------

def _top2_merge(at, lo, m1, m2, a1):
    """Fold one class tile's Eq.-1 scores ``at`` (B_t, I_t), whose first
    column is global class ``lo``, into the running top-2/argmax state."""
    cols = jax.lax.broadcasted_iota(jnp.int32, at.shape, 1) + lo
    b1 = jnp.max(at, axis=1)
    ba1 = jnp.argmax(at, axis=1).astype(jnp.int32) + lo
    b2 = jnp.max(jnp.where(cols == ba1[:, None], NEG, at), axis=1)
    return (jnp.maximum(m1, b1),
            jnp.maximum(jnp.maximum(m2, b2), jnp.minimum(m1, b1)),
            jnp.where(b1 > m1, ba1, a1))


def _eq2_score(m1, m2, active):
    """Eq. (2) discriminative score, with the <2-active-classes guard."""
    d = jnp.where(m2 > 1e-6, (m1 - m2) / jnp.maximum(m2, 1e-6), 0.0)
    d = jnp.where(m2 <= NEG / 2, 0.0, d)
    return jnp.where(active, d, 0.0)


def _dequantize_scores(c, scale_row):
    """int8 rows: the per-row scale factors out of the dot product, so it
    scales the score columns — the same order of operations as
    :func:`repro.core.semantic_cache.cosine_scores`.  A scale row is
    lane-major, which the columns of ``c`` are too; scaling the int8 rows
    before the dot would need the scales as a sublane-major column, a
    relayout that costs several MiB of VMEM."""
    return c * scale_row.astype(jnp.float32)


def _normalized_tap(sem_ref, j):
    s = sem_ref[j].astype(jnp.float32)                        # (B_t, d)
    return s / (jnp.sqrt(jnp.sum(s * s, axis=1, keepdims=True)) + 1e-8)


def _kernel_all(sem_ref, entries_ref, cmask_ref, lmask_ref, theta_ref,
                *args,
                alpha: float, num_layers: int, n_i_tiles: int,
                quantized: bool):
    if quantized:
        (scale_ref, score_ref, pred_ref, a_ref) = args
    else:
        (score_ref, pred_ref, a_ref) = args
        scale_ref = None
    bt = score_ref.shape[0]
    k = pl.program_id(0)                                      # table

    # Eq.-1 accumulator A, one (B_t, I_TILE) slab per class tile: 0 for
    # active classes, NEG for inactive/padded — VMEM-resident across the
    # full layer sweep.
    def init_tile(it, carry):
        a_ref[it] = jnp.where(cmask_ref[it] > 0, 0.0, NEG) * jnp.ones((bt, 1))
        return carry

    jax.lax.fori_loop(0, n_i_tiles, init_tile, 0)
    exit_layer = jnp.full((bt,), num_layers, jnp.int32)

    for j in range(num_layers):
        semn = _normalized_tap(sem_ref, j)
        active = lmask_ref[k, j] > 0                          # SMEM scalar

        # Running top-2/argmax across class tiles (a rolled loop: the
        # kernel's size, and its compile time, do not grow with I).
        def tile_step(it, carry, j=j, semn=semn, active=active):
            lo = pl.multiple_of(it * I_TILE, I_TILE)
            e = entries_ref[j, pl.ds(lo, I_TILE), :].astype(jnp.float32)
            c = jnp.dot(semn, e.T, precision=_DOT_PRECISION,
                        preferred_element_type=jnp.float32)   # (B_t, I_t)
            if quantized:
                c = _dequantize_scores(c, scale_ref[it, pl.ds(j, 1), :])
            apv = a_ref[it]
            at = jnp.where(cmask_ref[it] > 0, c + alpha * apv, NEG)  # Eq. (1)
            # Inactive layer: carry the accumulator state unchanged.
            a_ref[it] = jnp.where(active, at, apv)
            return _top2_merge(at, lo, *carry)

        m1, m2, a1 = jax.lax.fori_loop(
            0, n_i_tiles, tile_step,
            (jnp.full((bt,), NEG, jnp.float32),
             jnp.full((bt,), NEG, jnp.float32),
             jnp.zeros((bt,), jnp.int32)))

        d = _eq2_score(m1, m2, active)
        score_ref[:, j] = d
        pred_ref[:, j] = a1
        hit_j = active & (d > theta_ref[j])
        exit_layer = jnp.where((exit_layer == num_layers) & hit_j,
                               j, exit_layer)

    pred_ref[:, num_layers] = exit_layer


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _resident(block_shape, index_map):
    """A block that changes only with the table index: one VMEM buffer,
    not the pipeline's two (the whole table is the VMEM budget)."""
    return pl.BlockSpec(block_shape, index_map, pipeline_mode=pl.Buffered(1))


def _with_table_axis(sems, entries, class_mask, layer_mask, entry_scale):
    """Operands with a leading table axis K (stacked per-client tables, as
    ``round_step`` passes them); unbatched operands get K=1.  Returns the
    operands and whether the outputs drop the axis again."""
    if entries.ndim == 4:
        return (sems, entries, class_mask, layer_mask, entry_scale), False
    return (sems[None], entries[None], class_mask[None], layer_mask[None],
            None if entry_scale is None else entry_scale[None]), True


def _flat_batch_outputs(K, Bp, nb, L):
    """Output shapes and specs over the table-major (K·Bp) batch rows:
    Eq.-2 scores (K·Bp, L), and the per-layer argmax classes with the
    first-hit exit layer as column L (K·Bp, L+1) — 2-D blocks, which the
    TPU lays out as the kernel does for any K."""
    shapes = (jax.ShapeDtypeStruct((K * Bp, L), jnp.float32),
              jax.ShapeDtypeStruct((K * Bp, L + 1), jnp.int32))
    specs = (pl.BlockSpec((B_TILE, L), lambda k, b: (k * nb + b, 0)),
             pl.BlockSpec((B_TILE, L + 1), lambda k, b: (k * nb + b, 0)))
    return shapes, specs


def _unflatten(outs, K, Bp, B, L, squeeze):
    scores, preds = outs
    scores = scores.reshape(K, Bp, L)[:, :B]
    preds = preds.reshape(K, Bp, L + 1)[:, :B]
    preds, exit_layer = preds[..., :L], preds[..., L]
    if squeeze:
        return scores[0], preds[0], exit_layer[0]
    return scores, preds, exit_layer


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def cache_lookup_all_layers(sems: jax.Array, entries: jax.Array,
                            class_mask: jax.Array, layer_mask: jax.Array,
                            theta: jax.Array, *, alpha: float = 0.5,
                            entry_scale: jax.Array | None = None,
                            interpret: bool | None = None):
    """Full Eq. (1)/(2) lookup across all L layers in one ``pallas_call``.

    sems (B, L, d) raw pooled tap vectors; entries (L, I, d) unit rows
    (float32) or int8 quantized rows with ``entry_scale`` (L, I) bf16
    per-row scales; class_mask (I,) bool; layer_mask (L,) bool; theta (L,)
    per-layer Θ.  Returns (scores (B, L) f32, preds (B, L) i32, exit_layer
    (B,) i32 with L meaning "no hit").  The (B, L, I) accumulator never
    touches HBM.

    A leading table axis K on ``sems``/``entries``/``class_mask``/
    ``layer_mask``/``entry_scale`` looks up K stacked tables (one per
    client) in the same launch — grid ``(K, ⌈B/B_TILE⌉)`` — and adds K to
    the outputs.  (The TPU lowering cannot ``vmap`` this kernel: its SMEM
    operands would need a block per table.)

    Kernel-side layouts: taps arrive layer-major ``(L, K·B, d)`` and the
    class mask / scale planes class-tile-major, so every dynamic index in
    the kernel is on a leading axis or an aligned sublane offset; the
    per-layer scalars (layer mask, Θ) live in SMEM.
    """
    interpret = _resolve_interpret(interpret)
    (sems, entries, class_mask, layer_mask, entry_scale), squeeze = \
        _with_table_axis(sems, entries, class_mask, layer_mask, entry_scale)
    K, B, L, d = sems.shape
    I = entries.shape[2]
    Bp = -(-B // B_TILE) * B_TILE
    Ip = -(-I // I_TILE) * I_TILE
    n_i, nb = Ip // I_TILE, Bp // B_TILE
    semp = jnp.pad(sems, ((0, 0), (0, Bp - B), (0, 0), (0, 0)))
    semp = jnp.transpose(semp, (2, 0, 1, 3)).reshape(L, K * Bp, d)
    ep = jnp.pad(entries, ((0, 0), (0, 0), (0, Ip - I), (0, 0)))
    cmp_ = jnp.pad(class_mask.astype(jnp.int32), ((0, 0), (0, Ip - I)))
    quantized = entry_scale is not None

    inputs = [semp, ep.reshape(K * L, Ip, d),
              cmp_.reshape(K * n_i, 1, I_TILE),
              layer_mask.astype(jnp.int32), theta.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((L, B_TILE, d), lambda k, b: (0, k * nb + b, 0)),
        _resident((L, Ip, d), lambda k, b: (k, 0, 0)),
        _resident((n_i, 1, I_TILE), lambda k, b: (k, 0, 0)),
        _smem_spec(),
        _smem_spec(),
    ]
    if quantized:
        sp = jnp.pad(entry_scale, ((0, 0), (0, 0), (0, Ip - I)))
        sp = jnp.transpose(sp.reshape(K, L, n_i, I_TILE), (0, 2, 1, 3))
        inputs.append(sp.reshape(K * n_i, L, I_TILE))
        in_specs.append(_resident((n_i, L, I_TILE), lambda k, b: (k, 0, 0)))

    out_shapes, out_specs = _flat_batch_outputs(K, Bp, nb, L)
    outs = pl.pallas_call(
        functools.partial(_kernel_all, alpha=alpha, num_layers=L,
                          n_i_tiles=n_i, quantized=quantized),
        grid=(K, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((n_i, B_TILE, I_TILE), jnp.float32),   # Eq.-1 acc. A
        ],
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)
    return _unflatten(outs, K, Bp, B, L, squeeze)


# ---------------------------------------------------------------------------
# class-tiled all-layer kernel (huge-I tables that overflow VMEM)
# ---------------------------------------------------------------------------

def _kernel_all_tiled(sem_ref, entries_hbm, cmask_hbm, lmask_ref, theta_ref,
                      *args,
                      alpha: float, num_layers: int, n_c_blocks: int,
                      i_block: int, quantized: bool):
    """One batch-tile grid step of the tiled lookup, class blocks streamed
    through a **double-buffered DMA pipeline**.

    ``entries``/``class_mask`` (and the scale plane when quantized) arrive
    unblocked (``ANY`` memory space = HBM on TPU); the kernel owns the slab
    movement: two ``(L, i_block, d)`` VMEM slots, block ``t+1``'s async copy
    started before block ``t``'s compute begins, so the MXU never waits on a
    slab in the steady state — the lookup is bandwidth-, not latency-bound.
    The Eq.-1 accumulator only ever needs this block's ``(B_TILE, i_block)``
    column range — accumulation is columnwise across layers — so it rides the
    ``fori_loop`` carry with the running per-layer top-2/argmax state rather
    than persisting in scratch across grid revisits (there are none: the grid
    is batch tiles only).
    """
    if quantized:
        (scale_hbm, score_ref, pred_ref,
         ent_sl, msk_sl, scl_sl, dma_sems) = args
    else:
        (score_ref, pred_ref, ent_sl, msk_sl, dma_sems) = args
        scale_hbm = scl_sl = None
    bt = score_ref.shape[0]
    k = pl.program_id(0)                              # table

    def ent_dma(slot, t):
        return pltpu.make_async_copy(
            entries_hbm.at[k, :, pl.ds(t * i_block, i_block), :],
            ent_sl.at[slot], dma_sems.at[slot, 0])

    def msk_dma(slot, t):
        return pltpu.make_async_copy(
            cmask_hbm.at[k, t], msk_sl.at[slot], dma_sems.at[slot, 1])

    def scl_dma(slot, t):
        return pltpu.make_async_copy(
            scale_hbm.at[k, t], scl_sl.at[slot], dma_sems.at[slot, 2])

    def start(slot, t):
        ent_dma(slot, t).start()
        msk_dma(slot, t).start()
        if quantized:
            scl_dma(slot, t).start()

    def wait(slot, t):
        ent_dma(slot, t).wait()
        msk_dma(slot, t).wait()
        if quantized:
            scl_dma(slot, t).wait()

    start(0, 0)                                       # warm-up: block 0

    def block_step(t, carry):
        # One (B_t,) running top-1 / top-2 / argmax triple per layer: L
        # separate carries, so no per-layer column update (a scatter the
        # TPU lowering refuses) is ever needed.
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_c_blocks)
        def _():                                      # prefetch block t+1
            start(jax.lax.rem(t + 1, 2), t + 1)

        wait(slot, t)
        lo = t * i_block                  # global class offset of this block
        cmask = msk_sl[slot] > 0                      # (1, i_block)
        a_prev = jnp.where(cmask, 0.0, NEG) * jnp.ones((bt, 1))

        out = []
        for j in range(num_layers):
            semn = _normalized_tap(sem_ref, j)
            active = lmask_ref[k, j] > 0              # SMEM scalar

            e = ent_sl[slot, j].astype(jnp.float32)   # (i_block, d)
            c = jnp.dot(semn, e.T, precision=_DOT_PRECISION,
                        preferred_element_type=jnp.float32)  # (B_t, i_block)
            if quantized:
                c = _dequantize_scores(c, scl_sl[slot, pl.ds(j, 1), :])
            at = jnp.where(cmask, c + alpha * a_prev, NEG)   # Eq. (1)
            # Inactive layer: carry the accumulator state unchanged.
            a_prev = jnp.where(active, at, a_prev)
            # Block-local top-2, merged into the carried per-layer state.
            out.append(_top2_merge(at, lo, *carry[j]))
        return tuple(out)

    init = tuple((jnp.full((bt,), NEG, jnp.float32),
                  jnp.full((bt,), NEG, jnp.float32),
                  jnp.zeros((bt,), jnp.int32)) for _ in range(num_layers))
    state = jax.lax.fori_loop(0, n_c_blocks, block_step, init)

    # All blocks merged: Eq. (2) + first-hit exit, layer by layer.
    exit_layer = jnp.full((bt,), num_layers, jnp.int32)
    for j, (m1, m2, a1) in enumerate(state):
        active = lmask_ref[k, j] > 0
        d = _eq2_score(m1, m2, active)
        score_ref[:, j] = d
        pred_ref[:, j] = a1
        hit_j = active & (d > theta_ref[j])
        exit_layer = jnp.where((exit_layer == num_layers) & hit_j,
                               j, exit_layer)
    pred_ref[:, num_layers] = exit_layer


@functools.partial(jax.jit, static_argnames=("alpha", "i_block", "interpret"))
def cache_lookup_all_layers_tiled(sems: jax.Array, entries: jax.Array,
                                  class_mask: jax.Array, layer_mask: jax.Array,
                                  theta: jax.Array, *, alpha: float = 0.5,
                                  i_block: int | None = None,
                                  entry_scale: jax.Array | None = None,
                                  interpret: bool | None = None):
    """Class-tiled variant of :func:`cache_lookup_all_layers` for tables too
    large to hold ``entries (L, I, d)`` VMEM-resident.

    Same contract as the single-pass kernel (returns ``(scores (B, L),
    preds (B, L), exit_layer (B,))``) but ``entries`` stays in HBM
    (``ANY`` memory space) and the kernel streams ``(L, i_block, d)`` slabs
    through a two-slot VMEM scratch with manual async copies, prefetching
    block ``t+1`` while block ``t`` computes (double buffering).  VMEM use is
    O(``2·L·i_block·d``) instead of O(``L·I·d``), so ``I`` is bounded by HBM,
    not VMEM.  Quantized (int8 + bf16 scale) tables stream a third slab of
    per-row scales that multiply the score columns after the dot.

    ``i_block`` — class-block width (rounded to an ``I_TILE`` multiple);
    ``None`` picks the largest block whose working set fits the budget
    (:func:`repro.kernels.common.pick_class_block`).  A leading table axis
    batches K tables into one launch, as for the single-pass kernel.
    """
    interpret = _resolve_interpret(interpret)
    (sems, entries, class_mask, layer_mask, entry_scale), squeeze = \
        _with_table_axis(sems, entries, class_mask, layer_mask, entry_scale)
    K, B, L, d = sems.shape
    I = entries.shape[2]
    quantized = entry_scale is not None
    if i_block is None:
        i_block = pick_class_block(
            L, d, entry_dtype="int8" if quantized else "float32")
    i_block = max(I_TILE, (i_block // I_TILE) * I_TILE)
    Bp = -(-B // B_TILE) * B_TILE
    Ip = -(-I // i_block) * i_block
    n_c, nb = Ip // i_block, Bp // B_TILE
    semp = jnp.pad(sems, ((0, 0), (0, Bp - B), (0, 0), (0, 0)))
    semp = jnp.transpose(semp, (2, 0, 1, 3)).reshape(L, K * Bp, d)
    ep = jnp.pad(entries, ((0, 0), (0, 0), (0, Ip - I), (0, 0)))
    cmp_ = jnp.pad(class_mask.astype(jnp.int32),
                   ((0, 0), (0, Ip - I))).reshape(K, n_c, 1, i_block)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    inputs = [semp, ep, cmp_, layer_mask.astype(jnp.int32),
              theta.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((L, B_TILE, d), lambda k, b: (0, k * nb + b, 0)),
        any_spec,                                      # entries: kernel DMAs
        any_spec,                                      # class mask: ditto
        _smem_spec(),
        _smem_spec(),
    ]
    n_dma = 2
    scratch = [
        pltpu.VMEM((2, L, i_block, d), ep.dtype),      # entry slabs (2 slots)
        pltpu.VMEM((2, 1, i_block), jnp.int32),        # class-mask slabs
    ]
    if quantized:
        # Block-major (K, n_c, Lp, i_block) scale plane: one whole-tile DMA
        # per block.  Layers pad to the 16-row bf16 sublane tile — a 12-row
        # window of a tiled bf16 plane is not a legal DMA.
        Lp = -(-L // 16) * 16
        sp = jnp.pad(entry_scale, ((0, 0), (0, Lp - L), (0, Ip - I)))
        inputs.append(jnp.transpose(sp.reshape(K, Lp, n_c, i_block),
                                    (0, 2, 1, 3)))
        in_specs.append(any_spec)                      # scales: kernel DMAs
        scratch.append(pltpu.VMEM((2, Lp, i_block), entry_scale.dtype))
        n_dma = 3
    scratch.append(pltpu.SemaphoreType.DMA((2, n_dma)))

    out_shapes, out_specs = _flat_batch_outputs(K, Bp, nb, L)
    outs = pl.pallas_call(
        functools.partial(_kernel_all_tiled, alpha=alpha, num_layers=L,
                          n_c_blocks=n_c, i_block=i_block,
                          quantized=quantized),
        grid=(K, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)
    return _unflatten(outs, K, Bp, B, L, squeeze)
