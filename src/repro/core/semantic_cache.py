"""Class-based semantic cache — the paper's Eq. (1)/(2) lookup machinery.

The cache is a 2-D table: rows = classes, columns = cache layers (paper §IV,
Fig. 4).  Entry ``(i, j)`` is the L2-normalised semantic centroid of class ``i``
at cache layer ``j``.  During inference the model emits a pooled semantic
vector at every *active* cache layer; the lookup computes cosine similarities
against the *active* (hot-spot) class entries, accumulates them across layers
with decay ``alpha`` (Eq. 1) and exits early when the discriminative score of
the top-2 classes clears ``theta`` (Eq. 2).

Everything here is pure ``jnp`` and jit/vmap-safe.  The batched
``lookup_all_layers`` is the oracle used by the round simulator; it
dispatches between the fused single-``pallas_call`` kernel
(:mod:`repro.kernels.cache_lookup`) on TPU backends and the unfused
``lax.scan`` reference ``lookup_all_layers_ref`` (also the kernel's
bit-parity oracle) elsewhere.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Python float, not jnp.float32(...): a jnp call here would initialise the
# backend at import time; weak-typed promotion keeps every use float32.
NEG = -1e9


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Static configuration of the semantic cache."""

    num_classes: int          # I — rows of the global table
    num_layers: int           # L — columns (pre-set cache layers in the model)
    sem_dim: int              # dimensionality of semantic vectors
    alpha: float = 0.5        # Eq. (1) cross-layer decay
    # Eq. (2) hit threshold Θ.  Scalar (the paper's design) or a per-layer
    # tuple — a beyond-paper extension: shallow taps are weakly discriminative
    # (Fig. 1b), so a depth-decaying Θ buys hit accuracy at the shallow layers
    # without giving up deep-exit coverage (benchmarks/theta_schedule.py).
    # Landscape-dependent: the paper uses 0.012 (ResNet) / 0.035 (VGG); our
    # synthetic-tap landscape calibrates to ~0.055-0.1 for the <3% loss SLO.
    theta: float | tuple = 0.10
    # Storage dtype of *allocated* (client/serving/tier) cache entries:
    # "float32" (exact, the default) or "int8" — symmetric per-(layer, class)
    # quantization with bf16 scales (the serving/kv_quant.py idiom), cutting
    # lookup bytes ~4× and roughly doubling the classes per VMEM block
    # (repro.kernels.common.pick_class_block).  The *server's* global table
    # and Eq.-3/4 update tensors stay float32 — only the downloaded lookup
    # cuts are quantized, bounding the drift to the lookup scores
    # (tests/test_quant_cache.py documents the error analysis).
    entry_dtype: str = "float32"

    def theta_vec(self):
        import jax.numpy as jnp
        if isinstance(self.theta, tuple):
            assert len(self.theta) == self.num_layers
            return jnp.asarray(self.theta, jnp.float32)
        return jnp.full((self.num_layers,), float(self.theta), jnp.float32)


class CacheTable(NamedTuple):
    """A (possibly partially-allocated) semantic cache.

    ``entries``     — (L, I, d) float32 rows (L2-normalised where valid), or
                      int8 quantized rows when ``entry_scale`` is set.
    ``class_mask``  — (I,) bool, hot-spot classes present in this cache.
    ``layer_mask``  — (L,) bool, cache layers activated by the server.
    ``entry_scale`` — ``None`` for float32 tables; (L, I) bf16 per-row
                      symmetric dequantization scales for int8 tables
                      (``entries[l, i] ≈ q[l, i] * entry_scale[l, i]``).
    """

    entries: jax.Array
    class_mask: jax.Array
    layer_mask: jax.Array
    entry_scale: jax.Array | None = None

    @property
    def num_layers(self) -> int:
        return self.entries.shape[0]

    @property
    def num_classes(self) -> int:
        return self.entries.shape[1]

    @property
    def quantized(self) -> bool:
        return self.entry_scale is not None


def empty_table(cfg: CacheConfig) -> CacheTable:
    return CacheTable(
        entries=jnp.zeros((cfg.num_layers, cfg.num_classes, cfg.sem_dim), jnp.float32),
        class_mask=jnp.zeros((cfg.num_classes,), bool),
        layer_mask=jnp.zeros((cfg.num_layers,), bool),
    )


def l2_normalize(x: jax.Array, axis: int = -1, eps: float = 1e-8) -> jax.Array:
    return x / (jnp.linalg.norm(x, axis=axis, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# int8 entry quantization (the serving/kv_quant.py idiom, per cache row)
# ---------------------------------------------------------------------------


def quantize_entries(entries: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(layer, class) int8 quantization with bf16 scales.

    Same recipe as :func:`repro.serving.kv_quant.quantize` with one
    refinement: the rounding step divides by the *stored* (bf16-rounded)
    scale, not the exact float32 one, so dequantization satisfies the exact
    bound ``|q * scale - x| ≤ scale / 2`` elementwise — the property
    ``tests/test_quant_cache.py`` pins down.  (Rounding against the f32
    scale would add a ``127 * |scale_bf16 - scale_f32|`` term.)

    Returns ``(q (L, I, d) int8, scale (L, I) bf16)``.
    """
    scale = jnp.max(jnp.abs(entries), axis=-1) / 127.0          # (L, I) f32
    scale = jnp.maximum(scale, 1e-12).astype(jnp.bfloat16)
    sf = scale.astype(jnp.float32)[..., None]
    q = jnp.clip(jnp.round(entries / sf), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_entries(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_entries`: ``q * scale`` in float32."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


# allocate_subtable runs eagerly at round start; the jitted version keeps the
# 1/127-style constants inside the compiled program instead of tripping the
# implicit-transfer guard with per-round host scalars (cf. _f32_zero below).
_quantize_entries_jit = jax.jit(quantize_entries)


def quantize_table(table: CacheTable) -> CacheTable:
    """Quantize a float32 table's entries to int8 + bf16 scales."""
    if table.entry_scale is not None:
        return table
    q, scale = quantize_entries(table.entries)
    return table._replace(entries=q, entry_scale=scale)


def dequantize_table(table: CacheTable) -> CacheTable:
    """Materialise an int8 table back to float32 (no-op on float32 tables)."""
    if table.entry_scale is None:
        return table
    return table._replace(
        entries=dequantize_entries(table.entries, table.entry_scale),
        entry_scale=None)


def pool_semantic(h: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """Pool an activation into a semantic vector (paper: global average pool).

    ``h`` — (..., S, d) sequence/spatial activation; ``mask`` — (..., S) validity.
    """
    if mask is None:
        return h.mean(axis=-2)
    m = mask.astype(h.dtype)[..., None]
    return (h * m).sum(axis=-2) / jnp.maximum(m.sum(axis=-2), 1.0)


def cosine_scores(sem: jax.Array, entries_j: jax.Array, class_mask: jax.Array,
                  scale_j: jax.Array | None = None) -> jax.Array:
    """C[·, i] — cosine similarity of pooled vectors vs. layer-``j`` entries.

    ``sem`` — (..., d); ``entries_j`` — (I, d); returns (..., I) with inactive
    classes at ``NEG`` so they never win the top-2.  ``scale_j`` — (I,) per-
    row scales of int8 ``entries_j``: a row's scale factors out of its dot
    product, so it multiplies that class's score (exactly ``sem_n ·
    (q_i * s_i)`` in real arithmetic; this order is the one the fused
    kernels reproduce bit for bit).
    """
    sem_n = l2_normalize(sem)
    if scale_j is None:
        c = sem_n @ entries_j.T  # entries are stored normalised
    else:
        c = (sem_n @ entries_j.astype(jnp.float32).T) * scale_j.astype(
            jnp.float32)
    return jnp.where(class_mask, c, NEG)


def accumulate(c: jax.Array, a_prev: jax.Array, alpha: float,
               class_mask: jax.Array) -> jax.Array:
    """Eq. (1): A[i,j] = C[i,j] + alpha * A[i,j-1] (only for active classes)."""
    a = c + alpha * a_prev
    return jnp.where(class_mask, a, NEG)


class LayerDecision(NamedTuple):
    score: jax.Array        # D_j, (...,)
    pred: jax.Array         # arg-top-1 class, (...,) int32
    a_new: jax.Array        # accumulated similarities, (..., I)


def discriminative_score(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Eq. (2): D = (A_a − A_b) / A_b over the top-2 *active* classes.

    ``a`` — (..., I) accumulated similarities (inactive classes already NEG).
    Returns (D, top1_class).  Guarded against A_b ≤ 0 (cosine sims can be
    negative early on): in that regime the score is defined as 0 — no hit —
    which matches the paper's operating regime where hits only fire once the
    runner-up similarity is meaningfully positive.
    """
    top2, idx = jax.lax.top_k(a, 2)
    a_a, a_b = top2[..., 0], top2[..., 1]
    d = jnp.where(a_b > 1e-6, (a_a - a_b) / jnp.maximum(a_b, 1e-6), 0.0)
    # If fewer than 2 active classes exist, a_b is NEG — no valid score.
    d = jnp.where(a_b <= NEG / 2, 0.0, d)
    return d, idx[..., 0].astype(jnp.int32)


def lookup_layer(table: CacheTable, j: jax.Array, sem: jax.Array,
                 a_prev: jax.Array, alpha: float) -> LayerDecision:
    """Single-layer lookup at (dynamic) layer index ``j``."""
    entries_j = jnp.take(table.entries, j, axis=0)
    c = cosine_scores(sem, entries_j, table.class_mask)
    a = accumulate(c, a_prev, alpha, table.class_mask)
    d, pred = discriminative_score(a)
    return LayerDecision(score=d, pred=pred, a_new=a)


class LookupResult(NamedTuple):
    """Batched all-layer lookup outcome (the simulator oracle).

    ``hit``        — (B,) bool, any active layer cleared theta.
    ``exit_layer`` — (B,) int32, first hitting layer index, or L if no hit.
    ``pred``       — (B,) int32, class at exit (valid where hit).
    ``scores``     — (B, L) float32, D_j at every layer (0 where inactive).
    ``acc``        — (B, L, I) accumulated similarities (for absorption
                     rules).  ``None`` on the fused-kernel path, which by
                     design never materialises this tensor.
    """

    hit: jax.Array
    exit_layer: jax.Array
    pred: jax.Array
    scores: jax.Array
    acc: jax.Array | None


@partial(jax.jit, static_argnames=("cfg",))
def lookup_all_layers_ref(table: CacheTable, sems: jax.Array,
                          cfg: CacheConfig) -> LookupResult:
    """Unfused ``lax.scan`` reference for Eq. (1)/(2) across all L layers.

    Jitted at module level (``cfg`` static, like ``round_step``): called
    eagerly, the fresh ``step`` closure would force ``lax.scan`` to re-trace
    and re-compile on *every* call — each compile mmaps JIT code pages that
    are never released, so per-round callers (the topology tier lookups, the
    serving loop) leak address-space maps until ``vm.max_map_count`` kills
    the process with a misleading "Cannot allocate memory".

    ``sems`` — (B, L, d) pooled semantic vectors at every cache layer.

    Inactive layers are transparent: they neither accumulate (the paper only
    performs lookups at activated layers) nor can they hit.  The *first*
    hitting active layer is the exit layer; its top-1 class is the result.

    This is the bit-parity oracle for the fused Pallas kernel
    (:func:`repro.kernels.cache_lookup.cache_lookup_all_layers`) and the
    CPU fallback; it is also the only path that materialises the full
    ``(B, L, I)`` accumulator (``acc``).

    Quantized (int8) tables apply each row's scale to its dot product
    (:func:`cosine_scores`) — this defines the reference semantics the fused
    quantized kernels reproduce.
    """
    B = sems.shape[0]
    a0 = jnp.where(table.class_mask, 0.0, NEG) * jnp.ones((B, cfg.num_classes))
    scales = (jnp.zeros((cfg.num_layers, 0)) if table.entry_scale is None
              else table.entry_scale)

    def step(a_prev, inputs):
        sem_j, entries_j, scale_j, active_j = inputs
        c = cosine_scores(sem_j, entries_j, table.class_mask,
                          None if table.entry_scale is None else scale_j)
        a = accumulate(c, a_prev, cfg.alpha, table.class_mask)
        # Inactive layer: carry state unchanged, emit no score.
        a_out = jnp.where(active_j, a, a_prev)
        d, pred = discriminative_score(a)
        d = jnp.where(active_j, d, 0.0)
        return a_out, (d, pred, a_out)

    sems_t = jnp.swapaxes(sems, 0, 1)                     # (L, B, d)
    _, (scores, preds, accs) = jax.lax.scan(
        step, a0, (sems_t, table.entries, scales, table.layer_mask))
    scores = jnp.swapaxes(scores, 0, 1)                   # (B, L)
    preds = jnp.swapaxes(preds, 0, 1)                     # (B, L)
    accs = jnp.swapaxes(accs, 0, 1)                       # (B, L, I)

    hits_per_layer = scores > cfg.theta_vec()[None, :]    # (B, L)
    hit = hits_per_layer.any(axis=1)
    exit_layer = jnp.where(
        hit, jnp.argmax(hits_per_layer, axis=1), cfg.num_layers).astype(jnp.int32)
    pred = jnp.take_along_axis(
        preds, jnp.minimum(exit_layer, cfg.num_layers - 1)[:, None], axis=1)[:, 0]
    return LookupResult(hit=hit, exit_layer=exit_layer, pred=pred,
                        scores=scores, acc=accs)


def lookup_all_layers(table: CacheTable, sems: jax.Array, cfg: CacheConfig,
                      *, impl: str = "auto", mesh=None) -> LookupResult:
    """Run Eq. (1)/(2) across all L layers for a batch of tap vectors.

    Dispatches between the fused Pallas kernels
    (:mod:`repro.kernels.cache_lookup`) and the unfused ``jnp`` reference
    (:func:`lookup_all_layers_ref`).

    ``impl``
      * ``"auto"``   — fused on a TPU backend, reference otherwise
        (interpret-mode emulation of the kernel is far slower than XLA on
        CPU).
      * ``"fused"``  — force a kernel; single-pass vs. class-tiled is chosen
        from the VMEM budget estimate in :mod:`repro.kernels.common`
        (interpret mode is still auto-detected inside the kernel).
      * ``"fused_single"`` / ``"fused_tiled"`` — pin a specific kernel
        (parity tests and benchmarks).
      * ``"ref"``    — the ``lax.scan`` oracle.

    The fused paths return ``acc=None`` — they never materialise the
    ``(B, L, I)`` accumulator; callers needing ``acc`` must ask for
    ``impl="ref"``.

    A stacked table — a leading client axis K on every leaf, ``sems``
    (K, B, L, d) — looks up each client's batch in its own table, and every
    result field gains the K axis.  The fused kernels take the stack in one
    launch; this is how the round engine batches its clients (a ``vmap``
    of the kernels does not lower on the TPU).

    ``mesh`` — the mesh of a class-sharded cluster, whose gathered tables
    are replicated on it: a kernel then runs whole on every device under
    ``shard_map`` (JAX refuses to lower a Mosaic kernel in a multi-device
    program outside one).
    """
    if impl == "auto":
        impl = "fused" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        if table.entries.ndim == 4:
            return jax.vmap(lambda t, s: lookup_all_layers_ref(t, s, cfg))(
                table, sems)
        return lookup_all_layers_ref(table, sems, cfg)
    entry_dtype = "int8" if table.entry_scale is not None else "float32"
    if impl == "fused":
        from repro.kernels.common import single_pass_fits
        impl = ("fused_single"
                if single_pass_fits(cfg.num_layers, cfg.num_classes,
                                    cfg.sem_dim, entry_dtype=entry_dtype)
                else "fused_tiled")
    if impl not in ("fused_single", "fused_tiled"):
        raise ValueError(f"unknown lookup impl: {impl!r}")

    from repro.kernels.cache_lookup import (cache_lookup_all_layers,
                                            cache_lookup_all_layers_tiled)
    kernel = (cache_lookup_all_layers if impl == "fused_single"
              else cache_lookup_all_layers_tiled)

    def call(sems, table, theta):
        return kernel(sems, table.entries, table.class_mask, table.layer_mask,
                      theta, alpha=cfg.alpha, entry_scale=table.entry_scale)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        call = jax.shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)
    scores, preds, exit_layer = call(sems, table, cfg.theta_vec())
    hit = exit_layer < cfg.num_layers
    pred = jnp.take_along_axis(
        preds, jnp.minimum(exit_layer, cfg.num_layers - 1)[..., None],
        axis=-1)[..., 0]
    return LookupResult(hit=hit, exit_layer=exit_layer, pred=pred,
                        scores=scores, acc=None)


_F32_ZERO = None


def _f32_zero() -> jax.Array:
    """Lazily-cached device-resident float32 zero.  ``allocate_subtable``
    runs *eagerly* for a single cut; a literal ``0.0`` there would
    re-materialise a host scalar each call — an implicit transfer the
    runtime sanitizer's guard forbids.  One explicit device_put, reused;
    made eagerly even when first asked for inside a trace
    (:func:`allocate_subtables`), so no tracer is cached."""
    global _F32_ZERO
    if _F32_ZERO is None:
        import numpy as np
        with jax.ensure_compile_time_eval():
            _F32_ZERO = jax.device_put(np.zeros((), np.float32))
    return _F32_ZERO


def allocate_subtable(global_entries: jax.Array, x: jax.Array,
                      *, entry_dtype: str = "float32") -> CacheTable:
    """Extract a client cache from the global table given an allocation matrix.

    ``x`` — (L, I) bool indicator (ACA output, transposed to layer-major).
    The paper allocates full rows of the hot-spot set at chosen layers, so
    class/layer masks are recovered by projection.

    ``entry_dtype="int8"`` quantizes the cut on the way out (the download a
    client/tier actually stores); the server-side ``global_entries`` stay
    float32.  Unallocated rows quantize to all-zero ``q`` with the floor
    scale, so masking semantics are unchanged.
    """
    layer_mask = x.any(axis=1)
    class_mask = x.any(axis=0)
    keep = (layer_mask[:, None] & class_mask[None, :])[..., None]
    entries = jnp.where(keep, global_entries, _f32_zero())
    if entry_dtype == "int8":
        q, scale = _quantize_entries_jit(entries)
        return CacheTable(entries=q, class_mask=class_mask,
                          layer_mask=layer_mask, entry_scale=scale)
    if entry_dtype != "float32":
        raise ValueError(f"unknown entry dtype: {entry_dtype!r}")
    return CacheTable(
        entries=entries,
        class_mask=class_mask,
        layer_mask=layer_mask,
    )


@partial(jax.jit, static_argnames=("entry_dtype", "stacked"))
def allocate_subtables(global_entries: jax.Array, xs: jax.Array,
                       *, entry_dtype: str = "float32",
                       stacked: bool = True):
    """K clients' cuts in one program: :func:`allocate_subtable` vmapped
    over the (K, L, I) allocation matrices ``xs``.

    Returns the stacked :class:`CacheTable` (K, L, I, d) that
    :func:`repro.core.engine.round_step` takes, or with ``stacked=False``
    the list of the K tables.  Each cut is bitwise the one
    :func:`allocate_subtable` makes alone, in one dispatch whatever K is.
    """
    tables = jax.vmap(lambda x: allocate_subtable(
        global_entries, x, entry_dtype=entry_dtype))(xs)
    if stacked:
        return tables
    return [jax.tree_util.tree_map(lambda a, k=k: a[k], tables)
            for k in range(xs.shape[0])]
