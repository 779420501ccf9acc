"""Shared kernel-launch helpers: backend detection and the VMEM budget
model that picks between the single-pass and class-tiled fused lookups.

The budget numbers model a TPU core's ~16 MB of VMEM.  We only plan
against a fraction of it (``VMEM_FRACTION``) — the pipeline needs
headroom for double-buffered input blocks and the compiler's own
temporaries, so treating the full 16 MB as available would be optimistic
exactly when it matters (large tables).
"""

from __future__ import annotations

import jax

# Tile sizes shared by the cache-lookup kernels (MXU/VPU lane-aligned).
B_TILE = 128
I_TILE = 128

VMEM_BYTES = 16 * 2 ** 20          # per-core VMEM (TPU v4/v5-class)
VMEM_FRACTION = 0.75               # plannable fraction (pipeline headroom)
_F32 = 4                           # bytes

# Per-element entry bytes by table dtype, plus the per-(layer, class) scale
# that rides along with quantized entries (bf16, see kv_quant idiom in
# repro.core.semantic_cache.quantize_entries).
_ENTRY_BYTES = {"float32": 4, "int8": 1}
_SCALE_BYTES = {"float32": 0, "int8": 2}


def entry_row_bytes(sem_dim: int, entry_dtype: str = "float32") -> int:
    """Bytes of one (layer, class) entry row: d elements + its scale."""
    try:
        return sem_dim * _ENTRY_BYTES[entry_dtype] + _SCALE_BYTES[entry_dtype]
    except KeyError:
        raise ValueError(f"unknown entry dtype: {entry_dtype!r}") from None


def default_interpret() -> bool:
    """Interpret Pallas kernels unless we are on a real TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means auto-detect from the active backend."""
    return default_interpret() if interpret is None else bool(interpret)


def vmem_budget_bytes() -> int:
    return int(VMEM_BYTES * VMEM_FRACTION)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lookup_single_pass_vmem_bytes(num_layers: int, num_classes: int,
                                  sem_dim: int, b_tile: int = B_TILE,
                                  entry_dtype: str = "float32") -> int:
    """Resident bytes of the single-pass fused lookup at one grid step.

    The whole ``entries (L, I_pad, d)`` table (plus its bf16 scale plane when
    quantized), one batch tile of taps, and the ``(B_TILE, I_pad)`` Eq.-1
    accumulator all live in VMEM together — this is the ceiling the
    class-tiled variant removes.
    """
    ip = _round_up(max(num_classes, 1), I_TILE)
    entries = num_layers * ip * entry_row_bytes(sem_dim, entry_dtype)
    taps = b_tile * num_layers * sem_dim * _F32
    acc = b_tile * ip * _F32
    outs = b_tile * (2 * num_layers + 1) * _F32
    return entries + taps + acc + outs


def lookup_tiled_vmem_bytes(num_layers: int, i_block: int, sem_dim: int,
                            b_tile: int = B_TILE,
                            entry_dtype: str = "float32") -> int:
    """Resident bytes of the class-tiled lookup at one grid step: the two
    ``(L, i_block, d)`` entries slab slots (+ scale planes when quantized)
    of the kernel's double-buffered DMA, the double-buffered tile of taps,
    the per-block Eq.-1 accumulator, the running top-2/argmax state, and the
    double-buffered output tiles.

    Both slab slots count: the TPU compiler allocates them as scoped VMEM
    next to the pipeline's own buffers, so a plan that booked one slot
    overflowed the chip's 16 MiB scoped limit at serving widths.
    """
    entries = 2 * num_layers * i_block * entry_row_bytes(sem_dim,
                                                          entry_dtype)
    taps = 2 * b_tile * num_layers * sem_dim * _F32
    acc = 2 * b_tile * i_block * _F32          # a_prev + candidate
    top2 = 3 * b_tile * num_layers * _F32
    outs = 2 * b_tile * (2 * num_layers + 1) * _F32
    return entries + taps + acc + top2 + outs


def single_pass_fits(num_layers: int, num_classes: int, sem_dim: int,
                     b_tile: int = B_TILE,
                     entry_dtype: str = "float32") -> bool:
    """Can the whole table stay VMEM-resident for the single-pass kernel?"""
    return (lookup_single_pass_vmem_bytes(num_layers, num_classes, sem_dim,
                                          b_tile, entry_dtype)
            <= vmem_budget_bytes())


def pick_class_block(num_layers: int, sem_dim: int,
                     b_tile: int = B_TILE, max_block: int = 4096,
                     entry_dtype: str = "float32") -> int:
    """Largest I-block (multiple of ``I_TILE``, ≤ ``max_block``) whose tiled
    working set fits the VMEM budget.  Always returns at least ``I_TILE``.
    int8 entries shrink the slab ~4×, so the quantized block is never smaller
    than the float32 one for the same budget (property-tested)."""
    block = max_block
    while block > I_TILE and (lookup_tiled_vmem_bytes(num_layers, block,
                                                      sem_dim, b_tile,
                                                      entry_dtype)
                              > vmem_budget_bytes()):
        block -= I_TILE
    return max(block, I_TILE)
