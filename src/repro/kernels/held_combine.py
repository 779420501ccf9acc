"""Held-expert combine kernel: each token's gated sum of the rows its held
experts computed, read straight from the grouped matmuls' sorted output.

``out`` (M, d) holds the held assignments' rows sorted by expert, group e
taking ``sizes[e]`` rows from the top and its tokens in ascending order
(what a stable sort by expert gives).  ``pos`` (T, K) names the row of each
(token, k) assignment; a row at or past the held total H = Σ sizes marks
an absent assignment, which has no row.  The kernel computes

    y[t] = Σ_k gate[t, k] · out[pos[t, k]]            over held k

Grid = token tiles of ``tile`` tokens.  A tile's held rows are, per expert,
one contiguous run of ``out`` (the tile's tokens that chose that expert),
so the tile needs only the 8-row blocks those runs touch: 8 rows is the
least a DMA of ``out``'s tiled HBM layout may move.  At 64 tokens a tile
and granite's routing that is about 1.8x the held rows, since a run of
about 9 rows shares its end blocks with other tiles.  The wrapper lists
each tile's blocks in ascending order, a block shared by two runs once;
the kernel streams them from HBM (``memory_space=ANY``) in chunks of 16
blocks, 128 rows, each chunk's copies in flight together, the next chunk
(or the next tile's first) started before the current one is combined.
Per chunk, with r the row id of each of its 128 rows (-1 in a slot no
listed block filled):

    S    = the chunk's rows, those at or past H or in no listed block zeroed
    W    = Σ_k where(pos[:, k] == r, gate[:, k], 0)        (tile, 128) f32
    acc += W @ S                                           MXU, f32 acc

A token's K experts are distinct, so each entry of W is one gate or 0.
For bfloat16 rows W is split into three bfloat16 parts that sum to the
float32 gate exactly, so the three MXU passes multiply exactly and sum in
float32; float32 rows take one pass at ``HIGHEST``.  The (tile, d) float32
accumulator is rounded once to ``out``'s dtype.

What is read: only blocks holding a held row of the tile.  Their other
rows are held rows of other tiles, except in the block that holds row
H - 1, whose rows at or past H (at most 7) are zeroed before use.  The
custom call is named ``held_combine`` so a device trace finds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

TOKEN_TILE = 64         # tokens per grid step (fastest of 64-256 on a v5e)
BLOCK = 8               # rows per DMA: the sublane tile of out's HBM layout
CHUNK = 16              # blocks per chunk: 128 rows, the MXU's depth
_COLS = 512             # output columns per MXU call


def _bf16_parts(w):
    """Three bfloat16 arrays whose float32 sum is ``w`` exactly."""
    parts = []
    for _ in range(3):
        p = w.astype(jnp.bfloat16)
        parts.append(p)
        w = w - p.astype(jnp.float32)
    return parts


def _kernel(blk_ref, nblk_ref, chunk0_ref, held_ref, pos_ref, gate_ref,
            out_hbm, y_ref, buf, acc, sem, *, cap: int):
    i = pl.program_id(0)
    rows = CHUNK * BLOCK
    held = held_ref[0]

    def copies(j, c, slot, act):
        """``act`` on the copy of each listed block of tile j's chunk c."""
        def one(b, carry):
            row = pl.multiple_of(blk_ref[j * cap + c * CHUNK + b] * BLOCK,
                                 BLOCK)
            act(pltpu.make_async_copy(
                out_hbm.at[pl.ds(row, BLOCK)],
                buf.at[slot, pl.ds(pl.multiple_of(b * BLOCK, BLOCK), BLOCK)],
                sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.clip(nblk_ref[j] - c * CHUNK, 0, CHUNK),
                          one, 0)

    def start(j, c, slot):
        copies(j, c, slot, lambda cp: cp.start())

    @pl.when(i == 0)
    def _():
        start(0, 0, 0)

    acc[...] = jnp.zeros_like(acc)
    nch = jnp.maximum(1, (nblk_ref[i] + CHUNK - 1) // CHUNK)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def chunk(c, carry):
        slot = jax.lax.rem(chunk0_ref[i] + c, 2)
        copies(i, c, slot, lambda cp: cp.wait())

        @pl.when(c + 1 < nch)
        def _():
            start(i, c + 1, 1 - slot)

        @pl.when((c + 1 == nch) & (i + 1 < pl.num_programs(0)))
        def _():
            start(i + 1, 0, 1 - slot)

        nb = jnp.clip(nblk_ref[i] - c * CHUNK, 0, CHUNK)
        first = i * cap + c * CHUNK
        last = blk_ref[first + jnp.maximum(nb, 1) - 1]

        @pl.when((nb < CHUNK) | ((last + 1) * BLOCK > held))
        def _():                  # rows never copied or past H: zero them
            bad = ((sub // BLOCK >= nb)
                   | ((sub // BLOCK == nb - 1)
                      & (last * BLOCK + sub % BLOCK >= held)))
            buf[slot] = jnp.where(bad, 0, buf[slot]).astype(buf.dtype)

        # row id of each of the chunk's rows; -1 where no listed block is
        r_lane = jnp.full((1, rows), -1, jnp.int32)
        for b in range(CHUNK):
            r_lane = jnp.where(lane // BLOCK == b,
                               blk_ref[first + b] * BLOCK + lane % BLOCK,
                               r_lane)
        r_lane = jnp.where((lane // BLOCK < nb) & (r_lane < held), r_lane, -1)

        p, g = pos_ref[...], gate_ref[...]
        w = jnp.zeros((p.shape[0], rows), jnp.float32)
        for k in range(p.shape[1]):
            w = jnp.where(p[:, k:k + 1] == r_lane, g[:, k:k + 1], w)
        d = acc.shape[1]
        cols = _COLS if d % _COLS == 0 else d
        if buf.dtype == jnp.float32:
            parts, prec = [w], jax.lax.Precision.HIGHEST
        else:
            parts, prec = _bf16_parts(w), jax.lax.Precision.DEFAULT
        for lo in range(0, d, cols):
            s = buf[slot, :, lo:lo + cols]
            y = acc[:, lo:lo + cols]
            for part in parts:
                y = y + jnp.dot(part, s, precision=prec,
                                preferred_element_type=jnp.float32)
            acc[:, lo:lo + cols] = y
        return carry

    jax.lax.fori_loop(0, nch, chunk, 0)
    y_ref[...] = acc[...].astype(y_ref.dtype)


def _tile_blocks(pos, sizes, tile: int, cap: int):
    """Each tile's 8-row blocks of ``out``, ascending, each once.

    pos (Tp, K), Tp a multiple of ``tile`` -> (blocks (nt·cap,), counts
    (nt,), held total (1,))."""
    Tp, K = pos.shape
    n = sizes.shape[0]
    nt = Tp // tile
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    expert = (pos[..., None] >= ends).sum(-1)                 # n: absent
    mine = (expert[..., None] == jnp.arange(n)).reshape(nt, tile * K, n)
    p = pos.reshape(nt, tile * K, 1)
    top = jnp.iinfo(jnp.int32).max
    lo = jnp.where(mine, p, top).min(1) // BLOCK              # (nt, n)
    hi = jnp.where(mine, p, -1).max(1) // BLOCK
    # runs ascend with the expert: skip a block the run before listed
    prev = jax.lax.cummax(hi, axis=1)
    prev = jnp.concatenate([jnp.full((nt, 1), -1, prev.dtype),
                            prev[:, :-1]], axis=1)
    start = jnp.maximum(lo, prev + 1)
    count = jnp.where(hi >= 0, jnp.maximum(hi - start + 1, 0), 0)
    end = jnp.cumsum(count, axis=1)
    slot = jnp.arange(cap)
    run = jnp.minimum((slot[None, :, None] >= end[:, None, :]).sum(-1),
                      n - 1)                                  # (nt, cap)
    blocks = (jnp.take_along_axis(start, run, 1) + slot
              - jnp.take_along_axis(end - count, run, 1))
    total = jnp.minimum(end[:, -1], cap)      # cap holds when runs are whole
    blocks = jnp.where(slot < total[:, None], blocks, 0)
    return blocks.reshape(-1), total, ends[-1:]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def held_combine(out: jax.Array, pos: jax.Array, gate: jax.Array,
                 sizes: jax.Array, *, tile: int = TOKEN_TILE,
                 interpret: bool | None = None) -> jax.Array:
    """out (M, d), pos (T, K) int, gate (T, K), sizes (n,) -> y (T, d).

    ``y[t] = Σ_k gate[t, k] · out[pos[t, k]]`` over the assignments whose
    row lies below ``sizes.sum()``; ``out``'s rows are grouped by expert,
    ``sizes[e]`` each, tokens ascending within a group.  Products and sums
    are float32; ``y`` takes ``out``'s dtype.
    """
    interpret = resolve_interpret(interpret)
    M, d = out.shape
    T, K = pos.shape
    n = sizes.shape[0]
    Tp = -(-T // tile) * tile
    nt = Tp // tile
    # at most min(K, n) held rows a token; each run adds at most one
    # partly foreign block at either end
    cap = -(-(tile * min(K, n) + 6 * n) // BLOCK) + n
    cap = -(-cap // CHUNK) * CHUNK
    pos = jnp.pad(pos.astype(jnp.int32), ((0, Tp - T), (0, 0)),
                  constant_values=M)
    gate = jnp.pad(gate.astype(jnp.float32), ((0, Tp - T), (0, 0)))
    if M % BLOCK:
        out = jnp.pad(out, ((0, -M % BLOCK), (0, 0)))
    blocks, count, held = _tile_blocks(pos, sizes, tile, cap)
    chunks = jnp.maximum(1, -(-count // CHUNK))
    chunk0 = jnp.cumsum(chunks) - chunks
    y = pl.pallas_call(
        functools.partial(_kernel, cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((tile, K), lambda i, *_: (i, 0)),
                pl.BlockSpec((tile, K), lambda i, *_: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, CHUNK * BLOCK, d), out.dtype),
                pltpu.VMEM((tile, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((Tp, d), out.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="held_combine",
    )(blocks, count, chunk0.astype(jnp.int32), held, pos, gate, out)
    return y[:T]
