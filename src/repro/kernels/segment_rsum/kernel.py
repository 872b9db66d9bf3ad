"""Pallas TPU kernel: reproducible GROUPBY (segment RSUM, paper §V).

TPU adaptation (DESIGN.md §3.2 item 4): the paper's cache-resident summation
buffers become MXU tiles.  Per level, the extracted contributions q are exact
integer multiples of ulp(A^(l)); a (block_n x group_tile) one-hot matmul sums
them *exactly* in float32 provided block_n <= 2^(m - W + 2) — the float
mantissa never fills.  The per-group running sums live as int32 window
offsets in VMEM scratch with one renormalization (carry propagation) per
input block.

Multi-column fusion (DESIGN.md §10): the kernel takes a *stacked* input
(ncols, block_n) with per-column extractor ladders (L, ncols), so one
one-hot matmul per level accumulates every aggregate column at once —
SUM / COUNT / MEAN / VAR share a single streaming pass over the rows
instead of re-streaming per aggregate.  The contraction
(ncols, block_n) @ (block_n, group_tile) reuses the same one-hot operand
for all columns.

Grid: (group_tiles, input_blocks) — inner axis sequential (accumulation);
each input block is re-streamed once per group tile, trading HBM reads for
MXU-friendly tiles exactly the way the paper trades partitioning passes for
cache residency.  The W knob trades per-level accuracy for tile size
(W=18 -> 128-row tiles; W=12 -> 8192-row tiles), the TPU analogue of the
paper's bsz/cache trade-off (§V-C).

Level pruning (DESIGN.md §11): the kernel is *ladder-agnostic* — ``L`` is
simply the number of extractor rows in ``A``/``inv_ulp``, so the wrapper
(ops.py) may hand it a prescan-proved sub-ladder ``levels = (lo, hi)`` and
the kernel streams, extracts and renormalizes only those ``hi - lo`` live
levels.  Extraction starting at level ``lo`` with ``r = x`` is exact
because every skipped top level provably extracts q = 0 (the residual
passes through unchanged); the skipped levels are re-embedded as exact
zeros outside, keeping the full-L table bit-identical to an unpruned run
while the per-block FLOPs, VMEM scratch and output DMA all shrink by
``L / (hi - lo)``.

Layout (what the TPU compiler accepts, DESIGN.md §3.2): rows run along the
128 lanes.  Each grid step streams ``step`` rows and walks them one
128-lane sub-block at a time; the ids arrive as a ``(1, 128)`` lane vector
and the one-hot is built transposed, ``(group_tile, 128)``, by comparing
them against a sublane iota, so no id is ever moved from a lane into a
sublane.  The extractor ladders and their inverse ulps arrive broadcast to
lane-dense ``(L, ncols, 128)`` blocks.  The contraction is
``(ncols, 128) x (group_tile, 128)^T`` at ``precision=HIGHEST``: the MXU's
default f32 precision rounds operands to bf16, which would drop the low
bits of the extracted integers.  Scaling ``q`` by ``1/ulp`` before the
matmul is exact (a power of two), so the MXU sums small integers, and those
sums are exact while a sub-block holds at most ``2^(m-W+2)`` real rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # rows per one-hot matmul: one lane tile


def exact_block_bound(m: int, W: int) -> int:
    """Max rows per one-hot matmul with exact f32 accumulation: 2^(m-W+2)."""
    return 1 << (m - W + 2)


def _segment_kernel(ids_ref, x_ref, a_ref, iu_ref, k_out, c_out,
                    k_acc, c_acc, *, L: int, m: int, step: int,
                    group_tile: int):
    ni = pl.program_id(1)
    nblk = pl.num_programs(1)
    gi = pl.program_id(0)

    @pl.when(ni == 0)
    def _init():
        k_acc[...] = jnp.zeros_like(k_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    groups = (jax.lax.broadcasted_iota(jnp.int32, (group_tile, LANES), 0)
              + gi * group_tile)

    def sub_block(s, carry):
        col = pl.multiple_of(s * LANES, LANES)
        ids = ids_ref[0, :, pl.ds(col, LANES)]               # (1, 128) int32
        onehot_t = (groups == ids).astype(jnp.float32)       # (gt, 128)
        r = x_ref[0, :, pl.ds(col, LANES)]                   # (ncols, 128)
        for l in range(L):
            A = a_ref[l]                                     # per-column
            q = (r + A) - A                                  # EFT, fixed A
            r = r - q
            # exact: |q / ulp| < 2^(W-1) and a sub-block holds at most
            # 2^(m-W+2) real rows, so every partial sum is below 2^(m+1)
            part = jax.lax.dot_general(
                q * iu_ref[l], onehot_t, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)          # (ncols, gt)
            k_acc[l] += part.astype(jnp.int32)
        return carry

    # int32 bounds: under jax_enable_x64 Python ints would make the index
    # int64, which Mosaic does not take
    jax.lax.fori_loop(np.int32(0), np.int32(step // LANES), sub_block, None)

    kk = k_acc[...]
    d = kk >> (m - 2)                                        # carry prop.
    k_acc[...] = kk - (d << (m - 2))
    c_acc[...] += d

    @pl.when(ni == nblk - 1)
    def _done():
        k_out[...] = k_acc[...]
        c_out[...] = c_acc[...]


def segment_rsum_pallas_call(ids3d, x3d, A, inv_ulp, *, L: int, m: int,
                             group_tile: int, num_group_tiles: int,
                             interpret: bool):
    """ids3d: (nblk, 1, step) int32; x3d: (nblk, ncols, step) f32, with
    ``step`` a multiple of 128; A/inv_ulp: (L, ncols) f32.  Returns (k, C):
    (L, ncols, G_padded) int32 with G_padded = tiles * group_tile, and
    ``group_tile`` a multiple of 128."""
    nblk, ncols, step = x3d.shape
    assert step % LANES == 0 and group_tile % LANES == 0
    kernel = functools.partial(_segment_kernel, L=L, m=m, step=step,
                               group_tile=group_tile)
    ladder = (L, ncols, LANES)
    A = jnp.broadcast_to(A[:, :, None], ladder)
    inv_ulp = jnp.broadcast_to(inv_ulp[:, :, None], ladder)
    g_total = num_group_tiles * group_tile
    table = pl.BlockSpec((L, ncols, group_tile), lambda gi, ni: (0, 0, gi))
    return pl.pallas_call(
        kernel,
        grid=(num_group_tiles, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, step), lambda gi, ni: (ni, 0, 0)),
            pl.BlockSpec((1, ncols, step), lambda gi, ni: (ni, 0, 0)),
            pl.BlockSpec(ladder, lambda gi, ni: (0, 0, 0)),
            pl.BlockSpec(ladder, lambda gi, ni: (0, 0, 0)),
        ],
        out_specs=[table, table],
        out_shape=[
            jax.ShapeDtypeStruct((L, ncols, g_total), jnp.int32),
            jax.ShapeDtypeStruct((L, ncols, g_total), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, ncols, group_tile), jnp.int32),
            pltpu.VMEM((L, ncols, group_tile), jnp.int32),
        ],
        interpret=interpret,
    )(ids3d, x3d, A, inv_ulp)
