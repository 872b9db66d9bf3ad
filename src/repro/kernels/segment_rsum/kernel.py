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
one-hot matmul accumulates every aggregate column and level at once —
SUM / COUNT / MEAN / VAR share a single streaming pass over the rows
instead of re-streaming per aggregate.  The contraction reuses the same
one-hot operand for all columns, levels and digit planes.

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
sublane.  The column count arrives padded to a multiple of 8 sublanes
(zero rows under zero ladders), and the extractor ladders and their
inverse ulps arrive broadcast to lane-dense ``(L, ncols, 128)`` blocks.

Digits (DESIGN.md §3.2 item 4): one bf16 MXU pass per sub-block and group
tile sums every live level and column.  Scaling ``q`` by ``1/ulp`` (a power
of two) makes it an integer ``v`` with ``|v| <= 2^(W-1)``, and
:func:`split_digits` writes ``v`` exactly as ``p = ceil(W/9)`` planes, each
an integer multiple of ``2^(9j)`` whose digit lies in ``[-256, 256]``: 8
significant bits, exact in bf16, as the 0/1 one-hot is.  The
``(L * p * ncols, 128) x (group_tile, 128)^T`` contraction at the default
precision multiplies exactly and accumulates in f32, and every partial sum
of a plane is a multiple of its ``2^(9j)`` below ``2^(m+1)`` in magnitude
while a sub-block holds at most ``2^(m-W+2)`` real rows, so it is exact.
The planes' sums convert to int32 and add into the level's window offsets:
the same integers the f32 contraction at ``precision=HIGHEST`` gave, in one
MXU pass in place of up to six per level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # rows per one-hot matmul: one lane tile
SUBLANES = 8     # f32 rows per vreg: the column count is padded to this
DIGIT_BITS = 9   # an integer digit in [-2^8, 2^8] has 8 bits: exact in bf16


def exact_block_bound(m: int, W: int) -> int:
    """Max rows per one-hot matmul with exact f32 accumulation: 2^(m-W+2)."""
    return 1 << (m - W + 2)


def bf16_digits(W: int) -> int:
    """Digit planes :func:`split_digits` needs for ``|v| <= 2^(W-1)``:
    ``ceil(W / 9)`` (1 for W <= 9, 2 for W <= 18, 3 up to f32's W = 21)."""
    return -(-W // DIGIT_BITS)


def split_digits(v, p: int) -> list:
    """Split integer-valued floats ``v`` with ``|v| <= 2^(9p-1)`` into ``p``
    planes, top first, that sum to ``v`` exactly.  Plane ``j`` (counted from
    the bottom) is ``d_j * 2^(9j)`` with an integer ``|d_j| <= 256``.

    ``d_j = floor(r / 2^(9j) + 1/2)`` rounds the remainder ``r`` to the
    nearest multiple of ``2^(9j)``: scaling by a power of two is exact, and
    ``|r / 2^(9j)| <= 2^20`` leaves the ``+ 1/2`` exact in f32's 24 bits.
    The next remainder ``r - d_j 2^(9j)`` is exact and lies in
    ``(-2^(9j-1), 2^(9j-1)]``.  So the top plane's remainder is ``v``, with
    ``|v / 2^(9(p-1))| <= 2^8``, and every lower one is at most
    ``2^(9j+8)``; either way ``|d_j| <= 256``, boundary included.  The
    bottom plane is the last remainder, at most ``2^8``, or ``v`` itself
    when ``p = 1``.  ``floor`` and not the fixed extractor
    ``(r + C) - C``: XLA folds that to ``r`` for a constant ``C``."""
    planes = []
    for j in range(p - 1, 0, -1):
        hi = jnp.floor(v * 2.0 ** -(DIGIT_BITS * j) + 0.5) * 2.0 ** (
            DIGIT_BITS * j)
        planes.append(hi)
        v = v - hi
    planes.append(v)
    return planes


def _segment_kernel(ids_ref, x_ref, a_ref, iu_ref, k_out, c_out,
                    k_acc, c_acc, *, L: int, m: int, p: int, step: int,
                    group_tile: int):
    ni = pl.program_id(1)
    nblk = pl.num_programs(1)
    gi = pl.program_id(0)
    ncols = x_ref.shape[1]

    @pl.when(ni == 0)
    def _init():
        k_acc[...] = jnp.zeros_like(k_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    groups = (jax.lax.broadcasted_iota(jnp.int32, (group_tile, LANES), 0)
              + gi * group_tile)

    def sub_block(s, carry):
        col = pl.multiple_of(s * LANES, LANES)
        ids = ids_ref[0, :, pl.ds(col, LANES)]               # (1, 128) int32
        onehot_t = (groups == ids).astype(jnp.bfloat16)      # (gt, 128)
        r = x_ref[0, :, pl.ds(col, LANES)]                   # (ncols, 128)
        planes = []
        for l in range(L):
            A = a_ref[l]                                     # per-column
            q = (r + A) - A                                  # EFT, fixed A
            r = r - q
            # |q / ulp| <= 2^(W-1), boundary included: level 1 admits
            # |x| < 2^(W-1) ulp (ReproSpec.lattice_e1), each later level
            # gets a residual of at most half the ulp above, 2^(W-1) of its
            # own, and rounding to a multiple of ulp never passes the
            # multiple 2^(W-1) ulp.  W <= 9p: p exact bf16 digit planes
            planes += split_digits(q * iu_ref[l], p)
        digits = jnp.concatenate(planes, axis=0).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            digits, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (L*p*nc, gt)
        k_acc[...] += part.astype(jnp.int32).reshape(
            L, p, ncols, group_tile).sum(axis=1)
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
                             W: int, group_tile: int, num_group_tiles: int,
                             interpret: bool):
    """ids3d: (nblk, 1, step) int32; x3d: (nblk, ncols, step) f32, with
    ``step`` a multiple of 128; A/inv_ulp: (L, ncols) f32.  Returns (k, C):
    (L, ncols, G_padded) int32 with G_padded = tiles * group_tile, and
    ``group_tile`` a multiple of 128."""
    nblk, ncols, step = x3d.shape
    assert step % LANES == 0 and group_tile % LANES == 0
    # pad the columns to whole sublane tiles, so that each digit plane
    # stacks aligned; HBM's (8, 128) tiling holds these rows already
    nc = -(-ncols // SUBLANES) * SUBLANES
    x3d = jnp.pad(x3d, ((0, 0), (0, nc - ncols), (0, 0)))
    ladder = (L, nc, LANES)
    A, inv_ulp = (jnp.broadcast_to(jnp.pad(a, ((0, 0), (0, nc - ncols)))
                                   [:, :, None], ladder)
                  for a in (A, inv_ulp))
    kernel = functools.partial(_segment_kernel, L=L, m=m, p=bf16_digits(W),
                               step=step, group_tile=group_tile)
    g_total = num_group_tiles * group_tile
    table = pl.BlockSpec((L, nc, group_tile), lambda gi, ni: (0, 0, gi))
    k, C = pl.pallas_call(
        kernel,
        grid=(num_group_tiles, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, step), lambda gi, ni: (ni, 0, 0)),
            pl.BlockSpec((1, nc, step), lambda gi, ni: (ni, 0, 0)),
            pl.BlockSpec(ladder, lambda gi, ni: (0, 0, 0)),
            pl.BlockSpec(ladder, lambda gi, ni: (0, 0, 0)),
        ],
        out_specs=[table, table],
        out_shape=[
            jax.ShapeDtypeStruct((L, nc, g_total), jnp.int32),
            jax.ShapeDtypeStruct((L, nc, g_total), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, nc, group_tile), jnp.int32),
            pltpu.VMEM((L, nc, group_tile), jnp.int32),
        ],
        interpret=interpret,
    )(ids3d, x3d, A, inv_ulp)
    return k[:, :ncols], C[:, :ncols]
