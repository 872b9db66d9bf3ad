"""Pallas TPU kernel: flat reproducible sum (RSUM, paper §III-D).

TPU adaptation of the paper's AVX kernel (DESIGN.md §3.3/§12):

* the V SIMD lanes become the 128 VPU lanes; per-lane running sums live in a
  VMEM scratch accumulator of shape (L, ncols, 8, 128) as exact integer
  window offsets — one independent ladder per fused output column;
* the paper's NB-element carry-propagation cadence becomes one renorm per
  grid block (block_rows * 2^(W-1) is kept below 2^30 by ops.max_block_rows,
  so the int32 window arithmetic can never overflow between renorms);
* extraction against fixed lattice extractors A^(l) = 1.5 * 2^(e_l) runs on
  the VPU as two float adds + one multiply + int convert per live level (the
  ladder is window-agnostic: callers hand it a prescan-pruned sub-ladder);
* the horizontal merge (paper Eq. 2/3) happens outside the kernel as an exact
  integer lane reduction (ops.py).

The grid is 1-D over row blocks and must execute sequentially (accumulator
carried in scratch), which is the default "arbitrary" dimension semantics.

Layout (what the TPU compiler accepts, DESIGN.md §12): every operand is
lane-dense.  The extractor ladders arrive broadcast to ``(L, ncols, 8, 128)``
so the kernel never reshapes a lane vector into sublanes, and the running
sums are per *slot* of one ``(8, 128)`` tile: the kernel walks its block one
sublane tile at a time (``fori_loop``), so extraction works on single vregs
and the accumulators stay register-resident.  The 8 sublane slots of each
lane are folded outside the kernel; the per-lane result is the exact integer
sum either way, so every layout yields the same canonical state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # VPU lane width: last-dim tile
SUBLANES = 8     # f32 sublane tile: block_rows must be a multiple of this


def _rsum_kernel(x_ref, a_ref, iu_ref, k_out, c_out, k_acc, c_acc,
                 *, L: int, m: int, ncols: int, block_rows: int):
    i = pl.program_id(0)
    nblk = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        k_acc[...] = jnp.zeros_like(k_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    def tile(t, ks):
        row = pl.multiple_of(t * SUBLANES, SUBLANES)
        ks = list(ks)
        for c in range(ncols):
            r = x_ref[c, pl.ds(row, SUBLANES), :]        # (8, 128) f32
            for l in range(L):
                A = a_ref[l, c]                          # per-column extractor
                q = (r + A) - A                          # EFT vs fixed extractor
                r = r - q                                # exact remainder
                ks[l * ncols + c] += (q * iu_ref[l, c]).astype(jnp.int32)
        return tuple(ks)

    # int32 pinned: rows/8 * 2^(W-1) per slot < 2^30 (ops.max_block_rows)
    zero = jnp.zeros((SUBLANES, LANES), jnp.int32)
    # int32 bounds: under jax_enable_x64 Python ints would make the index
    # int64, which Mosaic does not take
    ks = jax.lax.fori_loop(np.int32(0), np.int32(block_rows // SUBLANES),
                           tile, (zero,) * (L * ncols))
    for l in range(L):
        for c in range(ncols):
            kk = k_acc[l, c] + ks[l * ncols + c]
            d = kk >> (m - 2)                            # renorm (carry prop.)
            k_acc[l, c] = kk - (d << (m - 2))
            c_acc[l, c] += d

    @pl.when(i == nblk - 1)
    def _done():
        k_out[...] = k_acc[...]
        c_out[...] = c_acc[...]


def rsum_pallas_call(x3d, A, inv_ulp, *, L: int, m: int, block_rows: int,
                     interpret: bool):
    """Launch the kernel.

    ``x3d``: (ncols, rows_total, 128) f32 with rows_total a multiple of
    block_rows, and block_rows a multiple of 8; ``A``/``inv_ulp``: (L, ncols)
    f32 per-column extractor ladders (L is the *live* level count — possibly
    a pruned window).  Returns per-lane (k, C): (L, ncols, 128) int32 each,
    canonical (``0 <= k < 2^(m-2)``).
    """
    ncols, rows_total, lanes = x3d.shape
    assert lanes == LANES and rows_total % block_rows == 0
    assert block_rows % SUBLANES == 0
    nblk = rows_total // block_rows
    tile = (L, ncols, SUBLANES, LANES)
    A = jnp.broadcast_to(A.reshape(L, ncols, 1, 1), tile)
    inv_ulp = jnp.broadcast_to(inv_ulp.reshape(L, ncols, 1, 1), tile)
    kernel = functools.partial(_rsum_kernel, L=L, m=m, ncols=ncols,
                               block_rows=block_rows)
    whole = pl.BlockSpec(tile, lambda i: (0, 0, 0, 0))
    k8, c8 = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((ncols, block_rows, LANES), lambda i: (0, i, 0)),
            whole,
            whole,
        ],
        out_specs=[whole, whole],
        out_shape=[jax.ShapeDtypeStruct(tile, jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM(tile, jnp.int32)] * 2,
        interpret=interpret,
    )(x3d, A, inv_ulp)
    # fold the 8 sublane slots of each lane: 8 canonical k sum below 2^(m+1)
    k = k8.sum(axis=2, dtype=jnp.int32)
    c = c8.sum(axis=2, dtype=jnp.int32)
    d = k >> (m - 2)
    return k - (d << (m - 2)), c + d
