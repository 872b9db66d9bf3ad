"""Jitted public wrappers for the flat reproducible-sum kernel.

Two entry points:

* :func:`rsum_acc` — historical flat API: sum all elements of a vector into
  one canonical accumulator (bit-identical to ``ref.rsum_acc_ref``);
* :func:`rsum_table` — the planner-facing strategy (DESIGN.md §12): the
  fused multi-column table layout of :func:`repro.core.aggregates
  .segment_table` specialized to ``num_segments == 1`` (SQL SUM without
  GROUP BY, gradient-norm sums).  Returns a stacked ``(1, ncols, L)``
  accumulator table, window-pruned extraction included, bit-identical to
  every other strategy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import accumulator as acc_mod
from repro.core import eft
from repro.core import prescan
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec
from repro.kernels.mode import resolve_interpret
from repro.kernels.rsum.kernel import LANES, SUBLANES, rsum_pallas_call

__all__ = ["rsum", "rsum_acc", "rsum_table", "max_block_rows"]

# Bytes of the TPU's scoped VMEM (16 MiB by default on v5e) that the
# kernel's pipelined buffers may take: Pallas double-buffers every block, so
# the input block counts twice, beside the ladders, outputs and scratch.  The
# rest is headroom for the compiler's own scratch.
VMEM_BUDGET_BYTES = 1 << 23


def max_block_rows(spec: ReproSpec, ncols: int = 1,
                   levels: tuple[int, int] | None = None) -> int:
    """Largest safe ``block_rows``, floored to a multiple of the lane tile.

    Two independent bounds (DESIGN.md §3.3):

    * **overflow** — each per-lane, per-level window offset gains at most
      ``2^(W-1) - 1`` per row and is renormalized once per grid block from a
      canonical value ``< 2^(m-2)``, so the in-flight int32 stays below
      ``2^(m-2) + block_rows * 2^(W-1)``; ``block_rows <= 2^(30 - (W-1))``
      keeps that under ``2^21 + 2^30 < 2^31``.  This holds per level, for
      any live-level count.
    * **VMEM** — Pallas double-buffers the ``(ncols, block_rows, 128)`` f32
      input block, and the ``(nlev, ncols, 8, 128)`` tiles (two ladders, two
      outputs, all double-buffered, plus two scratch accumulators) take ten
      tiles per live level and column; all of it must fit the budget.  The
      *pruned-window* level count ``nlev`` sizes the tiles, so a wide ladder
      shrinks the block (this is what actually binds for W=12, whose
      overflow bound alone would allow an absurd 2^19-row block).

    The result is a multiple of ``SUBLANES`` (f32 sublane tile) and at least
    ``SUBLANES``, so the zero-padded tail block consists of whole lane tiles
    — zero rows extract to ``k == 0`` at every level (``q = (0 + A) - A = 0``
    exactly), hence padding can never perturb the sums.
    """
    overflow = 1 << (30 - (spec.W - 1))
    nlev = prescan.window_length(levels, spec)
    ncols = max(int(ncols), 1)
    tiles = 10 * nlev * ncols * SUBLANES * LANES * 4
    free = max(VMEM_BUDGET_BYTES - tiles, 0)
    rows = min(overflow, free // (2 * ncols * LANES * 4))
    return max((rows // SUBLANES) * SUBLANES, SUBLANES)


@functools.partial(jax.jit, static_argnames=("num_segments", "spec",
                                             "block_rows", "levels",
                                             "interpret"))
def rsum_table(values, segment_ids=None, num_segments: int = 1,
               spec: ReproSpec = ReproSpec(), e1=None,
               block_rows: int | None = None,
               levels: tuple[int, int] | None = None,
               interpret: bool | None = None) -> ReproAcc:
    """Fused flat reduction: ``(n, ncols) -> ReproAcc (1, ncols, L)``.

    The ``rsum`` execution strategy of :func:`repro.core.aggregates
    .segment_table` — valid only for ``num_segments == 1``, where there is
    no table to index and the kernel's per-lane running sums beat every
    scatter/one-hot path.  ``segment_ids`` is accepted (and ignored) for
    dispatch-signature compatibility: with one group every row belongs to
    it.  ``levels`` is a prescan-proved live window; the returned table is
    full-L with exact zeros on pruned levels.

    The kernel is compiled by Mosaic on the TPU backend and interpreted on
    the CPU backend or where ``interpret=True`` asks for it; it is never
    interpreted on a TPU (:func:`repro.kernels.mode.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if spec.m > 30:
        raise ValueError("the TPU kernel supports float32 accumulators")
    if num_segments != 1:
        raise ValueError("rsum is the flat-aggregation strategy: "
                         "num_segments must be 1")
    del segment_ids
    values = jnp.asarray(values, spec.dtype)
    if values.ndim == 1:
        values = values[:, None]
    n, ncols = values.shape
    lo, hi = prescan.check_levels(levels, spec)
    nlev = hi - lo
    if e1 is None:
        e1 = acc_mod.required_e1(values, spec, axis=0)        # (ncols,)
    e1 = jnp.broadcast_to(jnp.asarray(e1, jnp.int32), (ncols,))

    rows_cap = max_block_rows(spec, ncols, levels)
    rows = rows_cap if block_rows is None else min(block_rows, rows_cap)
    rows = max((rows // SUBLANES) * SUBLANES, SUBLANES)

    # per-column extractor sub-ladder over the live window
    es = e1[None, :] - jnp.arange(lo, hi, dtype=jnp.int32)[:, None] * spec.W
    A = eft.extractor(es, spec.dtype)                         # (nlev, ncols)
    inv_ulp = eft.pow2(spec.m - es, spec.dtype)

    per_blk = rows * LANES
    pad = (-n) % per_blk
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad, ncols), spec.dtype)])
    x3d = values.T.reshape(ncols, -1, LANES)

    k_l, c_l = rsum_pallas_call(x3d, A, inv_ulp, L=nlev, m=spec.m,
                                block_rows=rows, interpret=interpret)
    # horizontal merge (paper Eq. 2/3) as an exact int reduction over lanes:
    # 128 canonical lanes sum to < 128 * 2^(m-2) < 2^31
    k = k_l.astype(spec.int_dtype).sum(axis=2)                # (nlev, ncols)
    C = c_l.astype(spec.int_dtype).sum(axis=2)
    k, C = acc_mod.renorm(k, C, spec)
    k = acc_mod.pad_levels(k.T[None], levels, spec)           # (1, ncols, L)
    C = acc_mod.pad_levels(C.T[None], levels, spec)
    return ReproAcc(k=k, C=C, e1=e1[None, :])


@functools.partial(jax.jit, static_argnames=("spec", "block_rows",
                                             "interpret"))
def rsum_acc(x, spec: ReproSpec = ReproSpec(), block_rows: int = 1024,
             interpret: bool | None = None) -> ReproAcc:
    """Reproducible sum of all elements of ``x`` -> canonical accumulator.

    Bit-identical to the pure-jnp oracle ``ref.rsum_acc_ref`` for any
    block_rows (associativity of the integer accumulation).
    """
    x = jnp.asarray(x, spec.dtype).reshape(-1)
    acc = rsum_table(x[:, None], num_segments=1, spec=spec,
                     block_rows=block_rows, interpret=interpret)
    return ReproAcc(k=acc.k[0, 0], C=acc.C[0, 0], e1=acc.e1[0, 0])


def rsum(x, spec: ReproSpec = ReproSpec(), block_rows: int = 1024,
         interpret: bool | None = None):
    """Finalized reproducible sum (float scalar)."""
    return acc_mod.finalize(rsum_acc(x, spec, block_rows, interpret), spec)
