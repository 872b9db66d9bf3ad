"""Jitted public wrappers for the segment RSUM / fused GROUPBY kernel.

``segment_agg_kernel`` is the fused multi-column entry point: a stacked
(n, ncols) value matrix aggregates into an accumulator table (G, ncols, L)
in one streaming pass (one bf16 one-hot matmul per 128-row sub-block serves
every column and live level — DESIGN.md §10).  ``segment_rsum_kernel`` is
the historical single-column API, kept as a thin wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import accumulator as acc_mod
from repro.core import eft
from repro.core import prescan
from repro.core.accumulator import ReproAcc
from repro.core.aggregates import pad_and_chunk
from repro.core.types import ReproSpec
from repro.kernels.mode import resolve_interpret
from repro.kernels.segment_rsum.kernel import (LANES, exact_block_bound,
                                               segment_rsum_pallas_call)

__all__ = ["segment_agg_kernel", "segment_rsum_kernel", "exact_block_bound",
           "max_step_rows"]

STEP_ROWS = 4096     # rows per grid step, when the int32 table allows it


def max_step_rows(spec: ReproSpec) -> int:
    """Rows per grid step: as many 128-lane sub-blocks as the int32 table
    absorbs between two renorms.  A sub-block adds less than
    ``real * 2^(W-1)`` to any window offset (``real`` rows of it are data),
    and a canonical offset is below ``2^(m-2)``, so ``2^30`` of growth per
    step keeps every offset inside int32."""
    real = min(exact_block_bound(spec.m, spec.W), LANES)
    subs = (1 << 30) // (real << (spec.W - 1))
    return LANES * max(1, min(subs, STEP_ROWS // LANES))


@functools.partial(jax.jit, static_argnames=("num_segments", "spec",
                                             "block_n", "group_tile",
                                             "interpret", "levels"))
def segment_agg_kernel(values, segment_ids, num_segments: int,
                       spec: ReproSpec = ReproSpec(), e1=None,
                       block_n: int | None = None, group_tile: int = 512,
                       interpret: bool | None = None,
                       levels: tuple[int, int] | None = None) -> ReproAcc:
    """Fused reproducible GROUPBY on the MXU: (n, ncols) -> table (G, ncols, L).

    Bit-identical to ``repro.core.aggregates.segment_table`` (any method)
    given the same per-column ``e1`` (defaults to the per-column row max,
    matching ``segment_table``).  ``levels = (lo, hi)`` hands the kernel a
    pruned extractor sub-ladder (static; prescan-proved, see
    :mod:`repro.core.prescan`): the grid streams and accumulates only the
    live levels, and the dead levels come back as exact zeros — the full-L
    table is bit-identical either way.

    ``block_n`` is the number of rows per grid step (default and ceiling
    :func:`max_step_rows`, floored to a multiple of 128); ``group_tile`` is
    rounded up to a multiple of 128.  Neither can change a bit.  The kernel
    is compiled by Mosaic on the TPU backend and interpreted on the CPU
    backend or where ``interpret=True`` asks for it; it is never interpreted
    on a TPU (:func:`repro.kernels.mode.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if spec.m > 30:
        raise ValueError("the TPU kernel supports float32 accumulators")
    lo, hi = prescan.check_levels(levels, spec)
    nlev = hi - lo
    cap = max_step_rows(spec)
    step = max(LANES, (min(block_n or cap, cap) // LANES) * LANES)
    values = jnp.asarray(values, spec.dtype)
    if values.ndim != 2:
        raise ValueError("segment_agg_kernel expects values (n, ncols)")
    segment_ids = jnp.asarray(segment_ids, jnp.int32).reshape(-1)
    ncols = values.shape[1]

    if e1 is None:
        e1 = acc_mod.required_e1(values, spec, axis=0)       # (ncols,)
    e1 = jnp.broadcast_to(jnp.asarray(e1, jnp.int32), (ncols,))
    lvl = jnp.arange(lo, hi, dtype=jnp.int32)
    es = e1[None, :] - lvl[:, None] * spec.W                 # (nlev, ncols)
    A = eft.extractor(es, spec.dtype)                        # (nlev, ncols)
    inv_ulp = eft.pow2(spec.m - es, spec.dtype)              # (nlev, ncols)

    real = exact_block_bound(spec.m, spec.W)
    if real < LANES:
        # fewer than 128 rows sum exactly in one matmul: give each run of
        # `real` rows its own 128-lane sub-block, padded with zero rows
        # under id -1, which match no group tile
        xc, ic = pad_and_chunk(values, real, segment_ids, dump_id=-1)
        xc = jnp.pad(xc, ((0, 0), (0, LANES - real), (0, 0)))
        ic = jnp.pad(ic, ((0, 0), (0, LANES - real)), constant_values=-1)
        values, segment_ids = xc.reshape(-1, ncols), ic.reshape(-1)
    # padding ids = -1: matches no group tile
    x3d, ids2d = pad_and_chunk(values, step, segment_ids, dump_id=-1)
    x3d = x3d.transpose(0, 2, 1)                             # (nblk, nc, step)

    g_lanes = -(-num_segments // LANES) * LANES
    group_tile = min(-(-group_tile // LANES) * LANES, g_lanes)
    n_tiles = -(-num_segments // group_tile)

    k, C = segment_rsum_pallas_call(
        ids2d[:, None, :], x3d, A, inv_ulp, L=nlev, m=spec.m, W=spec.W,
        group_tile=group_tile, num_group_tiles=n_tiles, interpret=interpret)
    k = k[:, :, :num_segments].transpose(2, 1, 0)         # (G, ncols, nlev)
    C = C[:, :, :num_segments].transpose(2, 1, 0)
    k = acc_mod.pad_levels(k.astype(spec.int_dtype), levels, spec)
    C = acc_mod.pad_levels(C.astype(spec.int_dtype), levels, spec)
    e1_b = jnp.broadcast_to(e1, (num_segments, ncols))
    return ReproAcc(k=k, C=C, e1=e1_b)


def segment_rsum_kernel(values, segment_ids, num_segments: int,
                        spec: ReproSpec = ReproSpec(),
                        block_n: int | None = None, group_tile: int = 512,
                        interpret: bool | None = None) -> ReproAcc:
    """Reproducible GROUPBY-SUM on the MXU.  Bit-identical to
    ``repro.core.segment.segment_rsum`` (any method) and to ref.py."""
    values = jnp.asarray(values, spec.dtype).reshape(-1)
    # historical contract: one global lattice exponent for the value column
    e1 = acc_mod.required_e1(values, spec)
    acc = segment_agg_kernel(values[:, None], segment_ids, num_segments,
                             spec, e1=e1[None], block_n=block_n,
                             group_tile=group_tile, interpret=interpret)
    return ReproAcc(k=acc.k[:, 0, :], C=acc.C[:, 0, :], e1=acc.e1[:, 0])
