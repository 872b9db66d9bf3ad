"""Fused multi-column reproducible segment aggregation (DESIGN.md §3.2/§10/§11).

The paper's GROUPBY-SUM generalizes to the full SQL aggregate family once the
value column is replaced by a *stacked column matrix*: COUNT is a SUM over a
ones column, MEAN is SUM/COUNT, VAR/STD are algebraic functions of
(SUM(x), SUM(x*x), COUNT), and SUM(x*y) is a SUM over an elementwise product
column.  All of these reduce to one fused segment reduction of a matrix
``X (n, ncols)`` into an accumulator *table* ``(G, ncols, L)`` — one
extraction pass over the rows, one kernel invocation, every derived aggregate
a pure (hence reproducible) function of the finalized table.

This module owns the jnp execution strategies, generalized three ways:

* arbitrary feature shape ``F`` — ``values (n, *F)`` aggregates to
  ``(G, *F, L)``; the fused GROUPBY engine uses ``F = (ncols,)``;
* per-column lattice exponents — ``e1`` may be any shape broadcastable to
  ``F`` so each column gets the tightest lattice its magnitude admits;
* a static **level window** ``levels = (lo, hi)`` — extraction touches only
  the lattice levels the data can reach (proved by the prescan statistics of
  :mod:`repro.core.prescan`); the pruned table embeds back into the
  canonical full-L layout with exact zeros, so pruned and unpruned paths are
  bit-identical (DESIGN.md §11).  The scatter scan can additionally skip
  *per-chunk* dead top levels (``chunk_skip``), driven by the vectorized
  prescan over the chunked rows.

Strategies: ``scatter`` (§IV drop-in), ``radix`` (§V-B PartitionAndAggregate
— counting-sort partition on the low group-id bits into cache-resident
sub-tables; ``sort`` is its compatibility alias, the argsort partition it
replaced cost O(n log n) comparator passes where counting sort costs two
streaming passes), and ``onehot`` (MXU summation buffer).  Method selection
lives one layer up, in :mod:`repro.ops.plan`; the Pallas fast path lives in
:mod:`repro.kernels.segment_rsum`.  All paths return bit-identical tables
for any ordering, chunking, bucketing or sharding of the rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import eft
from repro.core import accumulator as acc_mod
from repro.core import prescan
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec

__all__ = [
    "pad_and_chunk", "segment_table", "scatter_table", "sort_table",
    "radix_table", "onehot_table", "onehot_block_bound",
    "scatter_chunk_bound", "default_chunk", "table_bytes", "radix_buckets",
    "DEFAULT_CACHE_BYTES",
]

# The paper's summation-buffer budget (§V-A): the cache the per-group tables
# should stay resident in.  2^24 matches a typical L2+L3 share per core; the
# measured autotuner (repro/ops/calibrate.py) makes the *dispatch* robust to
# this being wrong, and the radix bucket count only needs it to order of
# magnitude.
DEFAULT_CACHE_BYTES = 1 << 24

_MAX_RADIX_BUCKETS = 64


def onehot_block_bound(spec: ReproSpec) -> int:
    """Largest one-hot matmul block with exact float accumulation.

    block * 2^(W-1) ulp must stay exactly representable: block <= 2^(m-W+2).
    (f32/W=18: 128 rows; f32/W=12: 8192 rows — W trades accuracy for tile
    size, the TPU analogue of the paper's bsz/cache trade-off.)
    """
    return 1 << (spec.m - spec.W + 2)


def scatter_chunk_bound(spec: ReproSpec) -> int:
    """Largest scatter chunk whose per-group int sums cannot overflow.

    chunk * 2^(W-1) < 2^(bits-1): int32/W=18 -> 2^13; we halve for margin.
    """
    bits = 31 if spec.m <= 30 else 63
    return 1 << (bits - spec.W)


def default_chunk(method: str, spec: ReproSpec) -> int:
    """Per-method safe default for the summation-buffer size knob."""
    if method == "rsum":
        from repro.kernels.rsum.ops import max_block_rows
        return max_block_rows(spec)
    if method == "pallas":
        from repro.kernels.segment_rsum.ops import max_step_rows
        return max_step_rows(spec)
    if method == "onehot":
        return onehot_block_bound(spec)
    return min(scatter_chunk_bound(spec), 4096)


def table_bytes(num_segments: int, ncols: int, spec: ReproSpec,
                levels: tuple[int, int] | None = None) -> int:
    """Bytes of the (G+1, ncols, L_eff) x {k, C} accumulator table — the
    summation buffer the paper's residency model budgets against."""
    nlev = prescan.window_length(levels, spec)
    item = np.dtype(spec.int_dtype).itemsize
    return (num_segments + 1) * max(int(ncols), 1) * nlev * 2 * item


def radix_buckets(num_segments: int, ncols: int, spec: ReproSpec,
                  cache_bytes: int = DEFAULT_CACHE_BYTES,
                  levels: tuple[int, int] | None = None) -> int:
    """Partition fan-out (a power of two) making each radix sub-table
    cache-resident: the smallest B with table_bytes / B <= cache_bytes."""
    tb = table_bytes(num_segments, ncols, spec, levels)
    b = 1
    while tb > b * cache_bytes and b < _MAX_RADIX_BUCKETS:
        b *= 2
    return b


def pad_and_chunk(values, chunk: int, segment_ids=None, dump_id=None):
    """Pad rows to a multiple of ``chunk`` and reshape to (nblk, chunk, *F).

    The one shared pad/chunk helper (DESIGN.md §10): padding rows are zeros,
    and — when ``segment_ids`` is given — carry ``dump_id`` so each caller
    routes them to its own dump row (``num_segments`` for the jnp strategies,
    ``-1`` for the Pallas kernel whose one-hot matches no group tile).

    Returns ``values`` chunked, or ``(values, segment_ids)`` chunked when ids
    are provided.
    """
    if segment_ids is not None and dump_id is None:
        raise ValueError("pad_and_chunk needs a dump_id to pad segment_ids "
                         "with (the caller's dump row / sentinel)")
    n = values.shape[0]
    feat = values.shape[1:]
    pad = (-n) % chunk
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad, *feat), values.dtype)])
        if segment_ids is not None:
            segment_ids = jnp.concatenate(
                [segment_ids, jnp.full(pad, dump_id, segment_ids.dtype)])
    values = values.reshape(-1, chunk, *feat)
    if segment_ids is None:
        return values
    return values, segment_ids.reshape(-1, chunk)


def _feat_e1(e1, feat):
    """Broadcast a (possibly scalar) e1 to the feature shape as int32."""
    return jnp.broadcast_to(jnp.asarray(e1, jnp.int32), feat)


def _skip_branches(e1_f, spec: ReproSpec, lo: int, hi: int):
    """lax.switch branches for per-chunk dead-top-level extraction.

    Branch i extracts levels [lo+i, hi) and zero-fills the i pruned leading
    levels; branch hi-lo returns all zeros (an all-padding / all-dead chunk
    skips extraction entirely).  Sound because the switch index comes from
    :func:`prescan.top_skip` of the chunk's own max exponent.
    """
    nlev = hi - lo

    def branch(i):
        def f(v_c):
            if i == nlev:
                return jnp.zeros((*v_c.shape, nlev), spec.int_dtype)
            k = acc_mod.extract(v_c, e1_f, spec, levels=(lo + i, hi))
            if i:
                k = jnp.pad(k, [(0, 0)] * (k.ndim - 1) + [(i, 0)])
            return k
        return f

    return [branch(i) for i in range(nlev + 1)]


def scatter_table(values, segment_ids, num_segments, spec: ReproSpec, e1,
                  chunk: int, levels: tuple[int, int] | None = None,
                  chunk_skip: bool = False):
    """Chunked integer scatter-add with renormalization between chunks
    (the drop-in strategy of paper §IV).

    ``levels`` statically restricts extraction to a prescan-proved window;
    ``chunk_skip`` additionally prescans each chunk's max exponent and
    dispatches (lax.switch) to an extraction variant that skips that chunk's
    provably-dead top levels.  Both return the pruned-width table — the
    caller embeds it into full L — and both are bit-identical to the
    unpruned path (the skipped entries are exact zeros).
    """
    lo, hi = prescan.check_levels(levels, spec)
    nlev = hi - lo
    vs, ids = pad_and_chunk(values, chunk, segment_ids, dump_id=num_segments)
    nseg = num_segments + 1  # last row collects padding, sliced off below
    idt = spec.int_dtype
    feat = values.shape[1:]
    e1_f = _feat_e1(e1, feat)

    use_skip = chunk_skip and nlev > 1
    if use_skip:
        stats = prescan.chunk_stats(vs, spec)              # (nblk, *F)
        skips = prescan.top_skip(e1_f, stats.max_exp, spec)
        skip_c = jnp.clip(
            jnp.min(skips.reshape(skips.shape[0], -1), axis=1) - lo,
            0, nlev).astype(jnp.int32)                     # (nblk,)
        branches = _skip_branches(e1_f, spec, lo, hi)

    def step(carry, inp):
        k_tab, c_tab = carry
        if use_skip:
            v_c, id_c, s_c = inp
            k = lax.switch(s_c, branches, v_c)             # (chunk, *F, nlev)
        else:
            v_c, id_c = inp
            k = acc_mod.extract(v_c, e1_f, spec, levels=(lo, hi))
        part = jax.ops.segment_sum(k, id_c, num_segments=nseg)  # exact ints
        k_tab, c_tab = acc_mod.renorm(k_tab + part, c_tab, spec)
        return (k_tab, c_tab), None

    k0 = jnp.zeros((nseg, *feat, nlev), idt)
    xs = (vs, ids, skip_c) if use_skip else (vs, ids)
    (k_tab, c_tab), _ = lax.scan(step, (k0, k0), xs)
    return k_tab[:num_segments], c_tab[:num_segments]


def _partition_dest(bucket, num_buckets: int, block: int = 8192):
    """Counting-sort destinations: a stable partition permutation by bucket.

    Two streaming passes, as in the paper's radix partition: (1) bucket
    histogram (exact integer scatter); (2) running per-bucket ranks, chunked
    so the working set is (block, B) ints.  Zero padding is harmless — pad
    rows trail every real row, so real ranks never see them, and their
    destinations are sliced off.
    """
    n = bucket.shape[0]
    counts = jax.ops.segment_sum(jnp.ones_like(bucket), bucket,
                                 num_segments=num_buckets)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)  # exclusive
    classes = jnp.arange(num_buckets, dtype=jnp.int32)
    bc = pad_and_chunk(bucket, block)                      # (nblk, block)

    def step(tot, b_c):
        oh = (b_c[:, None] == classes[None, :]).astype(jnp.int32)
        before = tot[None, :] + jnp.cumsum(oh, axis=0) - oh
        rank = jnp.take_along_axis(before, b_c[:, None], axis=1)[:, 0]
        # dtype pinned: under enable_x64 an int32 sum would promote to int64
        # and break the scan-carry contract
        return tot + oh.sum(axis=0, dtype=jnp.int32), rank

    _, ranks = lax.scan(step, jnp.zeros(num_buckets, jnp.int32), bc)
    return starts[bucket] + ranks.reshape(-1)[:n]


def _bucket_remap(num_segments: int, num_buckets: int) -> np.ndarray:
    """Static gather undoing the radix relabeling g -> (g & (B-1)) * Gsub +
    (g >> log2 B): full_table[g] = sub_tables[remap[g]]."""
    bits = num_buckets.bit_length() - 1
    gsub = -(-num_segments // num_buckets)
    g = np.arange(num_segments)
    return ((g & (num_buckets - 1)) * gsub + (g >> bits)).astype(np.int32)


def radix_table(values, segment_ids, num_segments, spec: ReproSpec, e1,
                chunk: int, levels: tuple[int, int] | None = None,
                chunk_skip: bool = False, num_buckets: int | None = None):
    """PartitionAndAggregate (paper §V-B): counting-sort partition on the
    low group-id bits, then the same chunked integer scatter per bucket.

    Groups are relabeled ``g -> (g & (B-1)) * ceil(G/B) + (g >> log2 B)`` so
    each bucket's rows — contiguous after the partition — aggregate into a
    contiguous, cache-resident sub-table of ceil(G/B) groups.  Aggregation
    is integer and order-blind, and the relabeling is a pure permutation of
    table rows, so the result is bit-identical to ``scatter_table`` on the
    original ids.  ``B == 1`` (table already resident) degenerates to plain
    scatter with zero partitioning cost.
    """
    feat = values.shape[1:]
    ncols = int(np.prod(feat)) if feat else 1
    if num_buckets is None:
        num_buckets = radix_buckets(num_segments, ncols, spec, levels=levels)
    nb = max(1, int(num_buckets))
    nb = 1 << (nb - 1).bit_length()                        # ceil to pow2
    if nb <= 1:
        return scatter_table(values, segment_ids, num_segments, spec, e1,
                             chunk, levels=levels, chunk_skip=chunk_skip)
    bits = nb.bit_length() - 1
    gsub = -(-num_segments // nb)
    bucket = segment_ids & (nb - 1)
    tkey = bucket * gsub + (segment_ids >> bits)
    dest = _partition_dest(bucket, nb)
    vperm = jnp.zeros_like(values).at[dest].set(values)
    kperm = jnp.zeros_like(tkey).at[dest].set(tkey)
    k, C = scatter_table(vperm, kperm, nb * gsub, spec, e1, chunk,
                         levels=levels, chunk_skip=chunk_skip)
    remap = jnp.asarray(_bucket_remap(num_segments, nb))
    return jnp.take(k, remap, axis=0), jnp.take(C, remap, axis=0)


def sort_table(values, segment_ids, num_segments, spec: ReproSpec, e1,
               chunk: int, levels: tuple[int, int] | None = None,
               chunk_skip: bool = False, num_buckets: int | None = None):
    """Partition first, then aggregate (paper §V-B).  Compatibility alias of
    :func:`radix_table` — the full ``argsort`` this strategy used as its
    partitioning pass is replaced by the counting-sort radix partition;
    aggregation bits are identical by design."""
    return radix_table(values, segment_ids, num_segments, spec, e1, chunk,
                       levels=levels, chunk_skip=chunk_skip,
                       num_buckets=num_buckets)


def onehot_table(values, segment_ids, num_segments, spec: ReproSpec, e1,
                 block: int, levels: tuple[int, int] | None = None,
                 chunk_skip: bool = False):
    """Per-level one-hot matmul accumulation — exact in float within a block
    (the MXU summation buffer), integer renorm between blocks.  ``levels``
    prunes the extractor ladder to the prescan-proved window; the dense
    accumulation makes per-chunk switching pointless (``chunk_skip`` is
    accepted for signature parity and ignored)."""
    del chunk_skip
    lo, hi = prescan.check_levels(levels, spec)
    nlev = hi - lo
    block = min(block, onehot_block_bound(spec))
    vs, ids = pad_and_chunk(values, block, segment_ids, dump_id=num_segments)
    nseg = num_segments + 1
    idt = spec.int_dtype
    feat = values.shape[1:]
    e1_f = _feat_e1(e1, feat)
    lvl = jnp.arange(lo, hi, dtype=jnp.int32)
    es = e1_f - lvl.reshape(nlev, *([1] * len(feat))) * spec.W  # (nlev, *F)
    inv_ulp = eft.pow2(spec.m - es, spec.dtype)                 # (nlev, *F)

    def step(carry, inp):
        k_tab, c_tab = carry
        v_c, id_c = inp
        r = v_c.astype(spec.dtype)
        onehot = jax.nn.one_hot(id_c, nseg, dtype=spec.dtype)  # (block, nseg)
        parts = []
        for l in range(nlev):
            A = eft.extractor(es[l], spec.dtype)             # (*F,)
            q, r = eft.eft_fixed(A, r)
            # exact: per-group |sum q| <= block * 2^(W-1) ulp <= 2^(m+1) ulp
            # HIGHEST: the MXU's default f32 precision rounds q to bf16
            s = jnp.einsum("n...,ng->g...", q, onehot,
                           precision=lax.Precision.HIGHEST)  # (nseg, *F)
            parts.append((s * inv_ulp[l]).astype(idt))
        part = jnp.stack(parts, axis=-1)                     # (nseg, *F, nlev)
        k_tab, c_tab = acc_mod.renorm(k_tab + part, c_tab, spec)
        return (k_tab, c_tab), None

    k0 = jnp.zeros((nseg, *feat, nlev), idt)
    (k_tab, c_tab), _ = lax.scan(step, (k0, k0), (vs, ids))
    return k_tab[:num_segments], c_tab[:num_segments]


_STRATEGIES = {
    "scatter": scatter_table,
    "sort": sort_table,
    "radix": radix_table,
    "onehot": onehot_table,
}


def segment_table(values, segment_ids, num_segments: int, spec: ReproSpec,
                  method: str, e1=None, chunk: int | None = None,
                  levels: tuple[int, int] | None = None,
                  chunk_skip: bool = False,
                  num_buckets: int | None = None) -> ReproAcc:
    """Fused reproducible segment reduction: ``(n, *F) -> ReproAcc (G, *F, L)``.

    ``method`` must be an executable strategy name ('scatter' | 'sort' |
    'radix' | 'onehot' | 'pallas' | 'rsum') — ``'auto'`` resolution belongs
    to :func:`repro.ops.plan.plan_groupby`.  'rsum' is the flat-aggregation
    kernel and requires ``num_segments == 1``.  ``e1`` may be scalar or any shape
    broadcastable to ``F`` (per-column lattices); defaults to the per-feature
    row maximum, which every execution path shares so their tables are
    bit-identical.  ``levels`` is a static prescan-proved live-level window
    (see :mod:`repro.core.prescan`); the returned table is always full-L,
    with exact zeros on pruned levels — bit-identical to the unpruned run.
    """
    values = jnp.asarray(values)
    segment_ids = jnp.asarray(segment_ids, jnp.int32)
    if segment_ids.ndim != 1 or values.shape[0] != segment_ids.shape[0]:
        raise ValueError("segment_table expects values (n, *F) and ids (n,)")
    values = values.astype(spec.dtype)
    feat = values.shape[1:]
    if e1 is None:
        e1 = acc_mod.required_e1(values, spec, axis=0)       # (*F,)
    if method == "rsum":
        from repro.kernels.rsum.ops import rsum_table
        flat = values.reshape(values.shape[0], -1)           # (n, prod(F))
        acc = rsum_table(flat, segment_ids, num_segments, spec,
                         e1=_feat_e1(e1, feat).reshape(-1),
                         block_rows=chunk, levels=levels)
        return ReproAcc(k=acc.k.reshape(num_segments, *feat, spec.L),
                        C=acc.C.reshape(num_segments, *feat, spec.L),
                        e1=acc.e1.reshape(num_segments, *feat))
    if method == "pallas":
        from repro.kernels.segment_rsum.ops import segment_agg_kernel
        flat = values.reshape(values.shape[0], -1)           # (n, prod(F))
        acc = segment_agg_kernel(flat, segment_ids, num_segments, spec,
                                 e1=_feat_e1(e1, feat).reshape(-1),
                                 block_n=chunk, levels=levels)
        return ReproAcc(k=acc.k.reshape(num_segments, *feat, spec.L),
                        C=acc.C.reshape(num_segments, *feat, spec.L),
                        e1=acc.e1.reshape(num_segments, *feat))
    if method not in _STRATEGIES:
        raise ValueError(f"unknown method {method!r}")
    if chunk is None:
        chunk = default_chunk(method, spec)
    kwargs = {"levels": levels, "chunk_skip": chunk_skip}
    if method in ("sort", "radix"):
        # the planner's fan-out decision (GroupbyPlan.buckets) rides along
        # so what executes is what the plan advertised
        kwargs["num_buckets"] = num_buckets
    k, C = _STRATEGIES[method](values, segment_ids, num_segments, spec, e1,
                               chunk, **kwargs)
    k = acc_mod.pad_levels(k, levels, spec)
    C = acc_mod.pad_levels(C, levels, spec)
    e1_b = jnp.broadcast_to(_feat_e1(e1, feat), (num_segments, *feat))
    return ReproAcc(k=k, C=C, e1=e1_b)
