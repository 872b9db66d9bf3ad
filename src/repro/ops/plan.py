"""Cost-model planner for reproducible GROUPBY (DESIGN.md §10/§11).

Every execution path — jnp onehot / scatter / radix (a.k.a. sort), the
Pallas MXU segment kernel, and the Pallas VPU flat kernel (``rsum``, valid
only at G == 1) — returns bit-identical accumulator tables, so method
choice is *purely* a performance decision.  This module makes that decision
explicit and auditable: :func:`plan_groupby` returns the strategy, the
summation-buffer size (``chunk``), the radix fan-out (``buckets``) and one
line of rationale.

Two cost sources, in priority order:

* **measured** — when a calibration cache exists (see
  :mod:`repro.ops.calibrate`), per-row costs are interpolated from actual
  microbenchmarks of each strategy on this machine;
* **modeled** — cold-start abstract per-row costs, derived from the same
  machine model the paper uses (summation-buffer residency, partitioning
  passes, SIMD width):

  * every path pays extraction: one error-free transformation + an integer
    conversion per *live* level (``_EXTRACT_COST``; the prescan's level
    window shrinks this);
  * ``onehot`` adds a dense (block x G) accumulation: G multiply-adds per
    row per level, spread over ``_LANES`` vector lanes;
  * ``pallas`` is the same matmul on the MXU systolic array
    (``_LANES * _MXU_DEPTH`` MACs/cycle) — TPU backend + f32 accumulators;
  * ``scatter`` pays a random access per level; the penalty quadruples once
    the (G+1, ncols, L_eff) int table spills the summation-buffer budget;
  * ``sort``/``radix`` pay the counting-sort partition (two streaming
    passes + a B-lane rank scan) to make every sub-table cache-resident,
    keeping the scatter penalty at its in-cache value for any group count.

``chunk`` is picked by the paper's buffer-residency model (§V-C): the
largest block whose extracted integers fit in the cache budget *beside* the
(sub-)table, clamped to the overflow-safety bound.

``block_rows`` bounds the working set of the sharded operator's shard
program (:mod:`repro.ops.sharded`), which aggregates each shard's rows one
row block at a time: the rows whose transient arrays fit a chip's
``BLOCK_BUDGET_BYTES`` (:func:`block_rows`).
"""
from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

from repro.core.aggregates import (  # noqa: F401  (re-exports)
    DEFAULT_CACHE_BYTES, default_chunk, onehot_block_bound, pad_and_chunk,
    radix_buckets, scatter_chunk_bound, table_bytes)
from repro.core.prescan import window_length
from repro.core.types import ReproSpec
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = [
    "GroupbyPlan", "PartialPlan", "plan_groupby", "plan_partial",
    "pick_chunk", "block_rows", "BLOCK_BUDGET_BYTES", "default_chunk",
    "onehot_block_bound", "scatter_chunk_bound", "pad_and_chunk",
    "table_bytes", "radix_buckets", "METHODS",
]

METHODS = ("onehot", "scatter", "sort", "radix", "pallas", "rsum")

_LANES = 128          # TPU VPU lane width
_CPU_LANES = 8        # effective XLA:CPU one-hot throughput (measured:
                      # BENCH_groupby.json puts the onehot/scatter crossover
                      # near G~10^2 on CPU vs ~4096 on 128-lane hardware)
_MXU_DEPTH = 64       # extra MAC throughput of the 128x128 systolic array
_EXTRACT_COST = 4.0   # EFT + scale-to-int, per row per level
_SCATTER_COST = 32.0  # random table access, per row per level, in cache
_SPILL_FACTOR = 4.0   # penalty multiplier once the table leaves the cache
_PARTITION_COST = 8.0  # counting-sort partition: 2 streaming passes per row
_MERGE_COST = 6.0      # state merge, per table element: demote gather +
                       # where + int add + renorm shift/mask
_CACHE_BYTES = DEFAULT_CACHE_BYTES
#: a chip's budget for the transient arrays of one row block of the shard
#: program: 1 GiB, a sixteenth of a TPU v5e chip's 16 GB
BLOCK_BUDGET_BYTES = 1 << 30


def _clamp_chunk(method: str, chunk: int, spec: ReproSpec) -> int:
    if method == "rsum":
        from repro.kernels.rsum.ops import max_block_rows
        return min(chunk, max_block_rows(spec))
    if method == "pallas":
        from repro.kernels.segment_rsum.ops import max_step_rows
        return min(chunk, max_step_rows(spec))
    if method == "onehot":
        return min(chunk, onehot_block_bound(spec))
    return min(chunk, scatter_chunk_bound(spec))


def pick_chunk(method: str, num_segments: int, ncols: int, spec: ReproSpec,
               levels=None, cache_bytes: int = _CACHE_BYTES) -> int:
    """Buffer-residency chunk choice (paper §V-C, replacing the fixed
    ``default_chunk``): the largest power-of-two block whose extracted
    integer slab (chunk x ncols x L_eff x itemsize) plus the float rows fit
    in the cache budget beside the (sub-)table, clamped to the per-method
    exactness/overflow bound.  When even the table spills, the block reverts
    to the safe default — blocking cannot buy residency back."""
    if method == "rsum":
        # flat kernel: chunk is its block_rows, bounded by int32 overflow
        # and the VMEM footprint of the (ncols, rows, 128) block + the
        # live-level scratch (see kernels.rsum.ops.max_block_rows)
        from repro.kernels.rsum.ops import max_block_rows
        return max_block_rows(spec, ncols, levels)
    if method == "pallas":
        # the segment kernel's rows per grid step (each step sums 128-row
        # sub-blocks exactly; see kernels.segment_rsum.ops.max_step_rows)
        from repro.kernels.segment_rsum.ops import max_step_rows
        return max_step_rows(spec)
    if method == "onehot":
        return onehot_block_bound(spec)
    bound = scatter_chunk_bound(spec)
    tb = table_bytes(num_segments, ncols, spec, levels)
    if method in ("sort", "radix"):
        tb //= radix_buckets(num_segments, ncols, spec, cache_bytes, levels)
    nlev = window_length(levels, spec)
    row_bytes = max(int(ncols), 1) * (
        nlev * np.dtype(spec.int_dtype).itemsize
        + np.dtype(spec.dtype).itemsize)
    free = cache_bytes - tb
    if free < 256 * row_bytes:
        # table spilled anyway: maximize the block to amortize the per-chunk
        # renormalization sweep over the table (the dominant cost out there)
        return bound
    return int(min(bound, 1 << (int(free // row_bytes).bit_length() - 1)))


def block_rows(n: int, ncols: int, spec: ReproSpec, chunk: int, levels=None,
               budget: int = BLOCK_BUDGET_BYTES) -> int:
    """Rows of one row block of an ``n``-row shard: the fewest blocks whose
    transient arrays fit ``budget``, made equal, and rounded up to whole
    ``chunk`` blocks of the strategy where a chunk fits the budget.

    A block row costs its stacked accumulator columns twice (as built and
    as the strategy lays them out), its extracted integers (one per live
    level and column) and its key twice.  Any block size gives the same
    bits: block tables add exactly (:mod:`repro.ops.sharded`).
    """
    n = max(int(n), 1)
    ncols = max(int(ncols), 1)
    row = ncols * (2 * np.dtype(spec.dtype).itemsize + window_length(
        levels, spec) * np.dtype(spec.int_dtype).itemsize) + 2 * 4
    cap = max(1, int(budget) // row)
    rows = -(-n // -(-n // cap))
    if cap >= chunk:
        rows = -(-rows // chunk) * chunk
    return min(rows, n)


def _emit_plan(plan: "GroupbyPlan", n: int, num_segments: int, ncols: int,
               backend: str, levels) -> "GroupbyPlan":
    """Plan-decision observability: one event + one counter per decision.

    The event carries everything needed to audit the decision after the
    fact — strategy, buffer sizes, cost source (measured vs modeled vs
    explicit) and the one-line rationale (DESIGN.md §13.4).  No-op unless
    tracing/metrics are enabled.
    """
    obs_metrics.counter("repro_plan_total", method=plan.method,
                        source=plan.source).inc()
    obs_trace.event("plan.groupby", method=plan.method, chunk=plan.chunk,
                    buckets=plan.buckets, block_rows=plan.block_rows,
                    source=plan.source,
                    cost_per_row=plan.cost, n=int(n), G=int(num_segments),
                    ncols=int(ncols), backend=backend,
                    levels=list(levels) if levels is not None else None,
                    reason=plan.reason)
    return plan


@dataclasses.dataclass(frozen=True)
class GroupbyPlan:
    """An executable dispatch decision: strategy + buffer sizes + rationale."""

    method: str          # 'onehot'|'scatter'|'sort'|'radix'|'pallas'|'rsum'
    chunk: int           # rows per block between renormalizations
    cost: float          # per-row cost (0.0 for explicit requests)
    reason: str          # one line of cost-model rationale
    buckets: int = 1     # radix partition fan-out (1 = no partitioning)
    source: str = "model"  # 'model' | 'measured' | 'explicit'
    block_rows: int = 0  # rows a row block of the shard program holds


def plan_groupby(n: int, num_segments: int, spec: ReproSpec, ncols: int = 1,
                 backend: str | None = None, method: str = "auto",
                 chunk: int | None = None, levels=None,
                 calibration="auto") -> GroupbyPlan:
    """Choose an execution strategy for an (n rows, G groups, ncols columns)
    reproducible GROUPBY.  Deterministic in its arguments (plus, when a
    calibration cache is present, in that cache); any choice is
    bit-compatible with any other, so this is purely a throughput decision.

    ``levels`` is the prescan's live-level window (shrinks extraction and
    table-residency costs); ``calibration`` is ``"auto"`` (use the cache if
    one exists), ``None`` (force the cold-start model), or a
    :class:`repro.ops.calibrate.Calibration`.
    """
    if backend is None:
        backend = jax.default_backend()
    buckets = radix_buckets(num_segments, ncols, spec, levels=levels)
    if method != "auto":
        if method not in METHODS:
            raise ValueError(f"unknown method {method!r}; want one of "
                             f"{('auto',) + METHODS}")
        if method == "rsum" and num_segments != 1:
            raise ValueError("method 'rsum' is the flat-aggregation kernel: "
                             f"it requires num_segments == 1, got "
                             f"{num_segments}")
        c = _clamp_chunk(
            method, chunk or pick_chunk(method, num_segments, ncols, spec,
                                        levels), spec)
        return _emit_plan(
            GroupbyPlan(method, c, 0.0, "explicit request",
                        buckets=buckets if method in ("sort", "radix")
                        else 1, source="explicit",
                        block_rows=block_rows(n, ncols, spec, c, levels,
                                              BLOCK_BUDGET_BYTES)),
            n, num_segments, ncols, backend, levels)

    cal = None
    if calibration is not None:
        from repro.ops import calibrate as cal_mod
        cal = (cal_mod.for_planner(spec, backend)
               if calibration == "auto" else calibration)

    candidates = ["onehot", "scatter", "sort"]
    if backend == "tpu" and spec.m <= 30:
        candidates.append("pallas")
    if num_segments == 1 and spec.m <= 30:
        # the flat-sum kernel: only valid with a single group (SQL SUM
        # without GROUP BY, gradient-norm reductions)
        candidates.append("rsum")

    costs, source = None, "model"
    if cal is not None:
        from repro.ops import calibrate as cal_mod
        # fitted_cost returns None outside a method's measured-G envelope
        # (e.g. onehot is never measured at large G), dropping it from the
        # measured race rather than trusting a flat extrapolation
        costs = {m: cal_mod.fitted_cost(cal, m, n, num_segments, ncols, spec,
                                        backend=backend)
                 for m in candidates}
        costs = {m: c for m, c in costs.items() if c is not None}
        if len(costs) >= 2:
            source = "measured"
        else:
            costs = None
    if costs is None:
        nlev = window_length(levels, spec)
        extract = _EXTRACT_COST * nlev
        tb = table_bytes(num_segments, ncols, spec, levels)
        in_cache = tb <= _CACHE_BYTES
        lanes = _LANES if backend == "tpu" else _CPU_LANES
        costs = {
            "onehot": extract + nlev * num_segments / lanes,
            "scatter": extract + nlev * _SCATTER_COST *
            (1.0 if in_cache else _SPILL_FACTOR),
            "sort": extract + nlev * _SCATTER_COST +
            (0.0 if buckets == 1
             else _PARTITION_COST + buckets / lanes),
        }
        if "pallas" in candidates:
            costs["pallas"] = extract + \
                nlev * num_segments / (_LANES * _MXU_DEPTH)
        if "rsum" in candidates:
            # per-lane int adds, no one-hot operand to materialize and no
            # table to index: half the G=1 MXU path's per-row work on TPU.
            # Off-TPU the kernel runs in interpret mode — price it out of
            # the cold race (only measurement can bring it back).
            costs["rsum"] = extract + (
                0.5 * nlev / (_LANES * _MXU_DEPTH) if backend == "tpu"
                else 1e3 * nlev)

    best = min(costs, key=costs.get)
    tb = table_bytes(num_segments, ncols, spec, levels)
    reason = (f"{'calibrated' if source == 'measured' else 'cost model'}: "
              f"{best}={costs[best]:.1f}/row over "
              + ", ".join(f"{m}={c:.1f}" for m, c in sorted(costs.items())
                          if m != best)
              + f" (G={num_segments}, n={n}, ncols={ncols}, "
              f"table {'fits' if tb <= _CACHE_BYTES else 'spills'} cache"
              + (f", B={buckets}" if best in ("sort", "radix") else "")
              + f", {backend})")
    c = _clamp_chunk(best, chunk or pick_chunk(best, num_segments, ncols,
                                               spec, levels), spec)
    return _emit_plan(
        GroupbyPlan(best, c, costs[best], reason,
                    buckets=buckets if best in ("sort", "radix") else 1,
                    source=source,
                    block_rows=block_rows(n, ncols, spec, c, levels,
                                          BLOCK_BUDGET_BYTES)),
        n, num_segments, ncols, backend, levels)


# ---------------------------------------------------------------------------
# partial planning: micro-batch strategy + merge amortization
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartialPlan:
    """A dispatch decision for streaming partial aggregation.

    ``agg`` is the per-micro-batch strategy (small batches naturally plan
    onto scatter; the partition/matmul strategies only win once a batch is
    large enough to amortize their setup).  ``merge_rows`` prices one store
    merge — demote + integer add + renorm over the whole ``(G, ncols,
    L_eff)`` table, *independent of the batch size* — in units of
    aggregated rows, and ``coalesce`` is the number of micro-batches worth
    buffering per store merge so the merge overhead stays at or below
    ``merge_frac`` of the aggregation work.  A batch that dwarfs the table
    coalesces to 1 (merge per batch); a trickle of tiny deltas into a huge
    table coalesces aggressively.

    ``pipeline`` is the ingest pipeline width: how many concurrent
    ``prepare`` workers the pipelined stream service should run.  The pure
    per-batch aggregation parallelizes perfectly (DESIGN.md §15); the
    amortized merge (``merge_rows / coalesce`` row-equivalents per batch)
    is the serialized stage, so by Amdahl the useful width is the
    parallel:serial work ratio — more workers than that just queue behind
    the commit lock.  Clamped to the machine's core count; like every
    other knob here it moves throughput only, never bits.
    """

    agg: GroupbyPlan     # per-micro-batch execution plan
    merge_rows: float    # one store merge, in row-equivalents
    coalesce: int        # micro-batches to buffer per store merge
    reason: str          # one line of rationale
    pipeline: int = 1    # concurrent prepare workers worth running


def plan_partial(n: int, num_segments: int, spec: ReproSpec, ncols: int = 1,
                 backend: str | None = None, method: str = "auto",
                 chunk: int | None = None, levels=None, calibration="auto",
                 merge_frac: float = 0.25,
                 max_coalesce: int = 64) -> PartialPlan:
    """Plan streaming partial aggregation for ``n``-row micro-batches into a
    ``(G, ncols)`` store.  Deterministic in its arguments; like
    :func:`plan_groupby` it is purely a throughput decision — any choice is
    bit-compatible with any other (merging is exact regardless of how the
    partials were computed or buffered).
    """
    agg = plan_groupby(n, num_segments, spec, ncols=ncols, backend=backend,
                       method=method, chunk=chunk, levels=levels,
                       calibration=calibration)
    nlev = window_length(levels, spec)
    per_row = agg.cost if agg.cost > 0 else _EXTRACT_COST * nlev
    merge_units = _MERGE_COST * num_segments * max(int(ncols), 1) * nlev
    merge_rows = merge_units / per_row
    n = max(int(n), 1)
    coalesce = max(1, min(max_coalesce,
                          -(-int(merge_rows) // max(int(merge_frac * n), 1))))
    # Amdahl width: parallel prepare work per batch over the amortized
    # serialized merge share.  merge_rows/coalesce row-equivalents of every
    # n-row batch are serial, so width beyond n·coalesce/merge_rows idles.
    cores = os.cpu_count() or 1
    pipeline = int(max(1, min(cores,
                              n * coalesce // max(int(merge_rows), 1))))
    reason = (f"merge ≈ {merge_rows:.0f} row-equivalents vs {n}-row "
              f"batches; coalesce {coalesce} batch(es) holds merge "
              f"overhead ≤ {merge_frac:.0%}; pipeline width {pipeline} "
              f"of {cores} core(s) ({agg.method}/{agg.source})")
    obs_trace.event("plan.partial", method=agg.method, chunk=agg.chunk,
                    merge_rows=merge_rows, coalesce=coalesce, n=n,
                    pipeline=pipeline, G=int(num_segments),
                    ncols=int(ncols), reason=reason)
    obs_metrics.counter("repro_plan_partial_total",
                        method=agg.method).inc()
    return PartialPlan(agg=agg, merge_rows=merge_rows, coalesce=coalesce,
                       reason=reason, pipeline=pipeline)
