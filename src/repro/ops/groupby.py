"""Unified reproducible GROUPBY: one entry point for the aggregate family.

``groupby_agg`` is the relational operator the paper builds toward: given a
value matrix and a key column, it computes any mix of SUM / COUNT / MEAN /
VAR / STD / SUM(x*y) / MIN / MAX in **one** fused pass, bit-identically
across execution methods, row orderings, chunk sizes and device shardings.

Since the partial/merge/finalize refactor (DESIGN.md §14) this module is a
thin composition over :mod:`repro.ops.partial`:

    groupby_agg(rows) == finalize(partial_agg(rows))

The one-shot path simply never calls ``merge`` — but because
``merge(partial(A), partial(B)) == partial(A ++ B)`` bit for bit, the same
three stages power the sharded operator (per-shard partials + collective
merge) and the streaming engine (:mod:`repro.stream`: a persistent state
plus one merge per micro-batch), all provably equal to this function.

How the family reduces to the paper's SUM (DESIGN.md §10): the requested
aggregates compile to a deduplicated list of *accumulator columns* — raw
columns, elementwise squares/products, and a ones column — which aggregate
as a stacked matrix into one accumulator table ``(G, ncols, L)``.  Every
derived aggregate (MEAN, VAR, STD) is then a fixed elementwise function of
the finalized sums; since the sums are bit-reproducible and the finalizer is
a pure function, the derived results are too.  MIN/MAX need no accumulator
at all: float min/max is associative, so ``segment_min``/``segment_max``
are exact and order-independent as-is.
"""
from __future__ import annotations

import numpy as np

from repro.core.types import ReproSpec
from repro.obs import trace as obs_trace
from repro.ops.partial import AGG_KINDS, agg_name, finalize, partial_agg

__all__ = ["groupby_agg", "agg_name", "AGG_KINDS"]


def groupby_agg(values, keys, num_segments: int, aggs=("sum",),
                spec: ReproSpec | None = None, method: str = "auto",
                chunk: int | None = None, return_table: bool = False,
                levels="auto", check_finite: bool = False):
    """Bit-reproducible multi-aggregate GROUPBY.

    Args:
      values:       float (n,) single column or (n, C) column matrix.
      keys:         int32 (n,) in [0, num_segments) — the GROUP BY column.
      num_segments: static group count G.
      aggs:         aggregate requests: 'sum' | 'count' | 'mean' | 'var' |
                    'std' | 'min' | 'max' (column 0), or tuples
                    ('kind', col) / ('sum_prod', i, j).  'avg' aliases
                    'mean'.
      spec:         accumulator format; default ``ReproSpec()`` (f32, L=2).
      method:       'auto' (cost-model planner) or an explicit strategy:
                    'onehot' | 'scatter' | 'sort' | 'radix' | 'pallas' |
                    'rsum' (flat kernel; G == 1 only).
      chunk:        summation-buffer size knob (clamped to safe bounds).
      return_table: also return the raw accumulator table ``ReproAcc
                    (G, ncols, L)`` (for exact cross-fragment merging).
      levels:       lattice-level window.  ``"auto"`` (default) runs the
                    exponent prescan when the inputs are concrete — the
                    batch-adaptive two-pass mode (DESIGN.md §11): pass 1
                    streams the rows once for magnitude statistics, the host
                    derives the live window ``L_eff <= spec.L`` and whether
                    per-chunk pruning can pay, pass 2 runs the specialized
                    extraction.  Under jit (tracers) it degrades to the full
                    window.  ``None`` forces full; an explicit ``(lo, hi)``
                    tuple is used as given (caller-proved, e.g. from a
                    global prescan over shards).
      check_finite: opt-in §13.6 contract check — raise
                    ``FloatingPointError`` on ±inf/NaN inputs and on
                    derived columns (squares/products) that overflow to
                    non-finite values, instead of silently leaving the
                    reproducibility contract.  Needs concrete inputs.

    The call is one ``groupby.query`` span, the root of the query's
    ``groupby.*`` spans.

    Returns an ordered dict mapping canonical names (see :func:`agg_name`)
    to finalized (G,) arrays; with ``return_table=True``, a
    ``(results, table)`` pair.  Every output is bit-identical across
    methods, row orderings, chunk sizes, level windows and shardings.
    """
    with obs_trace.span("groupby.query", G=int(num_segments),
                        method=method) as sp:
        state = partial_agg(values, keys, num_segments, aggs=aggs, spec=spec,
                            method=method, chunk=chunk, levels=levels,
                            check_finite=check_finite)
        if obs_trace.enabled():
            sp.set(n=int(np.shape(values)[0]), ncols=state.sig.ncols)
        out = finalize(state)
    if return_table:
        return out, state.table
    return out
