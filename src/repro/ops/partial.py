"""The GROUPBY engine as an explicit algebra: partial / merge / finalize.

The paper's central payoff is that the accumulator is *associative*: any
partition of the input into partial aggregates merges to the bit-identical
result.  This module makes that algebra first-class (DESIGN.md §14):

* :func:`partial_agg` — aggregate a batch of rows into a
  :class:`PartialState`: the ``(G, ncols, L)`` accumulator table on the
  batch's own per-column lattice, stacked MIN/MAX columns, and a row count;
* :func:`merge` — combine two states **bitwise-associatively**.  Per-column
  ``e1`` mismatch is resolved by :func:`repro.core.accumulator.demote_to`
  onto the pairwise-max lattice; because states carry full-L tables with
  exact zeros on pruned levels, the live-level windows of the operands
  union for free.  Merging the partials of any row partition, in any order
  or tree shape, equals the one-shot extraction on the union lattice bit
  for bit (the demotion lemma, DESIGN.md §14.2);
* :func:`finalize` — the pure deterministic function from a state to the
  result dict every execution path shares.

``groupby_agg`` is ``finalize(partial_agg(...))``;
``sharded_groupby_agg`` is per-shard partials + collective merge +
finalize; the streaming engine (:mod:`repro.stream`) is a persistent state
plus ``merge`` per micro-batch.  One algebra, every deployment shape.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accumulator as acc_mod
from repro.core import aggregates
from repro.core import prescan
from repro.core.accumulator import ReproAcc
from repro.core.types import FLOAT_SPECS, ReproSpec
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.ops.plan import plan_groupby

__all__ = [
    "AGG_KINDS", "AggSignature", "PartialState", "PartialPipeline",
    "agg_name", "partial_agg", "merge", "merge_all", "merge_all_jit",
    "finalize", "empty_partial", "pipeline_for", "state_nbytes",
]

AGG_KINDS = ("sum", "count", "mean", "var", "std", "min", "max", "sum_prod")


# ---------------------------------------------------------------------------
# aggregate compilation (the engine's front end)
# ---------------------------------------------------------------------------

def _normalize(aggs):
    """Accept 'sum' / ('sum', col) / ('sum_prod', i, j) forms -> tuples."""
    norm = []
    for a in aggs:
        if isinstance(a, str):
            a = (a,) if a in ("count",) else (a, 0)
        a = tuple(a)
        kind = a[0]
        if kind == "avg":
            kind, a = "mean", ("mean", *a[1:])
        if kind == "count":
            a = ("count",)
        elif kind == "sum_prod":
            if len(a) != 3:
                raise ValueError(f"sum_prod takes two columns, got {a!r}")
        elif len(a) != 2:
            raise ValueError(f"aggregate {a!r} takes exactly one column")
        if kind not in AGG_KINDS:
            raise ValueError(f"unknown aggregate {kind!r}; want {AGG_KINDS}")
        norm.append(a)
    return norm


def agg_name(a) -> str:
    """Canonical result key: 'sum(0)', 'count(*)', 'sum_prod(0,1)', ..."""
    a = _normalize([a])[0]
    if a[0] == "count":
        return "count(*)"
    return f"{a[0]}({','.join(str(c) for c in a[1:])})"


def _compile(aggs):
    """Compile aggregates to (names, accumulator columns, finalize plans).

    Columns are deduplicated: ``[("mean", 0), ("var", 0)]`` shares the raw
    column and the ones column, adding only the squares column.
    """
    norm = _normalize(aggs)
    cols, index = [], {}

    def need(c):
        if c not in index:
            index[c] = len(cols)
            cols.append(c)
        return index[c]

    plans = []
    for a in norm:
        kind = a[0]
        if kind == "sum":
            plans.append(("sum", need(("col", a[1]))))
        elif kind == "sum_prod":
            plans.append(("sum", need(("prod", a[1], a[2]))))
        elif kind == "count":
            plans.append(("count", need(("ones",))))
        elif kind == "mean":
            plans.append(("mean", need(("col", a[1])), need(("ones",))))
        elif kind in ("var", "std"):
            plans.append((kind, need(("col", a[1])), need(("sq", a[1])),
                          need(("ones",))))
        else:  # min / max: exact as-is, no accumulator column
            plans.append((kind, a[1]))
    return [agg_name(a) for a in norm], cols, plans


def _as_matrix(values, spec: ReproSpec):
    v = jnp.asarray(values, spec.dtype)
    if v.ndim == 1:
        v = v[:, None]
    if v.ndim != 2:
        raise ValueError(f"groupby_agg expects values (n,) or (n, C), "
                         f"got shape {v.shape}")
    return v


def _build_columns(v, cols, spec: ReproSpec):
    """Materialize the stacked accumulator-column matrix (n, ncols)."""
    parts = []
    for c in cols:
        if c[0] == "col":
            parts.append(v[:, c[1]])
        elif c[0] == "sq":
            parts.append(v[:, c[1]] * v[:, c[1]])
        elif c[0] == "prod":
            parts.append(v[:, c[1]] * v[:, c[2]])
        else:  # ("ones",)
            parts.append(jnp.ones(v.shape[0], spec.dtype))
    if not parts:
        return jnp.zeros((v.shape[0], 0), spec.dtype)
    return jnp.stack(parts, axis=1)


def _minmax_cols(plans):
    return sorted({p[1] for p in plans if p[0] in ("min", "max")})


def _col_name(c) -> str:
    if c[0] == "ones":
        return "ones"
    return f"{c[0]}({','.join(str(i) for i in c[1:])})"


# ---------------------------------------------------------------------------
# the aggregate signature: what makes two states mergeable
# ---------------------------------------------------------------------------

def _canonical_spec(spec: ReproSpec) -> ReproSpec:
    """Normalize the dtype object so signature equality is value equality
    (``np.float32`` vs ``jnp.float32`` construct equal signatures)."""
    canon = FLOAT_SPECS[np.dtype(spec.dtype)].dtype
    if spec.dtype is canon:
        return spec
    return ReproSpec(dtype=canon, L=spec.L, W=spec.W)


@dataclasses.dataclass(frozen=True)
class AggSignature:
    """Static identity of a partial state: two states merge iff their
    signatures are equal (same aggregates, group count and accumulator
    format — hence identical table/min/max shapes and result schema)."""

    aggs: tuple          # normalized aggregate tuples
    num_segments: int
    spec: ReproSpec

    @classmethod
    def build(cls, aggs, num_segments: int,
              spec: ReproSpec | None) -> "AggSignature":
        spec = _canonical_spec(spec or ReproSpec())
        return cls(aggs=tuple(_normalize(aggs)),
                   num_segments=int(num_segments), spec=spec)

    @property
    def compiled(self):
        """(names, accumulator columns, finalize plans) — cached."""
        return _compiled(self)

    @property
    def ncols(self) -> int:
        return len(self.compiled[1])

    @property
    def minmax(self):
        return _minmax_cols(self.compiled[2])

    def to_json(self) -> dict:
        """JSON form for checkpoint manifests (exact roundtrip)."""
        return {"aggs": [list(a) for a in self.aggs],
                "num_segments": self.num_segments,
                "dtype": np.dtype(self.spec.dtype).name,
                "L": self.spec.L, "W": self.spec.W}

    @classmethod
    def from_json(cls, d: dict) -> "AggSignature":
        spec = ReproSpec(dtype=FLOAT_SPECS[np.dtype(d["dtype"])].dtype,
                         L=int(d["L"]), W=int(d["W"]))
        return cls.build([tuple(a) for a in d["aggs"]],
                         d["num_segments"], spec)


@functools.lru_cache(maxsize=256)
def _compiled(sig: AggSignature):
    return _compile(sig.aggs)


# ---------------------------------------------------------------------------
# the partial state (a pytree; the signature rides as static aux data)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartialState:
    """A mergeable partial aggregate over some subset of the rows.

    Leaves: ``table`` — the integer accumulator table ``(G, ncols, L)`` on
    this state's per-column lattice; ``minv``/``maxv`` — stacked exact
    MIN/MAX columns ``(G, nmm)`` with the ±inf reduction identities on
    groups the subset never touched; ``rows`` — int32 row count (exact
    under merge, observability only).  ``sig`` is static aux data.
    """

    table: ReproAcc
    minv: jax.Array
    maxv: jax.Array
    rows: jax.Array
    sig: AggSignature

    @property
    def spec(self) -> ReproSpec:
        return self.sig.spec

    @property
    def num_segments(self) -> int:
        return self.sig.num_segments


jax.tree_util.register_pytree_node(
    PartialState,
    lambda s: ((s.table, s.minv, s.maxv, s.rows), s.sig),
    lambda sig, leaves: PartialState(*leaves, sig=sig),
)


def empty_partial(num_segments: int, aggs=("sum",),
                  spec: ReproSpec | None = None) -> PartialState:
    """The identity of :func:`merge`: an all-zero table at the bottom of
    the lattice, ±inf MIN/MAX identities, zero rows."""
    sig = AggSignature.build(aggs, num_segments, spec)
    spec = sig.spec
    g, nmm = sig.num_segments, len(sig.minmax)
    return PartialState(
        table=acc_mod.zeros(spec, (g, sig.ncols)),
        minv=jnp.full((g, nmm), jnp.inf, spec.dtype),
        maxv=jnp.full((g, nmm), -jnp.inf, spec.dtype),
        rows=jnp.zeros((), jnp.int32),
        sig=sig)


# ---------------------------------------------------------------------------
# non-finite contract (DESIGN.md §13.6): opt-in loud failure
# ---------------------------------------------------------------------------

def _check_finite(v, X, cols):
    """Fail loudly on ±inf/NaN inputs and on derived columns that overflow
    (e.g. ``var`` squaring a finite float32 past float32-max) — instead of
    letting strategies silently diverge outside the finite contract."""
    if not (prescan.is_concrete(v) and prescan.is_concrete(X)):
        raise ValueError(
            "check_finite=True needs concrete (non-traced) inputs: the "
            "check is host-driven, like the levels='auto' prescan")
    vn = np.asarray(v)
    bad = ~np.isfinite(vn)
    if bad.any():
        where = sorted(set(np.nonzero(bad)[1].tolist()))
        raise FloatingPointError(
            f"non-finite input values in column(s) {where}: the "
            "reproducibility contract covers finite inputs only "
            "(DESIGN.md §13.6)")
    Xn = np.asarray(X)
    badx = ~np.isfinite(Xn)
    if badx.any():
        names = [_col_name(cols[j])
                 for j in sorted(set(np.nonzero(badx)[1].tolist()))]
        raise FloatingPointError(
            f"derived accumulator column(s) {names} overflow to non-finite "
            "values from finite inputs (e.g. var squaring past "
            "float32-max); strategies legitimately diverge there "
            "(DESIGN.md §13.6)")


# ---------------------------------------------------------------------------
# stage 1: partial aggregation
# ---------------------------------------------------------------------------

def _resolve_levels(levels, X, e1, spec: ReproSpec):
    """Turn the ``levels`` request into (static window | None, chunk_skip).

    ``"auto"`` + concrete inputs = the prescan pass: one vectorized stream
    over the rows yields per-chunk, per-column exponent stats; the union of
    the live windows becomes the static window, and per-chunk top-skipping
    is enabled only when some chunk can prune *more* than the union (i.e.
    the data is magnitude-heterogeneous) — homogeneous inputs skip the
    per-chunk switch entirely so the hot loop stays branchless.
    """
    if levels is None:
        return None, False
    if levels != "auto":
        return prescan.check_levels(levels, spec), False
    if not (prescan.is_concrete(X) and prescan.is_concrete(e1)):
        return None, False                      # traced: full window
    if X.shape[0] == 0:
        return (0, 1), False                    # empty input: all-zero table
    probe = aggregates.default_chunk("scatter", spec)
    stats = prescan.chunk_stats(
        aggregates.pad_and_chunk(X, probe), spec)            # (nblk, ncols)
    lo_a, hi_a = prescan.level_window(stats, e1[None, :], spec)
    lo = _host_sync("lo", int, jnp.min(lo_a))
    hi = _host_sync("hi", int, jnp.max(hi_a))
    if lo >= hi:
        lo, hi = 0, 1                            # degenerate: all-zero input
    # heterogeneous when some chunk's own window starts above the union's
    # lo, i.e. that chunk can skip more top levels than the static window
    chunk_skip = hi - lo > 1 and _host_sync(
        "chunk_skip", bool,
        jnp.max(jnp.min(lo_a.reshape(lo_a.shape[0], -1), axis=1)) > lo)
    return (lo, hi), chunk_skip


def _host_sync(what: str, convert, x):
    """``convert(x)`` of a dispatched device array: the host waits for the
    device, timed as a ``groupby.host_sync`` span."""
    with obs_trace.span("groupby.host_sync", what=what):
        return convert(x)


def _prescan(X, levels, spec: ReproSpec):
    """Per-column ``required_e1`` and the resolved level window, timed as
    the ``groupby.prescan`` span: (e1, levels, chunk_skip)."""
    with obs_trace.span("groupby.prescan", n=int(X.shape[0]),
                        ncols=X.shape[1]) as sp:
        e1 = acc_mod.required_e1(X, spec, axis=0)            # per-column
        lv, chunk_skip = _resolve_levels(levels, X, e1, spec)
        sp.set(levels=list(lv) if lv is not None else None,
               chunk_skip=bool(chunk_skip), L=spec.L,
               L_eff=prescan.window_length(lv, spec))
    return e1, lv, chunk_skip


def _count_groupby(n, spec: ReproSpec, lv, plan):
    """The engine counters of one batch: rows, calls per method and the
    levels the prescan pruned (DESIGN.md §13.4).  No-op when metrics are
    disabled."""
    obs_metrics.counter("repro_groupby_rows_total").inc(int(n))
    obs_metrics.counter("repro_groupby_calls_total",
                        method=plan.method).inc()
    obs_metrics.counter("repro_groupby_levels_pruned_total").inc(
        spec.L - prescan.window_length(lv, spec))


def _columns(values, keys, cols, spec: ReproSpec, check_finite: bool):
    """The eager front, timed as the ``groupby.columns`` span: the value
    matrix, the int32 key column and the stacked accumulator columns,
    checked for non-finite values where asked: (v, keys, X)."""
    with obs_trace.span("groupby.columns"):
        v = _as_matrix(values, spec)
        keys = jnp.asarray(keys, jnp.int32).reshape(-1)
        if v.shape[0] != keys.shape[0]:
            raise ValueError("values and keys disagree on the row count")
        X = _build_columns(v, cols, spec)
        if check_finite:
            _check_finite(v, X, cols)
    return v, keys, X


def partial_agg(values, keys, num_segments: int, aggs=("sum",),
                spec: ReproSpec | None = None, method: str = "auto",
                chunk: int | None = None, levels="auto",
                check_finite: bool = False) -> PartialState:
    """Aggregate one batch of rows into a mergeable :class:`PartialState`.

    Arguments as in :func:`repro.ops.groupby_agg`; ``check_finite=True``
    additionally rejects ±inf/NaN inputs and derived-column overflow with a
    ``FloatingPointError`` (the §13.6 contract boundary made loud).

    The state's lattice is the tightest this batch admits (per-column
    ``required_e1``); :func:`merge` aligns mismatched lattices exactly, so
    any micro-batching of the rows merges to the one-shot state bit for
    bit.
    """
    sig = AggSignature.build(aggs, num_segments, spec)
    spec = sig.spec
    v, keys, X = _columns(values, keys, sig.compiled[1], spec, check_finite)
    ncols = X.shape[1]

    if ncols:
        e1, lv, chunk_skip = _prescan(X, levels, spec)
        plan = plan_groupby(int(X.shape[0]), num_segments, spec, ncols=ncols,
                            method=method, chunk=chunk, levels=lv)
        _count_groupby(X.shape[0], spec, lv, plan)
        # times the host's enqueue of the strategy: the device's time for
        # it is in the device trace
        with obs_trace.span("groupby.aggregate", method=plan.method,
                            chunk=plan.chunk, buckets=plan.buckets,
                            n=int(X.shape[0]), G=int(num_segments)):
            table = aggregates.segment_table(
                X, keys, num_segments, spec, method=plan.method, e1=e1,
                chunk=plan.chunk, levels=lv, chunk_skip=chunk_skip,
                num_buckets=plan.buckets if plan.method in ("sort", "radix")
                else None)
    else:
        table = acc_mod.zeros(spec, (num_segments, 0))

    mm = sig.minmax
    if mm:
        with obs_trace.span("groupby.minmax", ncols=len(mm)):
            minv = jnp.stack(
                [jax.ops.segment_min(v[:, j], keys, num_segments)
                 for j in mm], axis=1)
            maxv = jnp.stack(
                [jax.ops.segment_max(v[:, j], keys, num_segments)
                 for j in mm], axis=1)
    else:
        minv = jnp.zeros((num_segments, 0), spec.dtype)
        maxv = jnp.zeros((num_segments, 0), spec.dtype)

    return PartialState(table=table, minv=minv, maxv=maxv,
                        rows=jnp.asarray(v.shape[0], jnp.int32), sig=sig)


# ---------------------------------------------------------------------------
# stage 2: the associative merge
# ---------------------------------------------------------------------------

def _check_sig(a: PartialState, b: PartialState):
    if a.sig != b.sig:
        raise ValueError(
            "cannot merge partial states with different signatures: "
            f"{a.sig} vs {b.sig}")


def merge(a: PartialState, b: PartialState) -> PartialState:
    """Bitwise-associative, commutative merge of two partial states.

    The tables merge with the exact integer accumulator merge (demotion
    onto the pairwise-max lattice, integer add, canonical renorm); MIN/MAX
    columns merge elementwise (float min/max is exact and associative);
    row counts add.  ``merge(partial(A), partial(B)) ==
    partial(A ++ B)`` bit for bit, for any split — DESIGN.md §14.2.
    """
    _check_sig(a, b)
    spec = a.spec
    obs_metrics.counter("repro_partial_merges_total").inc()
    return PartialState(
        table=acc_mod.merge(a.table, b.table, spec),
        minv=jnp.minimum(a.minv, b.minv),
        maxv=jnp.maximum(a.maxv, b.maxv),
        rows=a.rows + b.rows,
        sig=a.sig)


def _merge_all_impl(states) -> PartialState:
    """The metric-free body of :func:`merge_all` (shared with the jitted
    spelling, where counters must not fire at trace time)."""
    states = list(states)
    if not states:
        raise ValueError("merge_all needs at least one state")
    for s in states[1:]:
        _check_sig(states[0], s)
    if len(states) == 1:
        return states[0]
    spec = states[0].spec
    minv = functools.reduce(jnp.minimum, [s.minv for s in states])
    maxv = functools.reduce(jnp.maximum, [s.maxv for s in states])
    rows = functools.reduce(lambda x, y: x + y, [s.rows for s in states])
    return PartialState(
        table=acc_mod.merge_all([s.table for s in states], spec),
        minv=minv, maxv=maxv, rows=rows, sig=states[0].sig)


def merge_all(states) -> PartialState:
    """Exact k-way merge (window-ring queries): one demotion onto the max
    lattice plus one integer tree reduction.  Bit-identical to any pairwise
    :func:`merge` fold — associativity is the whole point."""
    states = list(states)
    if len(states) > 1:
        obs_metrics.counter("repro_partial_merges_total").inc(len(states) - 1)
    return _merge_all_impl(states)


_merge_all_traced = jax.jit(_merge_all_impl)


def merge_all_jit(states) -> PartialState:
    """:func:`merge_all` through a cached XLA executable.

    The jit cache keys on the pytree structure — state count, signature
    (static aux data) and table shapes — so a streaming store flushing the
    same-depth coalescing buffer hits a compiled merge every time.  The
    merge is integer adds, exact float min/max and a canonical renorm;
    fusion cannot reassociate any of it, and bit-equality with the eager
    spelling is pinned by tests (``tests/test_stream_pipeline.py``).
    """
    states = list(states)
    if not states:
        raise ValueError("merge_all needs at least one state")
    if len(states) == 1:
        return states[0]
    obs_metrics.counter("repro_partial_merges_total").inc(len(states) - 1)
    return _merge_all_traced(states)


# ---------------------------------------------------------------------------
# stage 3: finalize
# ---------------------------------------------------------------------------

_SPREAD = ("var", "std")


def _whole_sums(table: ReproAcc, spec: ReproSpec):
    """The exact sums of a table whose rows are whole numbers (the ones
    column, whose sum is COUNT), ``(G, ncols)`` in the accumulator's integer
    type.

    Extraction leaves whole numbers whole: a level whose ulp is at least 1
    takes a multiple of that ulp, and the first level whose ulp is at most
    1 takes the whole rest.  So each level's value ``(C 2^(m-2) + k)
    2^(e_l - m)`` is a whole number, and their sum is exact in integers
    below the integer type's range (2^31 rows a group for float32).  A
    float32 sum could not hold a count past 2^24.
    """
    e = table.e1[..., None] - jnp.arange(spec.L, dtype=jnp.int32) * spec.W

    def times_pow2(x, s):
        return jnp.where(s >= 0, x << jnp.maximum(s, 0),
                         x >> jnp.maximum(-s, 0))

    return jnp.sum(times_pow2(table.C, e - 2) + times_pow2(table.k,
                                                           e - spec.m),
                   axis=-1, dtype=spec.int_dtype)


def _finalize_plans(plans, sums, mins, maxs, spec: ReproSpec, whole=None):
    """Derive every requested aggregate from the finalized table, in plan
    order.

    Fixed elementwise formulas — pure functions of reproducible inputs, so
    the outputs inherit bit-reproducibility.  Empty groups yield NaN for
    MEAN/VAR/STD (the reduction identity for MIN/MAX, 0 for SUM/COUNT).
    COUNT is the ones column's exact integer sum (``whole``, from
    :func:`_whole_sums`).  VAR/STD stop one step short: they yield the two
    terms of the population variance ``s2/n - mean*mean`` and the
    non-empty mask, which :func:`_finish_spread` takes in a program of its
    own.
    """
    nan = jnp.asarray(jnp.nan, spec.dtype)
    out = []
    for p in plans:
        kind = p[0]
        if kind == "sum":
            r = sums[:, p[1]]
        elif kind == "count":
            r = whole[:, p[1]]
        elif kind == "mean":
            s, cnt = sums[:, p[1]], sums[:, p[2]]
            r = jnp.where(cnt > 0, s / jnp.where(cnt > 0, cnt, 1), nan)
        elif kind in _SPREAD:
            s, s2, cnt = sums[:, p[1]], sums[:, p[2]], sums[:, p[3]]
            safe = jnp.where(cnt > 0, cnt, 1)
            mean = s / safe
            r = (s2 / safe, mean * mean, cnt > 0)
        elif kind == "min":
            r = mins[p[1]]
        else:
            r = maxs[p[1]]
        out.append(r)
    return out


def _finish_spread(kinds, terms, spec: ReproSpec):
    """The last step of each VAR/STD from :func:`_finalize_plans`' terms:
    ``max(s2/n - mean*mean, 0)``, its root for STD, NaN on empty groups."""
    nan = jnp.asarray(jnp.nan, spec.dtype)
    out = []
    for kind, (ex2, mean2, nonempty) in zip(kinds, terms):
        r = jnp.maximum(ex2 - mean2, 0.0)               # population var
        if kind == "std":
            r = jnp.sqrt(r)
        out.append(jnp.where(nonempty, r, nan))
    return out


@functools.lru_cache(maxsize=256)
def _finalizer(sig: AggSignature):
    """``finalize``'s body for one signature as compiled programs.

    Compiling must not move a bit against the eager execution of the same
    body (``jax.disable_jit()``), and only one rewrite could: XLA:CPU
    contracts a product into the add or subtract that consumes it (an
    FMA, one rounding instead of two) and drops ``optimization_barrier``.
    Every product of the table's conversion is exact (an integer times a
    power of two), so contracting it rounds the same.  The one product
    that rounds, VAR/STD's ``mean * mean``, therefore leaves the first
    program as a buffer and meets its subtraction in a second one, where
    no compiler can fuse the two.  A signature without VAR/STD runs one
    program; jit re-specializes per table shape.
    """
    names, _, plans = sig.compiled
    spec, mm = sig.spec, sig.minmax
    spread = [i for i, p in enumerate(plans) if p[0] in _SPREAD]
    kinds = [plans[i][0] for i in spread]

    counted = any(p[0] == "count" for p in plans)

    @jax.jit
    def exact(table, minv, maxv):
        sums = acc_mod.finalize(table, spec)                 # (G, ncols)
        mins = {j: minv[:, i] for i, j in enumerate(mm)}
        maxs = {j: maxv[:, i] for i, j in enumerate(mm)}
        return _finalize_plans(
            plans, sums, mins, maxs, spec,
            whole=_whole_sums(table, spec) if counted else None)

    @jax.jit
    def finish(terms):
        return _finish_spread(kinds, terms, spec)

    def run(state: PartialState):
        out = exact(state.table, state.minv, state.maxv)
        if spread:
            for i, r in zip(spread, finish([out[i] for i in spread])):
                out[i] = r
        return dict(zip(names, out))

    return run


def finalize(state: PartialState):
    """Deterministic conversion of a state to the finalized result dict.

    A pure function of the canonical state, so two states that are
    bit-identical (one-shot vs any merge tree) finalize to bit-identical
    results — the argument that lets the streaming engine answer queries
    mid-stream without losing the reproducibility contract.  It runs as
    one compiled program per signature (two with VAR/STD), bit-identical
    to the eager execution of the same body (:func:`_finalizer`); which
    of the two ran is counted as ``repro_groupby_finalize_total{path}``.
    All of it is timed as the ``groupby.finalize`` span.
    """
    path = "eager" if jax.config.jax_disable_jit else "compiled"
    with obs_trace.span("groupby.finalize", path=path):
        obs_metrics.counter("repro_groupby_finalize_total", path=path).inc()
        return _finalizer(state.sig)(state)


# ---------------------------------------------------------------------------
# the compiled partial pipeline (streaming prepare stage)
# ---------------------------------------------------------------------------

def state_nbytes(state: PartialState) -> int:
    """Host-memory footprint of a state's leaves (backpressure accounting)."""
    return sum(int(np.asarray(x).nbytes)
               for x in (state.table.k, state.table.C, state.table.e1,
                         state.minv, state.maxv, state.rows))


class PartialPipeline:
    """:func:`partial_agg` specialized to one fixed :class:`AggSignature`,
    with the jax-heavy tail compiled and cached.

    Eager ``partial_agg`` re-traces its strategies on every call — fine for
    one-shot queries, ruinous for a stream ingesting thousands of
    same-shaped micro-batches (XLA compilation dominated the measured batch
    cost ~10:1).  A store has exactly one signature and sees repeating
    batch shapes, so it is the natural place to amortize compilation; this
    class is that amortization, shared across stores (and across the shards
    of a :class:`repro.stream.ShardedStreamStore`) via :func:`pipeline_for`.

    Staging mirrors ``partial_agg`` exactly: the host-driven front (column
    build, per-column ``required_e1``, the concrete-input prescan, planner
    dispatch, the opt-in finite check) stays eager because its outputs are
    *static* compilation keys; the tail — ``segment_table`` plus the
    stacked MIN/MAX segment reductions — is one jitted function per
    (method, chunk, buckets, level window, chunk_skip) decision, with jit
    itself re-specializing per batch shape.  Every tail op is exact by
    construction (integer adds, EFT extraction, float min/max), so XLA
    fusion cannot perturb bits; compiled-vs-eager bit-equality is pinned by
    tests and the stream benchmark's cross-check gate.  (``finalize`` is
    compiled on its own, per signature, in a form whose bits fusion cannot
    move: see :func:`_finalizer`.)
    """

    def __init__(self, sig: AggSignature, method: str = "auto",
                 levels="auto", check_finite: bool = False):
        self.sig = sig
        self.method = method
        self.levels = tuple(levels) if isinstance(levels, list) else levels
        self.check_finite = check_finite
        self._tails: dict = {}

    def _tail(self, method: str, chunk: int, buckets: int, levels,
              chunk_skip: bool):
        key = (method, chunk, buckets, levels, chunk_skip)
        fn = self._tails.get(key)
        if fn is not None:
            return fn
        sig, spec, mm = self.sig, self.sig.spec, self.sig.minmax

        def tail(X, v, keys, e1):
            table = aggregates.segment_table(
                X, keys, sig.num_segments, spec, method=method, e1=e1,
                chunk=chunk, levels=levels, chunk_skip=chunk_skip,
                num_buckets=buckets if method in ("sort", "radix") else None)
            if mm:
                minv = jnp.stack(
                    [jax.ops.segment_min(v[:, j], keys, sig.num_segments)
                     for j in mm], axis=1)
                maxv = jnp.stack(
                    [jax.ops.segment_max(v[:, j], keys, sig.num_segments)
                     for j in mm], axis=1)
            else:
                minv = jnp.zeros((sig.num_segments, 0), spec.dtype)
                maxv = jnp.zeros((sig.num_segments, 0), spec.dtype)
            return table, minv, maxv

        # setdefault: two pool threads may race to build; one wrapper wins
        return self._tails.setdefault(key, jax.jit(tail))

    @property
    def compiled_variants(self) -> int:
        """Distinct plan decisions compiled so far (observability)."""
        return len(self._tails)

    def __call__(self, values, keys) -> PartialState:
        """Aggregate one batch — bit-identical to ``partial_agg`` with this
        pipeline's configuration, amortizing compilation across calls."""
        sig = self.sig
        spec = sig.spec
        v, keys, X = _columns(values, keys, sig.compiled[1], spec,
                              self.check_finite)
        ncols = X.shape[1]
        if not ncols:
            # min/max-only stores are rare and tiny: keep one code path
            return partial_agg(values, keys, sig.num_segments, aggs=sig.aggs,
                               spec=spec, method=self.method,
                               levels=self.levels,
                               check_finite=self.check_finite)
        e1, lv, chunk_skip = _prescan(X, self.levels, spec)
        plan = plan_groupby(int(X.shape[0]), sig.num_segments, spec,
                            ncols=ncols, method=self.method, levels=lv)
        _count_groupby(X.shape[0], spec, lv, plan)
        fn = self._tail(plan.method, plan.chunk, plan.buckets, lv,
                        bool(chunk_skip))
        # the host's enqueue of the compiled tail, as in partial_agg
        with obs_trace.span("groupby.aggregate", method=plan.method,
                            chunk=plan.chunk, buckets=plan.buckets,
                            n=int(X.shape[0]), G=int(sig.num_segments),
                            compiled=True):
            table, minv, maxv = fn(X, v, keys, e1)
        return PartialState(table=table, minv=minv, maxv=maxv,
                            rows=jnp.asarray(v.shape[0], jnp.int32), sig=sig)


@functools.lru_cache(maxsize=64)
def pipeline_for(sig: AggSignature, method: str = "auto", levels="auto",
                 check_finite: bool = False) -> PartialPipeline:
    """The shared :class:`PartialPipeline` for a configuration.  Stores and
    shards with equal (signature, method, levels, check_finite) reuse one
    pipeline — and therefore one set of compiled executables."""
    return PartialPipeline(sig, method=method, levels=levels,
                           check_finite=check_finite)
