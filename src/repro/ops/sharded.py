"""Sharded reproducible GROUPBY: per-shard partials + exact collective merge.

The paper merges per-thread private hash tables into a shared table with the
exact accumulator ``operator+=`` — schedule-independent because the merge is
integer arithmetic.  This module is the multi-device analogue (DESIGN.md §5,
§10 and §14): it is the partial/merge/finalize pipeline of
:mod:`repro.ops.partial` with the merge stage executed as a collective —
each shard aggregates its row slice into a local partial table with
:func:`segment_table`, the tables merge with :func:`repro_psum` (an integer
all-reduce, hence exact and associative over any reduction topology), and
the replicated merged state finalizes through the same
:func:`repro.ops.partial.finalize` every other deployment shape uses.

Bit-identity across mesh shapes rests on two facts:

* the lattice exponents are agreed globally *before* extraction: each shard
  takes a ``pmax`` of its per-column e1, and because the lattice snap is
  monotone, ``pmax(required_e1(shard)) == required_e1(whole input)`` — every
  mesh extracts on the very lattice a single device would use (so the
  collective merge never even needs the demotion path the host-side
  :func:`repro.ops.partial.merge` carries for mismatched micro-batches);
* everything after extraction is integer (table psum) or exactly associative
  (MIN/MAX via ``pmin``/``pmax``), and the finalizer is a pure function.

The whole per-shard path is one compiled program per (signature, mesh, row
count, plan, level window), built once and cached (DESIGN.md §10): the pad
to the shard count, the value matrix, the stacked accumulator and MIN/MAX
columns, the lattice agreement, the aggregation and the collective merge
all run inside it, so no eager copy of the table precedes it.  Each shard
aggregates its rows one row block at a time (``GroupbyPlan.block_rows``,
sized by the planner against a chip's working-set budget): a first pass
over the blocks finds the column maxima for the global lattice, a second
gives each block its table on that lattice and adds it to the shard's with
the accumulator's exact integer add and renorm.  Integer addition is
associative, so every block count gives the bits of one pass.

Shanmugavelu et al. show non-associative collective reductions breaking
run-to-run reproducibility in HPC/DL workloads; this operator is the
RDBMS-side answer — ``sharded_groupby_agg(..., mesh_4x1)`` equals
``groupby_agg(...)`` on one device, bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import accumulator as acc_mod
from repro.core import aggregates, collectives
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.ops.partial import (AggSignature, PartialState, _as_matrix,
                               _build_columns, _count_groupby, finalize)
from repro.ops.plan import plan_groupby

__all__ = ["sharded_groupby_agg", "sharded_partial_agg"]


def _shard_rows(n: int, nshards: int) -> int:
    """Rows of each shard once the rows are padded to the shard count."""
    return max(-(-n // nshards), 1)


@functools.lru_cache(maxsize=64)
def _shard_program(sig: AggSignature, mesh, axis_name: str, n: int, plan,
                   levels):
    """The compiled shard program of an ``n``-row input, and its row blocks
    a shard.

    ``program(values, keys)`` pads the rows to the shard count (padding
    rows land in a dump group ``G`` that is sliced off after the merge),
    and each shard then aggregates its rows in blocks of
    ``plan.block_rows``.  The last block ends at the shard's end; the rows
    it shares with the block before it go to the dump group.  Returns the
    merged ``(k, C, e1)`` table and MIN/MAX columns of the ``G`` groups,
    replicated.
    """
    spec = sig.spec
    _, cols, _ = sig.compiled
    mm = sig.minmax
    ncols, G = len(cols), sig.num_segments
    nshards = mesh.shape[axis_name]
    rows = _shard_rows(n, nshards)
    pad = rows * nshards - n
    R = min(max(plan.block_rows, 1), rows)
    nblocks = -(-rows // R)

    def block(v_s, id_s, b):
        start = jnp.minimum(b * R, rows - R)
        vb = lax.dynamic_slice_in_dim(v_s, start, R)
        ib = lax.dynamic_slice_in_dim(id_s, start, R)
        return vb, jnp.where(start + jnp.arange(R) >= b * R, ib, G)

    def local(v_s, id_s):
        if ncols:
            def col_max(b, amax):
                X = _build_columns(block(v_s, id_s, b)[0], cols, spec)
                return jnp.maximum(amax, jnp.max(jnp.abs(X), axis=0))

            amax = lax.fori_loop(0, nblocks, col_max,
                                 jnp.zeros(ncols, spec.dtype))
            # global lattice before extraction
            e1 = lax.pmax(acc_mod.required_e1(amax[None], spec, axis=0),
                          axis_name)

        def add_block(b, carry):
            k, C, mins, maxs = carry
            vb, ib = block(v_s, id_s, b)
            if ncols:
                t = aggregates.segment_table(
                    _build_columns(vb, cols, spec), ib, G + 1, spec,
                    method=plan.method, e1=e1, chunk=plan.chunk,
                    levels=levels,
                    num_buckets=plan.buckets
                    if plan.method in ("sort", "radix") else None)
                k, C = acc_mod.renorm(k + t.k, C + t.C, spec)
            if mm:
                m = jnp.stack([vb[:, j] for j in mm], axis=1)
                mins = jnp.minimum(mins, jax.ops.segment_min(m, ib, G + 1))
                maxs = jnp.maximum(maxs, jax.ops.segment_max(m, ib, G + 1))
            return k, C, mins, maxs

        z = jnp.zeros((G + 1, ncols, spec.L), spec.int_dtype)
        k, C, mins, maxs = lax.fori_loop(0, nblocks, add_block, (
            z, z, jnp.full((G + 1, len(mm)), jnp.inf, spec.dtype),
            jnp.full((G + 1, len(mm)), -jnp.inf, spec.dtype)))
        if ncols:
            tab = collectives.repro_psum(
                ReproAcc(k=k, C=C, e1=jnp.broadcast_to(e1, (G + 1, ncols))),
                spec, (axis_name,))
        else:
            tab = acc_mod.zeros(spec, (G + 1, 0))
        mins, maxs = lax.pmin(mins, axis_name), lax.pmax(maxs, axis_name)
        return tab.k[:G], tab.C[:G], tab.e1[:G], mins[:G], maxs[:G]

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P(), P(), P()), axis_names={axis_name},
        check_vma=False)

    @jax.jit
    def program(values, keys):
        v = _as_matrix(values, spec)
        ids = jnp.asarray(keys, jnp.int32).reshape(-1)
        if pad:
            v = jnp.pad(v, ((0, pad), (0, 0)))
            ids = jnp.pad(ids, (0, pad), constant_values=G)
        return fn(v, ids)

    return program, nblocks


def _sharded_partial(values, keys, num_segments, aggs, spec, mesh, axis_name,
                     method, chunk, levels):
    """:func:`sharded_partial_agg`, and the shard and block counts."""
    sig = AggSignature.build(aggs, num_segments, spec)
    spec = sig.spec
    n = int(np.shape(values)[0])
    if int(np.prod(np.shape(keys))) != n:
        raise ValueError("values and keys disagree on the row count")
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (axis_name,))
    if levels is not None:
        levels = tuple(int(x) for x in levels)
    nshards = mesh.shape[axis_name]
    plan = plan_groupby(_shard_rows(n, nshards), num_segments + 1, spec,
                        ncols=max(sig.ncols, 1), method=method, chunk=chunk,
                        levels=levels)
    _count_groupby(n, spec, levels, plan)
    misses = _shard_program.cache_info().misses
    program, nblocks = _shard_program(sig, mesh, axis_name, n, plan, levels)
    built = _shard_program.cache_info().misses > misses
    obs_metrics.counter("repro_groupby_shard_blocks_total").inc(
        nblocks * nshards)
    if built:
        obs_metrics.counter("repro_groupby_shard_programs_total").inc()
    with obs_trace.span("groupby.shard_program", built=int(built),
                        shards=nshards, blocks=nblocks):
        k, C, e1, mins, maxs = program(values, keys)
    state = PartialState(table=ReproAcc(k=k, C=C, e1=e1), minv=mins,
                         maxv=maxs, rows=jnp.asarray(n, jnp.int32), sig=sig)
    return state, nshards, nblocks


def sharded_partial_agg(values, keys, num_segments: int, aggs=("sum",),
                        spec: ReproSpec | None = None, mesh=None,
                        axis_name: str = "data", method: str = "auto",
                        chunk: int | None = None,
                        levels: tuple[int, int] | None = None
                        ) -> PartialState:
    """Multi-device partial aggregation: shard rows, aggregate locally on
    the globally agreed lattice, merge collectively.  Returns the same
    replicated :class:`PartialState` a single-device
    :func:`repro.ops.partial.partial_agg` over all rows would return, bit
    for bit — so it composes with the host-side ``merge`` (e.g. a stream
    store ingesting sharded micro-batches) like any other partial.

    The host's call of the compiled shard program is the
    ``groupby.shard_program`` span (attribute ``built``: 1 where this call
    built the program); the call counts in ``repro_groupby_rows_total``,
    ``repro_groupby_calls_total{method}``,
    ``repro_groupby_shard_blocks_total`` (row blocks, over all shards) and,
    where it built one, ``repro_groupby_shard_programs_total``.
    """
    return _sharded_partial(values, keys, num_segments, aggs, spec, mesh,
                            axis_name, method, chunk, levels)[0]


def sharded_groupby_agg(values, keys, num_segments: int, aggs=("sum",),
                        spec: ReproSpec | None = None, mesh=None,
                        axis_name: str = "data", method: str = "auto",
                        chunk: int | None = None,
                        levels: tuple[int, int] | None = None):
    """Multi-device :func:`repro.ops.groupby_agg` over a row-sharded table:
    ``finalize(sharded_partial_agg(...))``.

    Args:
      values/keys/num_segments/aggs/spec/method/chunk: as in
        :func:`groupby_agg`.
      mesh:      mesh to shard rows over; default 1-D mesh of every device.
      axis_name: mesh axis carrying the rows.
      levels:    optional static live-level window.  Must be proved against
        the *global* lattice and data (e.g. ``prescan.static_window`` over
        the whole column matrix before sharding) — each shard extracts on
        the global ``pmax`` lattice, so a window valid for the whole input
        is valid on every shard, and the pruned per-shard tables stay
        bit-identical to unpruned ones under the integer psum merge.

    Rows are padded to the shard count with a dump group that is sliced off
    after the merge, so any device count accepts any row count.  The call
    is one ``groupby.query`` span (attributes ``n``, ``G``, ``method``,
    ``shards``, ``blocks``), the root of ``groupby.shard_program`` and
    ``groupby.finalize``.  Returns the same dict as :func:`groupby_agg`,
    replicated; bit-identical to the single-device result for every mesh
    shape and block count.
    """
    with obs_trace.span("groupby.query", G=int(num_segments),
                        method=method) as sp:
        state, shards, blocks = _sharded_partial(
            values, keys, num_segments, aggs, spec, mesh, axis_name, method,
            chunk, levels)
        if obs_trace.enabled():
            sp.set(n=int(np.shape(values)[0]), ncols=state.sig.ncols,
                   shards=shards, blocks=blocks)
        return finalize(state)
