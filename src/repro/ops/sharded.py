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

Shanmugavelu et al. show non-associative collective reductions breaking
run-to-run reproducibility in HPC/DL workloads; this operator is the
RDBMS-side answer — ``sharded_groupby_agg(..., mesh_4x1)`` equals
``groupby_agg(...)`` on one device, bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import accumulator as acc_mod
from repro.core import aggregates, collectives
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec
from repro.ops.partial import (AggSignature, PartialState, _as_matrix,
                               _build_columns, finalize)
from repro.ops.plan import plan_groupby

__all__ = ["sharded_groupby_agg", "sharded_partial_agg"]


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
    """
    sig = AggSignature.build(aggs, num_segments, spec)
    spec = sig.spec
    v = _as_matrix(values, spec)
    keys = jnp.asarray(keys, jnp.int32).reshape(-1)
    if v.shape[0] != keys.shape[0]:
        raise ValueError("values and keys disagree on the row count")
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (axis_name,))
    nshards = mesh.shape[axis_name]
    nrows = v.shape[0]

    _, cols, _ = sig.compiled
    X = _build_columns(v, cols, spec)
    mm = sig.minmax
    M = (jnp.stack([v[:, j] for j in mm], axis=1) if mm
         else jnp.zeros((v.shape[0], 0), spec.dtype))

    # pad rows to the shard count; extra rows land in a dump group G
    nseg1 = num_segments + 1
    pad = (-X.shape[0]) % nshards
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, X.shape[1]), X.dtype)])
        M = jnp.concatenate([M, jnp.zeros((pad, M.shape[1]), M.dtype)])
        keys = jnp.concatenate(
            [keys, jnp.full(pad, num_segments, jnp.int32)])

    plan = plan_groupby(int(X.shape[0]) // nshards, nseg1, spec,
                        ncols=max(X.shape[1], 1), method=method, chunk=chunk,
                        levels=levels)

    def local(x_s, id_s, m_s):
        if x_s.shape[1]:
            e1 = acc_mod.required_e1(x_s, spec, axis=0)      # (ncols,)
            e1 = lax.pmax(e1, axis_name)  # global lattice before extraction
            tab = aggregates.segment_table(
                x_s, id_s, nseg1, spec, method=plan.method, e1=e1,
                chunk=plan.chunk, levels=levels,
                num_buckets=plan.buckets if plan.method in ("sort", "radix")
                else None)
            tab = collectives.repro_psum(tab, spec, (axis_name,))
        else:
            tab = acc_mod.zeros(spec, (nseg1, 0))
        mins = lax.pmin(jax.ops.segment_min(m_s, id_s, nseg1), axis_name)
        maxs = lax.pmax(jax.ops.segment_max(m_s, id_s, nseg1), axis_name)
        return tab.k, tab.C, tab.e1, mins, maxs

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P(), P(), P()), axis_names={axis_name},
        check_vma=False)
    k, C, e1, mins, maxs = jax.jit(fn)(X, keys, M)

    # slice off the dump group: what remains is exactly the partial a
    # single device would have produced over the unpadded rows
    table = ReproAcc(k=k[:num_segments], C=C[:num_segments],
                     e1=e1[:num_segments])
    return PartialState(table=table, minv=mins[:num_segments],
                        maxv=maxs[:num_segments],
                        rows=jnp.asarray(nrows, jnp.int32), sig=sig)


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
    after the merge, so any device count accepts any row count.  Returns the
    same dict as :func:`groupby_agg`, replicated; bit-identical to the
    single-device result for every mesh shape.
    """
    return finalize(sharded_partial_agg(
        values, keys, num_segments, aggs=aggs, spec=spec, mesh=mesh,
        axis_name=axis_name, method=method, chunk=chunk, levels=levels))
