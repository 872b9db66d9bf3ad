"""Reproducible cross-device reductions (DESIGN.md §3.2 / §5).

The paper merges per-thread private hash tables into a shared table with
``operator+=(repro<ScalarT,L>)`` — exact, hence schedule-independent.  The
distributed analogue is an all-reduce of accumulators.  Because the canonical
representation is integer, ``lax.psum`` over (k, C) is exact and associative:
*any* reduction topology (ring, tree, multi-pod hierarchy) produces identical
bits.

Overflow discipline: window offsets k live in [0, 2^(m-2)); an int32 psum of
them is exact for axis sizes up to 2^(33-m) (f32: 1024).  Production meshes
reduce hierarchically per axis ("data" then "pod"), renormalizing between
stages, so each stage stays within bound — this is the trick that makes the
scheme safe for 1000+ nodes (multi-pod meshes reduce one bounded axis at a
time).

``repro_psum_packed`` is the beyond-paper wire optimization: an all-reduce is
a reduce-scatter (needs integer headroom) followed by an all-gather (pure
data movement).  After the reduce-scatter we renormalize to canonical form
and bit-pack k (m-2 bits) + C into half the words before gathering, cutting
the gather-phase bytes by 2x at zero accuracy cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import accumulator as acc_mod
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec

__all__ = [
    "max_axis_size", "repro_psum", "repro_psum_packed", "pack_acc",
    "unpack_acc",
]


def max_axis_size(spec: ReproSpec) -> int:
    """Largest single-axis fan-in with exact integer psum of window offsets."""
    bits = 31 if spec.m <= 30 else 63
    return 1 << (bits - (spec.m - 2))


def _check_axis(axis_name, spec):
    size = lax.axis_size(axis_name)
    if size > max_axis_size(spec):
        raise ValueError(
            f"axis {axis_name!r} of size {size} exceeds the exact-psum bound "
            f"{max_axis_size(spec)}; reduce hierarchically (pass the axis as "
            "two mesh axes) or raise the accumulator int width.")
    return size


def repro_psum(acc: ReproAcc, spec: ReproSpec, axis_names) -> ReproAcc:
    """Exact all-reduce of accumulators over mesh axes (inside shard_map).

    Axes are reduced one at a time with a renormalization between stages, so
    window offsets never overflow.  The result is canonical and bit-identical
    for any axis order, device count, or reduction topology.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    for ax in axis_names:
        _check_axis(ax, spec)
        e1 = lax.pmax(acc.e1, ax)
        acc = acc_mod.demote_to(acc, e1, spec)
        k = lax.psum(acc.k, ax)
        C = lax.psum(acc.C, ax)
        k, C = acc_mod.renorm(k, C, spec)
        acc = ReproAcc(k=k, C=C, e1=e1)
    return acc


def repro_psum_scatter(acc: ReproAcc, spec: ReproSpec, axis_names,
                       dim: int) -> ReproAcc:
    """Exact reduce-scatter of accumulators along tensor dimension ``dim``
    (the ZeRO-2 building block: each device keeps 1/N of the reduced sums).

    Requires a *scalar* (per-tensor) e1 — gradient accumulators use one
    lattice point per tensor.  Renormalizes between axes so multi-pod
    hierarchies stay within the integer bound.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    assert acc.e1.ndim == 0, "repro_psum_scatter expects per-tensor e1"
    e1 = acc.e1
    for ax in axis_names:
        e1 = lax.pmax(e1, ax)
    acc = acc_mod.demote_to(acc, e1, spec)
    k, C = acc.k, acc.C
    for ax in axis_names:
        _check_axis(ax, spec)
        k = lax.psum_scatter(k, ax, scatter_dimension=dim, tiled=True)
        C = lax.psum_scatter(C, ax, scatter_dimension=dim, tiled=True)
        k, C = acc_mod.renorm(k, C, spec)
    return ReproAcc(k=k, C=C, e1=e1)


# ---------------------------------------------------------------------------
# Packed wire format (beyond-paper optimization, §Perf)
# ---------------------------------------------------------------------------

def _c_bits(spec: ReproSpec) -> int:
    return 32 - (spec.m - 2) - 1  # leave one sign/slack bit


def pack_acc(acc: ReproAcc, spec: ReproSpec):
    """Bit-pack canonical (k, C) into one int32 word per level.

    Layout per level: k in the low (m-2) bits (canonical, non-negative),
    C biased into the next ``32 - (m-2) - 1`` bits.  Valid only for |C| <
    2^(c_bits-1); callers renormalize and assert via debug checks.  f32/L=2:
    8 bytes/scalar instead of 16.
    """
    cb = _c_bits(spec)
    bias = 1 << (cb - 1)
    kk = acc.k.astype(jnp.int32)
    cc = (acc.C.astype(jnp.int32) + bias)
    word = kk | (cc << (spec.m - 2))
    return word, acc.e1


def unpack_acc(word, e1, spec: ReproSpec) -> ReproAcc:
    cb = _c_bits(spec)
    bias = 1 << (cb - 1)
    mask = (1 << (spec.m - 2)) - 1
    k = (word & mask).astype(spec.int_dtype)
    C = ((word >> (spec.m - 2)) & ((1 << cb) - 1)).astype(spec.int_dtype) - bias
    return ReproAcc(k=k, C=C, e1=e1)


def repro_psum_packed(acc: ReproAcc, spec: ReproSpec, axis_names) -> ReproAcc:
    """All-reduce = psum_scatter (int, exact) + packed all_gather (2x bytes).

    Requires the leading dim of the accumulator batch to be divisible by the
    total axis size; callers pad.  Falls back to :func:`repro_psum` when the
    packed window does not apply (f64).
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    total = 1
    for ax in axis_names:
        total *= lax.axis_size(ax)
    if spec.m > 30 or acc.k.ndim < 2 or acc.k.shape[0] % total != 0:
        return repro_psum(acc, spec, axis_names)   # packed layout N/A
    e1 = acc.e1
    for ax in axis_names:
        e1 = lax.pmax(e1, ax)
    acc = acc_mod.demote_to(acc, e1, spec)
    k, C = acc.k, acc.C
    for ax in axis_names:
        _check_axis(ax, spec)
        # reduce_scatter: each device ends with a 1/size shard of the sums
        k = lax.psum_scatter(k, ax, scatter_dimension=0, tiled=True)
        C = lax.psum_scatter(C, ax, scatter_dimension=0, tiled=True)
        k, C = acc_mod.renorm(k, C, spec)
    shard = ReproAcc(k=k, C=C, e1=e1)
    word, _ = pack_acc(shard, spec)
    for ax in reversed(axis_names):
        word = lax.all_gather(word, ax, axis=0, tiled=True)
    e1_full = e1  # e1 is replicated already (pmax result)
    return unpack_acc(word, e1_full, spec)
