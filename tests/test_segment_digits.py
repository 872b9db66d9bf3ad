"""The segment kernel's bf16 digit contract (DESIGN.md §3.2 item 4).

A contribution scaled to an integer ``|v| <= 2^(W-1)`` splits into
``ceil(W/9)`` planes whose digits are exact in bf16, so one default
precision MXU pass sums what an f32 contraction at HIGHEST did.  These
tests check the split itself, exhaustively where W = 18, and the kernel
against the scatter strategy, bit for bit, on columns built to sit on the
split's edges: at ``±2^(W-1)``, at ``±256·2^9 ± 1`` and at ``±256``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregates import segment_table
from repro.core.types import ReproSpec
from repro.kernels.segment_rsum.kernel import bf16_digits, split_digits
from repro.kernels.segment_rsum.ops import segment_agg_kernel

M = 23   # float32's stored mantissa bits


def _check_split(v, p):
    planes = split_digits(jnp.asarray(v, jnp.float32), p)
    assert len(planes) == p
    total = np.zeros(v.shape, np.float64)
    for j, plane in zip(range(p - 1, -1, -1), planes):
        plane = np.asarray(plane, np.float64)
        digit = plane / 2.0 ** (9 * j)
        assert np.array_equal(digit, np.round(digit))
        assert np.abs(digit).max() <= 256
        as_bf16 = jnp.asarray(plane, jnp.float32).astype(jnp.bfloat16)
        assert np.array_equal(np.asarray(as_bf16, np.float64), plane)
        total += plane
    assert np.array_equal(total, v.astype(np.float64))


def test_split_digits_every_integer_of_w18():
    """Every integer |v| <= 2^17, 262,145 values: two planes, hi + lo == v
    and both exact in bf16."""
    v = np.arange(-(1 << 17), (1 << 17) + 1, dtype=np.float32)
    assert v.size == 262_145
    assert bf16_digits(18) == 2
    _check_split(v, 2)


@pytest.mark.parametrize("W,p", [(9, 1), (12, 2), (18, 2), (21, 3)])
def test_split_digits_at_the_boundary(W, p):
    assert bf16_digits(W) == p
    top = 1 << (W - 1)
    edges = np.array([top, top - 1, top - 256, 256, 257, 255, 1, 0,
                      (256 << 9) + 1, (256 << 9) - 1, 256 << 9],
                     dtype=np.int64)
    edges = edges[edges <= top]
    v = np.concatenate([edges, -edges]).astype(np.float32)
    _check_split(v, p)


def _edge_scaled(W):
    """Scaled integers on the digit split's edges that a level can extract
    (``|v| <= 2^(W-1)``)."""
    top = 1 << (W - 1)
    v = np.array([top, top - 1, (256 << 9) + 1, (256 << 9) - 1, 256, 255,
                  257, 1], dtype=np.int64)
    v = v[v <= top]
    return np.concatenate([v, -v])


def _edge_columns(W, ncols, live, rng):
    """(n, ncols) float32 rows, each one edge integer times the ulp of one
    of the ``live`` levels of the lattice ``e1 = 2W``, in shuffled order,
    with a 128-row run of ``+2^(W-1)`` at level ``live[0]`` that fills a
    sub-block's f32 sums to ``2^24``."""
    e1 = 2 * W
    cols = []
    for _ in range(ncols):
        parts = []
        for lev in live:
            ulp = 2.0 ** (e1 - lev * W - M)
            parts.append(np.repeat(_edge_scaled(W), 4) * ulp)
        x = np.concatenate(parts)
        x = x[rng.permutation(x.size)]
        top = float(1 << (W - 1)) * 2.0 ** (e1 - live[0] * W - M)
        run = np.full(128, top)
        cols.append(np.concatenate([run, x]))
    return np.stack(cols, axis=1).astype(np.float32), e1


@pytest.mark.parametrize("ncols", [1, 5, 9])
@pytest.mark.parametrize("G", [6, 300])
@pytest.mark.parametrize("window", ["full", "pruned"])
@pytest.mark.parametrize("W", [9, 18, 21])
def test_segment_kernel_edge_values_bit_identity(W, window, G, ncols):
    """The kernel (interpret mode) against ``segment_table(method=
    "scatter")``, bit for bit, with the digit split at its edges; G = 300
    under 128-group tiles runs three tiles, and 9 columns pad to 16."""
    L = 3
    spec = ReproSpec(dtype=jnp.float32, L=L, W=W)
    rng = np.random.default_rng(W * 1000 + G * 10 + ncols)
    # pruned: no row reaches past 2^(W-1) ulps of level 1, half an ulp of
    # level 0, which then extracts exact zeros (a tie goes back to A); the
    # prescan's bound is one exponent short of proving it, the full-ladder
    # scatter table below checks it
    live, levels = ((0, 1, 2), None) if window == "full" else ((1, 2), (1, L))
    x, e1 = _edge_columns(W, ncols, live, rng)
    ids = rng.integers(0, G, x.shape[0]).astype(np.int32)
    ids[:128] = G // 2                      # the run lands in one group
    e1 = np.full(ncols, e1, np.int32)
    got = segment_agg_kernel(x, ids, G, spec, e1=e1, group_tile=128,
                             levels=levels, interpret=True)
    want = segment_table(x, ids, G, spec, method="scatter", e1=e1)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
