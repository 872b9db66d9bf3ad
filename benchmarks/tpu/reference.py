"""The plain reference: GROUP BY aggregates in float64 numpy, and the
comparison that decides whether a run is correct.

It imports nothing of the program.  It takes the raw generated columns (the
data, float32 as generated) and builds its own derived columns: products in
float64, which are exact for two float32 factors, and the row counts.

The comparison holds every finalized aggregate of every group to the error
bound that the configuration's accumulator format guarantees (DESIGN.md §3),
worked out from the configuration's stated ``(m, L, W)`` and never from the
format a run used:

* extraction drops, below the finest of the L levels, a residual of at most
  half that level's ulp per row: ``n * 2^(e1 - (L-1) W - m - 1)``, where
  ``e1`` is the lattice exponent that admits the column's largest magnitude;
* finalize rounds each of its L level values and L - 1 additions:
  ``2 L * 2^-(m+1)`` of the group's absolute sum;
* a product column is rounded to the format once per row before it is
  summed: another ``2^-(m+1)`` of the absolute sum;
* the float64 reference itself adds at most ``n * 2^-52`` of it.

MEAN is the finalized sum divided by the exact count: the sum's bound over
the count, plus the division's rounding.  COUNT is exact.  The readings are
the worst error as a share of its bound over all groups and aggregates (1.0
is the guarantee) and the number of groups whose COUNT differs.
"""
from __future__ import annotations

import math

import numpy as np

#: IEEE binary32 exponent range of normal numbers
_F32_MIN_EXP, _F32_MAX_EXP = -126, 127


def agg_columns(aggs) -> list[str]:
    """The value columns the aggregates read, in order of first use."""
    cols: list[str] = []
    for a in aggs:
        for c in a[1:]:
            if c not in cols:
                cols.append(c)
    return cols


def lattice_e1(amax: float, m: int, L: int, W: int) -> int:
    """The level-1 extractor exponent that admits ``amax``: the smallest
    multiple of W at or above ``E + m - W + 2`` (E the exponent of
    ``amax``), kept inside the format's normal range."""
    if amax > 0:
        e = math.frexp(amax)[1] - 1
    else:
        e = _F32_MIN_EXP - 1
    e1 = -((-(e + m - W + 2)) // W) * W
    lo = -((-(_F32_MIN_EXP + m + (L - 1) * W)) // W) * W
    hi = ((_F32_MAX_EXP - 1) // W) * W
    return min(max(e1, lo), hi)


class GroupReference:
    """float64 per-group sums, absolute sums and counts over the raw rows.

    ``weights``, where given, is how many times each row was delivered (a
    stream that sends the same rows again under fresh tags): the reference
    is then over that multiset of rows.
    """

    def __init__(self, columns: dict, keys, groups: int, weights=None):
        self.keys = np.asarray(keys, np.int64)
        self.weights = (np.ones(self.keys.shape[0]) if weights is None
                        else np.asarray(weights, np.float64))
        live = self.weights > 0
        self.columns = {c: np.asarray(v, np.float64)[live]
                        for c, v in columns.items()}
        self.keys, self.weights = self.keys[live], self.weights[live]
        self.groups = int(groups)
        self.count = np.bincount(self.keys, self.weights, self.groups)

    def _column(self, a):
        """(float64 values, rounded-per-row) of an aggregate's input."""
        if a[0] == "sum_prod":
            return self.columns[a[1]] * self.columns[a[2]], True
        return self.columns[a[1]], False

    def sums(self, x):
        return (np.bincount(self.keys, x * self.weights, self.groups),
                np.bincount(self.keys, np.abs(x) * self.weights,
                            self.groups))

    def sum_bound(self, x, rounded: bool, abs_sum, m: int, L: int, W: int):
        # a product rounded to the format may reach the next binade
        amax = float(np.max(np.abs(x))) if x.size else 0.0
        if rounded:
            amax *= 1 + 2.0 ** -m
        e_last = lattice_e1(amax, m, L, W) - (L - 1) * W
        rel = 2 * L * 2.0 ** -(m + 1) + (2.0 ** -(m + 1) if rounded else 0)
        return (self.count * 2.0 ** (e_last - m - 1)
                + (rel + self.count * 2.0 ** -52) * abs_sum)

    def compare(self, got, aggs, m: int, L: int, W: int) -> dict:
        """Readings of the program's finalized aggregates ``got`` (one
        array per aggregate, in the order of ``aggs``) against the
        reference and the bound of the stated format ``(m, L, W)``."""
        if len(got) != len(aggs):
            raise ValueError(f"{len(got)} results for {len(aggs)} aggregates")
        worst, count_bad = 0.0, 0
        live = self.count > 0
        eps = 2.0 ** -(m + 1)
        for a, g in zip(aggs, got):
            g = np.asarray(g, np.float64).reshape(-1)
            if g.shape[0] != self.groups:
                raise ValueError(f"{a}: {g.shape[0]} groups, want "
                                 f"{self.groups}")
            if a[0] == "count":
                count_bad += int(np.sum(g != self.count))
                continue
            x, rounded = self._column(a)
            s, abs_s = self.sums(x)
            bound = self.sum_bound(x, rounded, abs_s, m, L, W)
            if a[0] in ("sum", "sum_prod"):
                ref = s
            elif a[0] == "mean":
                n = np.where(live, self.count, 1.0)
                ref = s / n
                bound = bound / n + eps * (np.abs(ref) + bound / n)
            else:
                raise ValueError(f"the reference has no aggregate {a[0]!r}")
            err = np.where(live, np.abs(g - ref), 0.0)
            if a[0] in ("sum", "sum_prod"):
                # an empty group sums to exactly zero
                err = np.where(live, err, np.abs(g))
            # a non-finite result reads as the largest finite share, so
            # that the reading stays a JSON number
            big = np.finfo(np.float64).max
            err = np.where(np.isfinite(err), err, big)
            with np.errstate(over="ignore"):
                share = np.minimum(
                    err / np.maximum(bound, np.finfo(np.float64).tiny), big)
            worst = max(worst, float(np.max(share)) if share.size else 0.0)
        return {"error_share_of_bound": worst,
                "count_mismatches": count_bad}
