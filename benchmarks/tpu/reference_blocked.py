"""The plain reference of ``reference.py``, fed one block of rows at a time.

For tables that a host should not hold in float64 at once (TPC-H SF100's
590M Q1 rows would take 24 GB): each block adds its per-group float64
sums, absolute sums and counts and its largest magnitude of every input,
and the comparison is ``GroupReference.compare`` itself, with its DESIGN §3
bound formulae, over those totals.  It imports nothing of the program.

A block comes sorted by group key, so that each group's rows lie in one
run and a run's float64 sum is one ``np.add.reduceat``.  Sums are taken
in another order than ``GroupReference``'s ``bincount``; the bound's
``n * 2^-52`` term for the reference's own error holds for any order.
"""
from __future__ import annotations

import numpy as np

from benchmarks.tpu.reference import GroupReference


def _input(a):
    """The summed input of an aggregate: ``(column,)`` or ``(a, b)`` for a
    product."""
    return tuple(a[1:])


class BlockedGroupReference(GroupReference):
    """``GroupReference`` over blocks added with :meth:`add`."""

    def __init__(self, aggs, groups: int):
        self.groups = int(groups)
        self.count = np.zeros(self.groups)
        self.totals = {_input(a): [np.zeros(self.groups),
                                   np.zeros(self.groups), 0.0]
                       for a in aggs if a[0] != "count"}

    def add(self, columns: dict, keys) -> None:
        """One block: ``columns`` maps a column name to its float32 rows,
        ``keys`` holds the rows' groups in ascending order; rows whose key
        is past the last group are not counted."""
        keys = np.asarray(keys)
        bounds = np.searchsorted(keys, np.arange(self.groups + 1))
        self.count += np.diff(bounds)
        live = np.flatnonzero(bounds[1:] > bounds[:-1])
        starts = bounds[:-1][live]
        n = bounds[-1]
        for inp, tot in self.totals.items():
            if len(inp) == 2:
                x = np.multiply(columns[inp[0]][:n], columns[inp[1]][:n],
                                dtype=np.float64)
            else:
                x = columns[inp[0]][:n]
            if not x.size:
                continue
            lo, hi = float(np.min(x)), float(np.max(x))
            s = np.add.reduceat(x, starts, dtype=np.float64)
            tot[0][live] += s
            tot[1][live] += (s if lo >= 0 else np.add.reduceat(
                np.abs(x), starts, dtype=np.float64))
            tot[2] = max(tot[2], hi, -lo)

    def merge(self, other: "BlockedGroupReference") -> None:
        """Add the totals of ``other``, a reference over other rows."""
        self.count += other.count
        for inp, tot in self.totals.items():
            o = other.totals[inp]
            tot[0] += o[0]
            tot[1] += o[1]
            tot[2] = max(tot[2], o[2])

    def _column(self, a):
        return _input(a), a[0] == "sum_prod"

    def sums(self, inp):
        return self.totals[inp][0], self.totals[inp][1]

    def sum_bound(self, inp, rounded: bool, abs_sum, m: int, L: int,
                  W: int):
        return super().sum_bound(np.array([self.totals[inp][2]]), rounded,
                                 abs_sum, m, L, W)
