"""Operations and bytes that a query needs, from its shapes alone.

These fix the least time the chip could take for the work; a roofline
share is that least time over the time measured.  Least time is the larger
of bytes over peak HBM bandwidth and operations over the peak rate.  For
every query here the bytes bound it: about two operations a byte (see
:func:`segment_kernel_flops`) against v5e's ratio of 240 at its peaks.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def query_bytes(rows: int, value_columns: int, groups: int,
                aggregates: int) -> int:
    """What the query must move whatever strategy runs it: read each value
    column it names and the int32 group key once, write one float32 result
    per group and aggregate."""
    return rows * (value_columns * F32 + I32) + groups * aggregates * F32


def segment_kernel_bytes(rows: int, accumulator_columns: int, groups: int,
                         levels: int) -> int:
    """The segment kernel's own operands: the stacked float32 column matrix
    and int32 ids in, the (k, C) int32 table of every group, column and
    level out."""
    return (rows * (accumulator_columns * F32 + I32)
            + 2 * groups * accumulator_columns * levels * I32)


def segment_kernel_flops(rows: int, accumulator_columns: int,
                         levels: int) -> int:
    """Operations the reproducible sum needs per element and level: the
    error-free extraction ``q = (r + A) - A`` and ``r -= q`` (three), the
    scaling of ``q`` to an integer (one), and its addition into its group
    (one).  The kernel's one-hot matmul over a 128-group tile does more;
    that excess is the kernel's choice and not counted."""
    return rows * accumulator_columns * levels * 5


def least_seconds(nbytes: float, flops: float, peaks: dict) -> float:
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops"])
