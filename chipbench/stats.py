"""Arithmetic of the end-to-end metrics, kept with the benchmark."""
from __future__ import annotations

import math


def geomean(values) -> float:
    """Geometric mean of positive values (TPC-H's power statistic)."""
    values = list(values)
    if not values or min(values) <= 0:
        raise ValueError(f"geomean needs positive values, got {values!r}")
    return math.exp(sum(math.log(v) for v in values) / len(values))


def nearest_rank(values, pct: float) -> float:
    """The ``pct`` percentile by nearest rank: the ceil(pct/100 * n)-th
    smallest value, always one of the samples."""
    values = sorted(values)
    if not values:
        raise ValueError("no values")
    rank = max(1, math.ceil(pct / 100.0 * len(values)))
    return values[rank - 1]


def completed_in(records, start: float, end: float) -> list:
    """Records of queries that started at or after ``start`` and finished
    by ``end``: a query still in flight at the window's close is left
    out."""
    return [r for r in records
            if not r["failed"] and r["start"] >= start and r["end"] <= end]


def table_nbytes(table) -> int:
    """Bytes of a result or operand table's columns at their true row
    count: dictionary columns count their codes."""
    total = 0
    for col in table.cols.values():
        arr = getattr(col, "codes", col)
        total += arr.nbytes
    return total
