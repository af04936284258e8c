"""The comparison that decides ``correct``: a query's result against the
reference's answer for it."""
from __future__ import annotations

import numpy as np


def as_arrays(table) -> dict[str, np.ndarray]:
    """A result table's columns as plain arrays; dictionary-encoded
    strings are decoded to bytes."""
    out = {}
    for name, col in table.cols.items():
        if hasattr(col, "codes"):
            out[name] = np.asarray(col.values)[np.asarray(col.codes)] \
                if len(col.codes) else np.asarray([], "S1")
        else:
            out[name] = np.asarray(col)
    return out


def compare(got: dict[str, np.ndarray], want: dict[str, np.ndarray]
            ) -> tuple[bool, float]:
    """(exact part differs, float error).

    The exact part is the set of columns, the number of rows, and every
    key or other non-float cell, row by row in the answer's order: any
    difference there is a wrong answer. The float error is, over the
    float columns, the largest |got - want| relative to the largest
    |want| of that column; it is 0.0 where there is no float column and
    NaN where the exact part already differs.
    """
    if sorted(got) != sorted(want):
        return True, float("nan")
    n = {len(v) for v in want.values()}
    if {len(v) for v in got.values()} != n:
        return True, float("nan")
    err = 0.0
    for name, w in want.items():
        g = got[name]
        if w.dtype.kind == "f":
            g = np.asarray(g, np.float64)
            w = np.asarray(w, np.float64)
            if not len(w):
                continue
            scale = max(float(np.max(np.abs(w))), np.finfo(float).tiny)
            d = float(np.max(np.abs(g - w))) / scale
            err = max(err, d if np.isfinite(d) else float("inf"))
        elif w.dtype.kind == "S" or g.dtype.kind == "S":
            if g.dtype.kind != "S" or not np.array_equal(g, w):
                return True, float("nan")
        elif not np.array_equal(g, w):
            return True, float("nan")
    return False, err
