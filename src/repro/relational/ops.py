"""Relational operators (paper §4.1): data-centric, vectorized numpy.

This module is the reference semantics of the operators and nothing else
on the worker path: ``engine.oracle`` runs a query through ``apply_ops``
and ``op_join`` here, independently of the workers, whose per-row work
runs as one jitted device program per task (``relational.device_ops``).
The coordinator-side final stage still uses ``merge_partials`` and
``op_sort_limit`` over the few-row partial aggregates.

Expression mini-language (JSON-able), used by predicates and projections:
  column:      "l_quantity"
  constant:    {"const": 24}
  dict code:   {"code": ["l_shipmode", "MAIL"]}    (string -> code at compile)
  arithmetic:  {"fn": "mul", "args": [...]}        add|sub|mul|one_minus|one_plus
  comparison:  {"fn": "lt",  "args": [...]}        lt|le|gt|ge|eq|ne|in|and|or|not
"""
from __future__ import annotations

import numpy as np

from repro.relational.table import DictColumn, Table

_BIN = {"add": np.add, "sub": np.subtract, "mul": np.multiply,
        "lt": np.less, "le": np.less_equal, "gt": np.greater,
        "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal,
        "and": np.logical_and, "or": np.logical_or}


def eval_expr(t: Table, e):
    if isinstance(e, str):
        c = t[e]
        return c.codes if isinstance(c, DictColumn) else c
    if isinstance(e, (int, float)):
        return e
    if "const" in e:
        return e["const"]
    if "code" in e:
        col, val = e["code"]
        c = t[col]
        assert isinstance(c, DictColumn), col
        return c.code_of(val.encode() if isinstance(val, str) else val)
    fn = e["fn"]
    args = [eval_expr(t, a) for a in e["args"]]
    if fn == "one_minus":
        return 1.0 - args[0]
    if fn == "one_plus":
        return 1.0 + args[0]
    if fn == "not":
        return np.logical_not(args[0])
    if fn == "in":
        col = args[0]
        vals = args[1:]
        m = np.zeros(np.shape(col), bool)
        for v in vals:
            m |= np.equal(col, v)
        return m
    return _BIN[fn](*args)


def op_filter(t: Table, pred) -> Table:
    return t.filter(np.asarray(eval_expr(t, pred), bool))


def op_project(t: Table, columns: list[str]) -> Table:
    return t.project(columns)


def op_compute(t: Table, name: str, expr) -> Table:
    return t.with_column(name, np.asarray(eval_expr(t, expr)))


# ---------------------------------------------------------------------------
# hashing / partitioning
# ---------------------------------------------------------------------------

def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x.astype(np.uint64)
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def hash_key(col: np.ndarray) -> np.ndarray:
    return _splitmix64(np.asarray(col, np.int64))


def op_partition(t: Table, key: str, n: int) -> list[Table]:
    """Hash-partition into n partitions (the shuffle producer side)."""
    h = hash_key(np.asarray(t[key], np.int64)) % np.uint64(n)
    order = np.argsort(h, kind="stable")          # partition-major pack (C2)
    sorted_t = t.take(order)
    hs = h[order]
    bounds = np.searchsorted(hs, np.arange(n + 1, dtype=np.uint64))
    return [sorted_t.take(np.arange(bounds[i], bounds[i + 1]))
            for i in range(n)]


# ---------------------------------------------------------------------------
# joins (paper §4.1: broadcast + partitioned hash joins)
# ---------------------------------------------------------------------------

def op_join(left: Table, right: Table, lkey: str, rkey: str,
            prefix: str = "") -> Table:
    """Inner equi-join, general multiplicity, sort-probe (vectorized).

    Probe side = left; build side = right (the smaller relation, as in the
    paper's hash join: build a table from one partition, probe the other).
    """
    lk = np.asarray(left[lkey], np.int64)
    rk = np.asarray(right[rkey], np.int64)
    order = np.argsort(rk, kind="stable")
    rks = rk[order]
    lo = np.searchsorted(rks, lk, "left")
    hi = np.searchsorted(rks, lk, "right")
    counts = hi - lo
    l_idx = np.repeat(np.arange(len(lk)), counts)
    # right match indices: for row i, order[lo[i]:hi[i]]
    offs = np.repeat(lo, counts)
    within = np.arange(len(offs)) - np.repeat(
        np.cumsum(counts) - counts, counts)
    r_idx = order[offs + within]
    out = {n: (c.take(l_idx) if isinstance(c, DictColumn) else c[l_idx])
           for n, c in left.cols.items()}
    for n, c in right.cols.items():
        name = n if n not in out else prefix + n
        out[name] = c.take(r_idx) if isinstance(c, DictColumn) else c[r_idx]
    return Table(out)


def op_semijoin(left: Table, right: Table, lkey: str, rkey: str) -> Table:
    lk = np.asarray(left[lkey], np.int64)
    rk = np.unique(np.asarray(right[rkey], np.int64))
    idx = np.searchsorted(rk, lk)
    idx = np.clip(idx, 0, len(rk) - 1)
    return left.filter((len(rk) > 0) & (rk[idx] == lk))


# ---------------------------------------------------------------------------
# aggregation (two-phase, §4.1)
# ---------------------------------------------------------------------------

_AGGS = ("sum", "min", "max", "count", "avg")


def op_aggregate(t: Table, keys: list[str], aggs: list[tuple]) -> Table:
    """aggs: (out_name, fn, expr). Partial aggregation: avg -> sum+count."""
    if keys:
        kcols = [np.asarray(t[k].codes if isinstance(t[k], DictColumn)
                            else t[k]) for k in keys]
        combo = np.stack([k.astype(np.int64) for k in kcols], 1)
        uniq, inv = np.unique(combo, axis=0, return_inverse=True)
        ng = len(uniq)
    else:
        inv = np.zeros(len(t), np.int64)
        ng = 1
    out: dict = {}
    for i, k in enumerate(keys):
        c = t[k]
        if isinstance(c, DictColumn):
            out[k] = DictColumn(uniq[:, i].astype(np.uint32), c.values)
        else:
            out[k] = uniq[:, i].astype(np.asarray(c).dtype)
    # segment reductions in f64 (TPC-H sums need double)
    for name, fn, expr in aggs:
        v = eval_expr(t, expr) if expr is not None else np.ones(len(t))
        v = np.asarray(v, np.float64)
        if fn in ("sum", "avg"):
            # float64 even for no rows (bincount returns int64 there)
            out[name] = np.bincount(inv, weights=v, minlength=ng
                                    ).astype(np.float64)
            if fn == "avg":
                out[name + "__count"] = np.bincount(
                    inv, minlength=ng).astype(np.float64)
        elif fn == "count":
            out[name] = np.bincount(inv, minlength=ng).astype(np.float64)
        elif fn == "min":
            acc = np.full(ng, np.inf)
            np.minimum.at(acc, inv, v)
            out[name] = acc
        elif fn == "max":
            acc = np.full(ng, -np.inf)
            np.maximum.at(acc, inv, v)
            out[name] = acc
        else:
            raise ValueError(fn)
    return Table(out)


def apply_ops(t: Table, ops: list, base_reader) -> Table:
    """Run a stage's plan ops in order (``base_reader(name)`` gives a
    broadcast join's small table)."""
    for op in ops:
        kind = op["op"]
        if kind == "filter":
            t = op_filter(t, op["pred"])
        elif kind == "project":
            t = op_project(t, op["columns"])
        elif kind == "compute":
            t = op_compute(t, op["name"], op["expr"])
        elif kind == "partial_agg":
            t = op_aggregate(t, op["keys"], [tuple(a) for a in op["aggs"]])
        elif kind == "broadcast_join":
            t = op_join(t, base_reader(op["table"]), op["lkey"], op["rkey"])
        else:
            raise ValueError(kind)
    return t


def merge_partials(parts: list[Table], keys: list[str],
                   aggs: list[tuple]) -> Table:
    """Final aggregation: reduce partial aggregates (sums/counts add,
    min/min, max/max), then finish avg = sum/count."""
    t = Table.concat(parts)
    if not len(t):
        return t
    merged_aggs = []
    for name, fn, _ in aggs:
        if fn in ("sum", "count"):
            merged_aggs.append((name, "sum", name))
        elif fn == "avg":
            merged_aggs.append((name, "sum", name))
            merged_aggs.append((name + "__count", "sum", name + "__count"))
        else:
            merged_aggs.append((name, fn, name))
    out = op_aggregate(t, keys, merged_aggs)
    for name, fn, _ in aggs:
        if fn == "avg":
            out.cols[name] = out[name] / np.maximum(out[name + "__count"], 1)
            del out.cols[name + "__count"]
    return out


def op_sort_limit(t: Table, by: list[tuple], limit: int | None) -> Table:
    """by: list of (column, ascending)."""
    if not len(t):
        return t
    keys = []
    for col, asc in reversed(by):
        c = t[col]
        v = np.asarray(c.codes if isinstance(c, DictColumn) else c)
        keys.append(v if asc else -v.astype(np.float64))
    order = np.lexsort(keys)
    if limit is not None:
        order = order[:limit]
    return t.take(order)
