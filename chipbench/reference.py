"""The plain reference: each query's answer over the benchmark's tables.

Nothing here imports the program. The tables are ``dbgen.generate``'s,
from the seed; the program is given the same rows. ``answer`` computes
each of the six supported queries straight from its SQL meaning, in
numpy over whole tables, with no plan, store, shuffle or device:

* q1  — lineitem shipped by 1998-09-02 minus 90 days, grouped by
  (returnflag, linestatus): sum of quantity, price and discounted price,
  average quantity, row count; ordered by the two flags.
* q3  — BUILDING customers' orders before 1995-03-15 with lineitems
  shipped after it, grouped by (orderkey, orderdate, shippriority):
  revenue; the 10 largest by revenue, then orderdate.
* q5  — ASIA customers' orders of 1994 joined with their lineitems
  whose supplier is of the customer's nation, grouped by nation name:
  revenue, largest first.
* q6  — lineitem shipped in 1994 with discount in [0.05, 0.07] and
  quantity < 24: sum of price x discount.
* q12 — MAIL and SHIP lineitems committed before receipt, shipped before
  commit and received in 1994, joined with their orders, grouped by
  shipmode: lines of 1-URGENT/2-HIGH orders and of the others.
* q14 — lineitem shipped in September 1995 joined with part: the
  discounted revenue of PROMO parts and of all parts.

These are the program's plans' meanings (its q1 and q14 return fewer
columns than the TPC-H spec's, and q14 the two sums rather than their
ratio). ``dtype`` is the float type every float column and sum is
computed in: float64 is the configuration's precision, float32 the
control that has to come out as not correct.

Any other query's answer is ``answer(tables, dtype)`` of
``answers/<query>.py``, found by name as a metric's reader is.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

from chipbench.dbgen import days as _days


# ---------------------------------------------------------------------------
# query answers
# ---------------------------------------------------------------------------

def _lookup(fk: np.ndarray, pk: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Row of each foreign key in a table with unique primary keys
    ``pk``, and whether it exists there."""
    order = np.argsort(pk, kind="stable")
    pos = np.clip(np.searchsorted(pk[order], fk), 0, len(pk) - 1)
    row = order[pos]
    return row, pk[row] == fk


def _group_sums(keys: list[np.ndarray], values: list[np.ndarray], dtype):
    """Distinct key tuples in ascending order and, per value column, its
    sum over each group accumulated in ``dtype``."""
    if not len(keys[0]):
        return [k[:0] for k in keys], [np.zeros(0, dtype) for _ in values]
    order = np.lexsort(keys[::-1])
    sk = [k[order] for k in keys]
    new = np.ones(len(order), bool)
    new[1:] = np.logical_or.reduce([k[1:] != k[:-1] for k in sk])
    gid = np.cumsum(new) - 1
    sums = []
    for v in values:
        acc = np.zeros(int(gid[-1]) + 1, dtype)
        np.add.at(acc, gid, np.asarray(v, dtype)[order])
        sums.append(acc)
    return [k[new] for k in sk], sums


def _disc_price(li, rows, dtype):
    price = li["l_extendedprice"][rows].astype(dtype)
    return price * (dtype(1.0) - li["l_discount"][rows].astype(dtype))


def _q1(t, dtype):
    li = t["lineitem"]
    rows = np.nonzero(li["l_shipdate"] <= _days(1998, 9, 2) - 90)[0]
    qty = li["l_quantity"][rows].astype(dtype)
    price = li["l_extendedprice"][rows].astype(dtype)
    keys, (s_qty, s_price, s_disc, count) = _group_sums(
        [li["l_returnflag"][rows], li["l_linestatus"][rows]],
        [qty, price, _disc_price(li, rows, dtype), np.ones(len(rows))],
        dtype)
    return {"l_returnflag": keys[0], "l_linestatus": keys[1],
            "sum_qty": s_qty, "sum_base_price": s_price,
            "sum_disc_price": s_disc, "avg_qty": s_qty / count,
            "count_order": count}


def _q3(t, dtype):
    d = _days(1995, 3, 15)
    cu, od, li = t["customer"], t["orders"], t["lineitem"]
    o = np.nonzero(od["o_orderdate"] < d)[0]
    c_row, c_ok = _lookup(od["o_custkey"][o], cu["c_custkey"])
    o = o[c_ok & (cu["c_mktsegment"][c_row] == b"BUILDING")]
    rows = np.nonzero(li["l_shipdate"] > d)[0]
    o_row, o_ok = _lookup(li["l_orderkey"][rows], od["o_orderkey"][o])
    rows, o_row = rows[o_ok], o[o_row[o_ok]]
    keys, (rev,) = _group_sums(
        [li["l_orderkey"][rows], od["o_orderdate"][o_row],
         od["o_shippriority"][o_row]],
        [_disc_price(li, rows, dtype)], dtype)
    top = np.lexsort((keys[1], -rev.astype(np.float64)))[:10]
    return {"l_orderkey": keys[0][top], "o_orderdate": keys[1][top],
            "o_shippriority": keys[2][top], "revenue": rev[top]}


def _q5(t, dtype):
    cu, od, li = t["customer"], t["orders"], t["lineitem"]
    na, re, su = t["nation"], t["region"], t["supplier"]
    n_row, _ = _lookup(cu["c_nationkey"], na["n_nationkey"])
    r_row, _ = _lookup(na["n_regionkey"][n_row], re["r_regionkey"])
    asia = re["r_name"][r_row] == b"ASIA"
    o = np.nonzero((od["o_orderdate"] >= _days(1994, 1, 1))
                   & (od["o_orderdate"] < _days(1995, 1, 1)))[0]
    c_row, c_ok = _lookup(od["o_custkey"][o], cu["c_custkey"])
    keep = c_ok & asia[c_row]
    o, c_row = o[keep], c_row[keep]
    o_row, o_ok = _lookup(li["l_orderkey"], od["o_orderkey"][o])
    rows = np.nonzero(o_ok)[0]
    cust = c_row[o_row[rows]]
    s_row, s_ok = _lookup(li["l_suppkey"][rows], su["s_suppkey"])
    same = s_ok & (su["s_nationkey"][s_row] == cu["c_nationkey"][cust])
    rows, cust = rows[same], cust[same]
    names = na["n_name"][n_row[cust]]
    keys, (rev,) = _group_sums([names], [_disc_price(li, rows, dtype)],
                               dtype)
    order = np.argsort(-rev.astype(np.float64), kind="stable")
    return {"n_name": keys[0][order], "revenue": rev[order]}


def _q6(t, dtype):
    li = t["lineitem"]
    disc = li["l_discount"]
    rows = np.nonzero((li["l_shipdate"] >= _days(1994, 1, 1))
                      & (li["l_shipdate"] < _days(1995, 1, 1))
                      & (disc >= 0.05) & (disc <= 0.07)
                      & (li["l_quantity"] < 24))[0]
    v = li["l_extendedprice"][rows].astype(dtype) * disc[rows].astype(dtype)
    acc = np.zeros(1, dtype)
    np.add.at(acc, np.zeros(len(rows), np.int64), v)
    return {"revenue": acc}


def _q12(t, dtype):
    li, od = t["lineitem"], t["orders"]
    lo, hi = _days(1994, 1, 1), _days(1995, 1, 1)
    mode = li["l_shipmode"]
    rows = np.nonzero(((mode == b"MAIL") | (mode == b"SHIP"))
                      & (li["l_commitdate"] < li["l_receiptdate"])
                      & (li["l_shipdate"] < li["l_commitdate"])
                      & (li["l_receiptdate"] >= lo)
                      & (li["l_receiptdate"] < hi))[0]
    o_row, o_ok = _lookup(li["l_orderkey"][rows], od["o_orderkey"])
    rows, o_row = rows[o_ok], o_row[o_ok]
    prio = od["o_orderpriority"][o_row]
    high = (prio == b"1-URGENT") | (prio == b"2-HIGH")
    keys, (h, low) = _group_sums([mode[rows]], [high, ~high], dtype)
    return {"l_shipmode": keys[0], "high_line_count": h,
            "low_line_count": low}


def _q14(t, dtype):
    li, pa = t["lineitem"], t["part"]
    rows = np.nonzero((li["l_shipdate"] >= _days(1995, 9, 1))
                      & (li["l_shipdate"] < _days(1995, 10, 1)))[0]
    p_row, p_ok = _lookup(li["l_partkey"][rows], pa["p_partkey"])
    rows, p_row = rows[p_ok], p_row[p_ok]
    promo = np.char.startswith(pa["p_type"][p_row], b"PROMO")
    v = _disc_price(li, rows, dtype)
    sums = []
    for w in (np.where(promo, v, dtype(0.0)), v):
        acc = np.zeros(1, dtype)
        np.add.at(acc, np.zeros(len(rows), np.int64), w)
        sums.append(acc)
    return {"promo": sums[0], "total": sums[1]}


ANSWERS = {"q1": _q1, "q3": _q3, "q5": _q5, "q6": _q6, "q12": _q12,
           "q14": _q14}
ANSWERS_DIR = pathlib.Path(__file__).resolve().parent / "answers"


def answerer(query: str):
    """``answer(tables, dtype)`` of the query: one of ``ANSWERS``, else
    that of ``answers/<query>.py``. KeyError for a query with neither."""
    if query in ANSWERS:
        return ANSWERS[query]
    path = ANSWERS_DIR / f"{query}.py"
    if not path.is_file():
        raise KeyError(f"no answer for {query!r}: not one of "
                       f"{sorted(ANSWERS)} and no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_answer_{query}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.answer


def answer(query: str, tables: dict, dtype=np.float64) -> dict:
    """The query's result: column name -> array, rows in its ORDER BY."""
    return answerer(query)(tables, dtype)
