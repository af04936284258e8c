"""Device path of the worker operators (relational.device_ops) against the
numpy reference (relational.ops): same rows, same order, same bytes."""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.relational import device_ops as D
from repro.relational import ops as OPS
from repro.relational.table import (DictColumn, Table, partitions_to_object,
                                    serialize_table)
from repro.relational.tpch import QUERIES, generate

I64 = np.iinfo(np.int64)


def _table(rng, n, key_hi=5):
    return Table({
        "k": rng.integers(-key_hi, key_hi, n).astype(np.int64),
        "a": rng.uniform(-10, 10, n).round(2),
        "b": rng.integers(0, 11, n) / 100.0,
        "e": rng.integers(0, 9, n).astype(np.int32),
        "d": DictColumn(rng.integers(0, 4, n).astype(np.uint32),
                        [b"AIR", b"MAIL", b"SHIP", b"TRUCK"]),
    })


def _build(rng, m, key_hi=5, dup=3):
    keys = np.repeat(rng.integers(-key_hi, key_hi, m), dup)[:m]
    return Table({"bk": keys.astype(np.int64),
                  "v": rng.uniform(0, 1, m),
                  "s": DictColumn(rng.integers(0, 3, m).astype(np.uint32),
                                  [b"x", b"y", b"z"])})


def _reference(t, ops, builds, partition):
    """The worker's semantics on the numpy reference operators."""
    for op in ops:
        if op["op"] == "join":
            t = OPS.op_join(t, builds[op["table"]], op["lkey"], op["rkey"])
        else:
            t = OPS.apply_ops(t, [op], builds.__getitem__)
    if partition is None:
        return t
    if not len(t):
        return [Table({})] * partition[1]
    return OPS.op_partition(t, *partition)


def _bytes(out):
    return partitions_to_object(out) if isinstance(out, list) \
        else serialize_table(out)


AGGS = [["s", "sum", {"fn": "mul", "args": [
            "a", {"fn": "one_minus", "args": ["b"]}]}],
        ["m", "avg", "a"], ["lo", "min", "a"], ["hi", "max", "e"],
        ["c", "count", None]]
PIPELINES = [
    # scan: predicate with codes, computed column, grouped partial agg
    ([{"op": "filter", "pred": {"fn": "and", "args": [
         {"fn": "in", "args": ["d", {"code": ["d", "MAIL"]},
                               {"code": ["d", "SHIP"]}]},
         {"fn": "lt", "args": ["b", 0.07]}]}},
      {"op": "compute", "name": "x", "expr": {"fn": "add", "args": [
          "e", {"const": 1}]}},
      {"op": "partial_agg", "keys": ["d", "k"], "aggs": AGGS}], None),
    # many-to-many join (overflows its first capacity), then partition
    ([{"op": "join", "table": "B", "lkey": "k", "rkey": "bk"},
      {"op": "filter", "pred": {"fn": "ne", "args": [
          "s", {"code": ["s", "y"]}]}}], ("k", 5)),
    # projection then partition; keyless aggregate (one group, even empty)
    ([{"op": "project", "columns": ["k", "d", "a"]}], ("k", 3)),
    ([{"op": "filter", "pred": {"fn": "gt", "args": ["a", 100]}},
      {"op": "partial_agg", "keys": [], "aggs": AGGS}], None),
]
# row counts on both sides of the padding buckets' boundaries
SIZES = [0, 1, 1023, 1024, 1025]


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(SIZES),
       st.sampled_from(range(len(PIPELINES))))
def test_device_ops_match_reference(seed, n, which):
    rng = np.random.default_rng(seed)
    t = _table(rng, n)
    builds = {"B": _build(rng, 1 + n // 2)}
    ops, partition = PIPELINES[which]
    got = D.run(t, ops, builds, partition)
    want = _reference(t, ops, builds, partition)
    assert _bytes(got) == _bytes(want)


# repeated value columns within a combiner: sum and avg of one
# expression, count beside an avg's count, min and max over two columns
DUP_EXPR = {"fn": "mul", "args": ["a", {"fn": "one_minus", "args": ["b"]}]}
DUP_AGGS = [["s", "sum", DUP_EXPR], ["m", "avg", DUP_EXPR],
            ["c", "count", None], ["q", "avg", "a"], ["lo_a", "min", "a"],
            ["lo_e", "min", "e"], ["hi_a", "max", "a"], ["hi_b", "max", "b"]]


@pytest.mark.parametrize("keys", [["d", "k"], []])
@pytest.mark.parametrize("n", [0, 1, 1023, 1024, 1025])
def test_repeated_value_columns_match_reference(keys, n):
    t = _table(np.random.default_rng(100 + n), n)
    ops = [{"op": "filter", "pred": {"fn": "lt", "args": ["b", 0.07]}},
           {"op": "partial_agg", "keys": keys, "aggs": DUP_AGGS}]
    got = D.run(t, ops, {})
    assert _bytes(got) == _bytes(_reference(t, ops, {}, None))


_SCATTER = re.compile(r'"stablehlo\.scatter".*?unique_indices = (\w+)\}>'
                      r'.*?\}\) : \([^)]*\) -> tensor<([^>]*)>', re.S)


def _segment_scatters(t, ops, cap=1024):
    """Result types of the float64 scatters with non-unique indices in
    the lowered task program: the aggregate's segment reductions."""
    spec, _, _ = D._spec(ops, t, {}, None, cap)
    with jax.enable_x64(True):
        cols = {n: jax.ShapeDtypeStruct(
                    (cap,), (c.codes if isinstance(c, DictColumn)
                             else np.asarray(c)).dtype)
                for n, c in t.cols.items()}
        text = D._program.lower(cols, np.int32(len(t)), {}, np.uint64(1),
                                spec=spec).as_text()
    return sorted(ty for unique, ty in _SCATTER.findall(text)
                  if unique == "false" and ty.endswith("f64"))


def _scan_task(query):
    st = next(s for s in QUERIES[query]()["stages"] if s["name"] == "scan_agg")
    return generate(0.001, seed=0)["lineitem"].project(st["columns"]), \
        st["ops"]


@pytest.mark.parametrize("task, want", [
    # six output columns over four distinct value columns: one scatter
    ("q1", ["1024x4xf64"]),
    # one column is a scatter of one-value rows
    ("q6", ["1024x1xf64"]),
    # add over three distinct columns (count shares avg's ones); min, max
    ("AGGS", ["1024x1xf64", "1024x1xf64", "1024x3xf64"]),
])
def test_one_segment_scatter_per_combiner(task, want):
    if task == "AGGS":
        t = _table(np.random.default_rng(0), 100)
        ops = [{"op": "partial_agg", "keys": ["d", "k"], "aggs": AGGS}]
    else:
        t, ops = _scan_task(task)
    assert _segment_scatters(t, ops) == want


def test_splitmix64_bit_identical():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.integers(I64.min, I64.max, 4096, np.int64),
                        np.array([I64.min, I64.max, 0, -1, 1], np.int64)])
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(D.splitmix64)(x))
    np.testing.assert_array_equal(got, OPS._splitmix64(x))


def test_partition_keeps_numpy_row_order():
    t = _table(np.random.default_rng(1), 5000, key_hi=1000)
    got = D.run(t, [], {}, ("k", 7))
    want = OPS.op_partition(t, "k", 7)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["a"], w["a"])
        np.testing.assert_array_equal(g["d"].codes, w["d"].codes)


def test_groups_in_np_unique_order():
    t = _table(np.random.default_rng(2), 3000, key_hi=50)
    op = {"op": "partial_agg", "keys": ["k", "e"], "aggs": AGGS}
    got = D.run(t, [op], {})
    want = OPS.op_aggregate(t, op["keys"], [tuple(a) for a in AGGS])
    assert got.column_names() == want.column_names()
    for n in want.column_names():
        np.testing.assert_array_equal(got[n], want[n])


@pytest.mark.parametrize("n", [1024, 4096])
def test_radix_order_is_stable_lexsort(n):
    rng = np.random.default_rng(n)
    k1 = rng.choice(np.array([I64.min, -7, 0, 3, I64.max]), n)
    k2 = rng.integers(-2 ** 40, 2 ** 40, n)
    k3 = np.zeros(n, np.int64)                      # a span of zero bits
    valid = rng.random(n) < 0.8
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(D._order)(valid, [k1, k2, k3]))
    idx = np.nonzero(valid)[0]
    want = idx[np.lexsort((k3[idx], k2[idx], k1[idx]))]    # stable
    np.testing.assert_array_equal(
        got, np.concatenate([want, np.nonzero(~valid)[0]]))


def test_x64_scope_is_per_thread():
    """Worker threads run device ops under 64-bit types while another
    thread keeps JAX's 32-bit defaults."""
    t = _table(np.random.default_rng(3), 2000)
    op = {"op": "partial_agg", "keys": ["d"], "aggs": AGGS}
    want = _bytes(OPS.op_aggregate(t, ["d"], [tuple(a) for a in AGGS]))
    stop = threading.Event()
    outs, dtypes = [], []

    def work():
        for _ in range(3):
            outs.append(_bytes(D.run(t, [op], {})))

    def watch():
        while not stop.is_set():
            dtypes.append(jnp.arange(3).dtype)
    workers = [threading.Thread(target=work) for _ in range(4)]
    watcher = threading.Thread(target=watch)
    watcher.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    stop.set()
    watcher.join(timeout=10)
    assert not any(w.is_alive() for w in workers + [watcher])
    assert outs == [want] * 12
    assert dtypes and set(dtypes) == {jnp.dtype("int32")}
