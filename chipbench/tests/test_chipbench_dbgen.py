"""The benchmark's TPC-H tables keep dbgen's cardinalities, keys and
value distributions, and reach the program unchanged."""
import numpy as np
import pytest

from chipbench import dbgen, harness

SF = 0.01
SEED = 2**31 + 9


@pytest.fixture(scope="module")
def tables():
    return dbgen.generate(SF, SEED)


def test_cardinalities_and_every_column(tables):
    n = {t: len(next(iter(c.values()))) for t, c in tables.items()}
    assert n["customer"] == 1500 and n["orders"] == 15000
    assert n["part"] == 2000 and n["supplier"] == 100
    assert n["partsupp"] == 4 * n["part"]
    assert n["nation"] == 25 and n["region"] == 5
    widths = {"lineitem": 16, "orders": 9, "customer": 8, "part": 9,
              "supplier": 7, "partsupp": 5, "nation": 4, "region": 3}
    assert {t: len(c) for t, c in tables.items()} == widths


def test_orders_have_one_to_seven_numbered_lines(tables):
    li, od = tables["lineitem"], tables["orders"]
    keys, per_order = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, od["o_orderkey"])
    assert per_order.min() == 1 and per_order.max() == 7
    assert np.all(np.diff(li["l_orderkey"]) >= 0)
    assert li["l_linenumber"].max() == 7
    assert np.sum(li["l_linenumber"] == 1) == len(od["o_orderkey"])


def test_sparse_order_keys_and_customers_without_orders(tables):
    ok = tables["orders"]["o_orderkey"]
    # 8 of every 32 keys: bits 3 and 4 are never set
    assert np.all((ok & 24) == 0) and np.all(np.diff(ok) > 0)
    assert ok[0] == 1 and ok[7] == 32 and ok.max() < 4 * len(ok) + 8
    assert not np.any(tables["orders"]["o_custkey"] % 3 == 0)


def test_lineitem_suppliers_are_their_parts_suppliers(tables):
    li, ps = tables["lineitem"], tables["partsupp"]
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert len(pairs) == len(ps["ps_partkey"])
    assert all(p in pairs for p in zip(li["l_partkey"][:2000].tolist(),
                                       li["l_suppkey"][:2000].tolist()))


def test_prices_and_dates_follow_from_keys(tables):
    li, od, pa = tables["lineitem"], tables["orders"], tables["part"]
    rp = pa["p_retailprice"][li["l_partkey"] - 1]
    assert np.allclose(li["l_extendedprice"], li["l_quantity"] * rp)
    assert pa["p_retailprice"][0] == 901.0
    row = np.searchsorted(od["o_orderkey"], li["l_orderkey"])
    odate = od["o_orderdate"][row]
    assert np.all((li["l_shipdate"] - odate >= 1)
                  & (li["l_shipdate"] - odate <= 121))
    assert np.all((li["l_commitdate"] - odate >= 30)
                  & (li["l_commitdate"] - odate <= 90))
    gap = li["l_receiptdate"] - li["l_shipdate"]
    assert gap.min() == 1 and gap.max() == 30
    charge = np.bincount(row, li["l_extendedprice"] * (1 + li["l_tax"])
                         * (1 - li["l_discount"]), len(row))
    assert np.allclose(od["o_totalprice"], charge[:len(od["o_orderkey"])],
                       atol=0.006)


def test_flags_follow_from_the_dates(tables):
    li, od = tables["lineitem"], tables["orders"]
    late = li["l_receiptdate"] > dbgen.CURRENT_DATE
    assert np.all((li["l_returnflag"] == b"N") == late)
    assert np.all((li["l_linestatus"] == b"O")
                  == (li["l_shipdate"] > dbgen.CURRENT_DATE))
    q1 = li["l_shipdate"] <= dbgen.days(1998, 9, 2) - 90
    groups = set(zip(li["l_returnflag"][q1].tolist(),
                     li["l_linestatus"][q1].tolist()))
    assert groups == {(b"A", b"F"), (b"N", b"F"), (b"N", b"O"),
                      (b"R", b"F")}
    assert set(od["o_orderstatus"].tolist()) == {b"F", b"O", b"P"}


def test_text_has_dbgens_lengths(tables):
    lens = np.char.str_len(tables["lineitem"]["l_comment"])
    assert lens.min() >= 10 and lens.max() <= 43
    lens = np.char.str_len(tables["partsupp"]["ps_comment"])
    assert lens.min() >= 49 and lens.max() <= 198
    assert tables["customer"]["c_phone"][0][:3] == b"%02d-" % (
        tables["customer"]["c_nationkey"][0] + 10)


def test_same_seed_same_tables():
    a, b = dbgen.generate(0.002, 5), dbgen.generate(0.002, 5)
    c = dbgen.generate(0.002, 6)
    for t in a:
        for col in a[t]:
            assert np.array_equal(a[t][col], b[t][col])
    assert not np.array_equal(a["lineitem"]["l_partkey"][:100],
                              c["lineitem"]["l_partkey"][:100])


def test_program_tables_hold_the_same_values(tables):
    prog = harness.program_tables(tables)
    for t, cols in tables.items():
        for name, v in cols.items():
            got = prog[t][name]
            if v.dtype.kind == "S":
                assert got.values == sorted(got.values)
                assert np.array_equal(np.asarray(got.values)[got.codes], v)
                if name in dbgen.DOMAINS:
                    assert got.values == dbgen.DOMAINS[name]
            else:
                assert got is v


def test_lineitem_is_one_object_at_64_mib():
    from repro.relational.table import serialize_table
    li = harness.program_tables(
        {"lineitem": dbgen.generate(0.1, SEED)["lineitem"]})["lineitem"]
    size = len(serialize_table(li))
    assert 64 << 20 < size < 96 << 20
    assert round(size / (64 << 20)) == 1
