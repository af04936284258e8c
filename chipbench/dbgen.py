"""The benchmark's TPC-H data: all eight tables at every column of the
TPC-H specification (v3, section 1.4), drawn as its ``dbgen`` draws them
(section 4.2.3), from the seed.

Nothing here imports the program. What follows dbgen, per table:

* keys start at 1; ``o_orderkey`` is dbgen's sparse key (8 of every 32);
  every order has 1 to 7 lineitems, numbered from 1, stored in order;
* ``o_custkey`` skips every third customer, which places no orders;
* ``l_suppkey`` and ``ps_suppkey`` follow from the part key by dbgen's
  formula, so each part has 4 suppliers and a lineitem uses one of them;
* ``p_retailprice`` follows from the part key, ``l_extendedprice`` is
  quantity times it, ``o_totalprice`` sums its lines' charges;
* ship, commit and receipt dates follow from the order date;
  ``l_returnflag`` and ``l_linestatus`` from the receipt and ship dates
  against the current date 1995-06-17, ``o_orderstatus`` from its lines'
  statuses;
* names, phones, brands, containers and types as the specification
  builds them.

Text columns (comments, addresses) have dbgen's lengths. A comment is a
slice of a pool of words from dbgen's grammar vocabulary, as dbgen cuts
comments from its text pool; the grammar's sentence structure is not
reproduced (no query reads these columns). Integers and keys are int64,
dates int32 days since 1970-01-01, decimals float64 and strings
fixed-width bytes arrays.
"""
from __future__ import annotations

import datetime

import numpy as np

BASE = {"customer": 150_000, "orders": 1_500_000, "part": 200_000,
        "supplier": 10_000}
_EPOCH = datetime.date(1970, 1, 1)


def days(y, m, d) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


START_DATE = days(1992, 1, 1)
CURRENT_DATE = days(1995, 6, 17)
END_DATE = days(1998, 12, 31)

NATIONS = [b"ALGERIA", b"ARGENTINA", b"BRAZIL", b"CANADA", b"EGYPT",
           b"ETHIOPIA", b"FRANCE", b"GERMANY", b"INDIA", b"INDONESIA",
           b"IRAN", b"IRAQ", b"JAPAN", b"JORDAN", b"KENYA", b"MOROCCO",
           b"MOZAMBIQUE", b"PERU", b"CHINA", b"ROMANIA", b"SAUDI ARABIA",
           b"VIETNAM", b"RUSSIA", b"UNITED KINGDOM", b"UNITED STATES"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1]
REGIONS = [b"AFRICA", b"AMERICA", b"ASIA", b"EUROPE", b"MIDDLE EAST"]
SEGMENTS = [b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"HOUSEHOLD",
            b"MACHINERY"]
PRIORITIES = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW"]
SHIPMODES = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]
INSTRUCTIONS = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}".encode() for a in
         ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}".encode() for a in
              ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage "
    "hinder print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring brave "
    "stealthy permanent enticing idle busy regular final ironic even bold "
    "silent sometimes always never furiously slyly carefully blithely "
    "quickly fluffily slowly quietly ruthlessly thinly closely doggedly "
    "daringly bravely stealthily permanently enticingly idly busily "
    "regularly finally ironically evenly boldly silently about above "
    "according to across after against along alongside of among around at "
    "atop before behind beneath beside besides between beyond by despite "
    "during except for from in place of inside instead of into near on "
    "outside over past since through throughout toward under until up upon "
    "without with within do may might shall will would can could should "
    "ought must need try").split()
_ALNUM = (b"0123456789abcdefghijklmnopqrstuvwxyz"
          b"ABCDEFGHIJKLMNOPQRSTUVWXYZ, ")

# the enumerated string columns' domains, sorted: the program stores
# them dictionary-encoded over the whole domain
DOMAINS = {
    "l_returnflag": [b"A", b"N", b"R"], "l_linestatus": [b"F", b"O"],
    "l_shipinstruct": sorted(INSTRUCTIONS), "l_shipmode": sorted(SHIPMODES),
    "o_orderstatus": [b"F", b"O", b"P"],
    "o_orderpriority": sorted(PRIORITIES),
    "c_mktsegment": sorted(SEGMENTS),
    "p_mfgr": [b"Manufacturer#%d" % m for m in range(1, 6)],
    "p_brand": [b"Brand#%d%d" % (m, n) for m in range(1, 6)
                for n in range(1, 6)],
    "p_type": sorted(TYPES), "p_container": sorted(CONTAINERS),
    "n_name": sorted(NATIONS), "r_name": sorted(REGIONS),
}


def counts(sf: float) -> dict[str, int]:
    """Rows of each table at scale factor ``sf`` (lineitem's is drawn)."""
    return {t: max(int(n * sf), 5) for t, n in BASE.items()}


def _pool(rng, words, nbytes: int) -> bytes:
    """Text to cut slices from: random ``words`` joined by spaces."""
    n = nbytes // 6 + 16
    pick = rng.integers(0, len(words), n)
    return b" ".join(words[i] for i in pick.tolist())[:nbytes]


def _slices(rng, pool: bytes, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` strings of lengths uniform in [lo, hi], each cut from
    ``pool`` at a random offset."""
    lens = rng.integers(lo, hi + 1, n)
    offs = rng.integers(0, len(pool) - hi, n)
    return np.asarray([pool[o:o + k] for o, k in
                       zip(offs.tolist(), lens.tolist())], f"S{hi}")


def _text(rng, pool, n, avg):
    """dbgen's comment: length uniform in [0.4, 1.6] x ``avg``."""
    return _slices(rng, pool, n, int(0.4 * avg), int(1.6 * avg))


def _address(rng, pool, n):
    return _slices(rng, pool, n, 10, 40)


def _phone(rng, nationkey: np.ndarray) -> np.ndarray:
    n = len(nationkey)
    a, b, c = (rng.integers(100, 1000, n), rng.integers(100, 1000, n),
               rng.integers(1000, 10000, n))
    return np.asarray([b"%02d-%03d-%03d-%04d" % v for v in zip(
        (nationkey + 10).tolist(), a.tolist(), b.tolist(), c.tolist())],
        "S15")


def _numbered(prefix: bytes, keys: np.ndarray) -> np.ndarray:
    return np.asarray([prefix + b"#%09d" % k for k in keys.tolist()],
                      f"S{len(prefix) + 10}")


def _pick(rng, values, n) -> np.ndarray:
    return np.asarray(values)[rng.integers(0, len(values), n)]


def _money(rng, lo_cents, hi_cents, n) -> np.ndarray:
    return rng.integers(lo_cents, hi_cents + 1, n) / 100.0


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int):
    """The ``i``-th (0..3) supplier of each part, by dbgen's formula."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def generate(sf: float, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """All eight tables at scale factor ``sf``: name -> column -> array,
    columns in the specification's order."""
    rng = np.random.default_rng(seed)
    n = counts(sf)
    text = _pool(rng, [w.encode() for w in WORDS], 1 << 21)
    chars = bytes(_ALNUM[i] for i in
                  rng.integers(0, len(_ALNUM), 1 << 18).tolist())

    region = {"r_regionkey": np.arange(5, dtype=np.int64),
              "r_name": np.asarray(REGIONS),
              "r_comment": _text(rng, text, 5, 72)}
    nation = {"n_nationkey": np.arange(25, dtype=np.int64),
              "n_name": np.asarray(NATIONS),
              "n_regionkey": np.asarray(NATION_REGION, np.int64),
              "n_comment": _text(rng, text, 25, 72)}

    s_key = np.arange(1, n["supplier"] + 1, dtype=np.int64)
    s_nat = rng.integers(0, 25, len(s_key)).astype(np.int64)
    supplier = {"s_suppkey": s_key,
                "s_name": _numbered(b"Supplier", s_key),
                "s_address": _address(rng, chars, len(s_key)),
                "s_nationkey": s_nat,
                "s_phone": _phone(rng, s_nat),
                "s_acctbal": _money(rng, -99999, 999999, len(s_key)),
                "s_comment": _text(rng, text, len(s_key), 63)}

    c_key = np.arange(1, n["customer"] + 1, dtype=np.int64)
    c_nat = rng.integers(0, 25, len(c_key)).astype(np.int64)
    customer = {"c_custkey": c_key,
                "c_name": _numbered(b"Customer", c_key),
                "c_address": _address(rng, chars, len(c_key)),
                "c_nationkey": c_nat,
                "c_phone": _phone(rng, c_nat),
                "c_acctbal": _money(rng, -99999, 999999, len(c_key)),
                "c_mktsegment": _pick(rng, SEGMENTS, len(c_key)),
                "c_comment": _text(rng, text, len(c_key), 73)}

    p_key = np.arange(1, n["part"] + 1, dtype=np.int64)
    n_part = len(p_key)
    colors = np.argsort(rng.random((n_part, len(COLORS))), axis=1)[:, :5]
    cw = [c.encode() for c in COLORS]
    mfgr = rng.integers(1, 6, n_part)
    part = {"p_partkey": p_key,
            "p_name": np.asarray([b" ".join(cw[i] for i in row)
                                  for row in colors.tolist()], "S55"),
            "p_mfgr": np.asarray([b"Manufacturer#%d" % m
                                  for m in mfgr.tolist()], "S14"),
            "p_brand": np.asarray([b"Brand#%d%d" % (m, b) for m, b in zip(
                mfgr.tolist(), rng.integers(1, 6, n_part).tolist())], "S8"),
            "p_type": _pick(rng, TYPES, n_part),
            "p_size": rng.integers(1, 51, n_part).astype(np.int64),
            "p_container": _pick(rng, CONTAINERS, n_part),
            "p_retailprice": retail_cents(p_key) / 100.0,
            "p_comment": _text(rng, text, n_part, 14)}

    ps_part = np.repeat(p_key, 4)
    n_ps = len(ps_part)
    partsupp = {"ps_partkey": ps_part,
                "ps_suppkey": supplier_of(ps_part, np.tile(np.arange(4),
                                                           n_part),
                                          len(s_key)),
                "ps_availqty": rng.integers(1, 10000, n_ps).astype(np.int64),
                "ps_supplycost": _money(rng, 100, 100000, n_ps),
                "ps_comment": _text(rng, text, n_ps, 124)}

    n_ord = n["orders"]
    idx = np.arange(1, n_ord + 1, dtype=np.int64)
    o_key = ((idx >> 3) << 5) | (idx & 7)
    buyers = c_key[c_key % 3 != 0]
    o_date = rng.integers(START_DATE, END_DATE - 151 + 1,
                          n_ord).astype(np.int32)
    lines = rng.integers(1, 8, n_ord)
    n_li = int(lines.sum())
    of = np.repeat(np.arange(n_ord), lines)          # each line's order
    first = np.cumsum(lines) - lines
    l_part = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    qty = rng.integers(1, 51, n_li)
    l_ship = o_date[of] + rng.integers(1, 122, n_li).astype(np.int32)
    l_commit = o_date[of] + rng.integers(30, 91, n_li).astype(np.int32)
    l_receipt = l_ship + rng.integers(1, 31, n_li).astype(np.int32)
    price = qty * retail_cents(l_part) / 100.0
    disc = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    flag = np.where(l_receipt <= CURRENT_DATE,
                    np.asarray([b"R", b"A"])[rng.integers(0, 2, n_li)],
                    b"N")
    status = np.where(l_ship > CURRENT_DATE, b"O", b"F")
    lineitem = {
        "l_orderkey": o_key[of],
        "l_partkey": l_part,
        "l_suppkey": supplier_of(l_part, rng.integers(0, 4, n_li),
                                 len(s_key)),
        "l_linenumber": (np.arange(n_li) - first[of] + 1).astype(np.int64),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": flag.astype("S1"),
        "l_linestatus": status.astype("S1"),
        "l_shipdate": l_ship,
        "l_commitdate": l_commit,
        "l_receiptdate": l_receipt,
        "l_shipinstruct": _pick(rng, INSTRUCTIONS, n_li),
        "l_shipmode": _pick(rng, SHIPMODES, n_li),
        "l_comment": _text(rng, text, n_li, 27),
    }
    n_f = np.bincount(of, status == b"F", n_ord)
    charge = np.bincount(of, price * (1 + tax) * (1 - disc), n_ord)
    orders = {
        "o_orderkey": o_key,
        "o_custkey": buyers[rng.integers(0, len(buyers), n_ord)],
        "o_orderstatus": np.where(n_f == lines, b"F",
                                  np.where(n_f == 0, b"O", b"P")
                                  ).astype("S1"),
        "o_totalprice": charge.round(2),
        "o_orderdate": o_date,
        "o_orderpriority": _pick(rng, PRIORITIES, n_ord),
        "o_clerk": _numbered(b"Clerk", rng.integers(
            1, max(int(sf * 1000), 1) + 1, n_ord)),
        "o_shippriority": np.zeros(n_ord, np.int64),
        "o_comment": _text(rng, text, n_ord, 49),
    }
    return {"region": region, "nation": nation, "supplier": supplier,
            "customer": customer, "part": part, "partsupp": partsupp,
            "orders": orders, "lineitem": lineitem}
