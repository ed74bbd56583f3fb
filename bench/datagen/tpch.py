"""TPC-H data for the benchmark, generated in bulk from a seed.

The schema and storage types are those of the repository's
``data/tpch.py``.  Keys, cardinalities, dates, prices and flags follow the
TPC-H specification's clause 4.2.3, as dbgen makes them:

* ``o_orderkey`` is sparse: of every 32 keys the first 8 are used, so at
  scale factor SF the keys span 1 .. SF x 6,000,000;
* every order has 1 to 7 lineitems, stored in orderkey order with
  ``l_linenumber`` 1 .. count; ``o_custkey`` is never a multiple of 3;
* ``l_shipdate`` = orderdate + 1..121 days, ``l_commitdate`` = orderdate +
  30..90, ``l_receiptdate`` = shipdate + 1..30; ``l_linestatus`` is ``O``
  after CURRENTDATE (1995-06-17) and ``F`` otherwise; ``l_returnflag`` is
  ``R`` or ``A`` when received by CURRENTDATE and ``N`` otherwise;
  ``o_orderstatus`` is ``F``/``O`` when all its lines are, else ``P``;
* ``l_extendedprice`` = quantity x ``p_retailprice`` of its part, whose
  price follows from the partkey; ``l_suppkey`` is one of the part's four
  suppliers; ``o_totalprice`` sums its lines' charges.

Free text (names beyond their numbered form, addresses, comments) is not
dbgen's grammar.  The number of lineitems is a function of the scale factor
alone: the per-order counts are one fixed multiset, which the seed only
shuffles, so every seed makes the same amount of work.

Every column is one vectorised numpy call, and every VARCHAR column comes
out already dictionary-encoded, as int32 codes over a sorted heap (code 0
is NULL, code 1 the smallest string), so loading a table never encodes
Python strings row by row.

A column is ``Col(kind, data, scale, heap)``:

* ``kind``  -- the storage type: ``int64``, ``float64``, ``date`` (int32
  days since 1970-01-01), ``decimal`` (int64 scaled by 10**scale) or
  ``varchar`` (int32 codes into ``heap``);
* ``heap``  -- for ``varchar`` only: the heap as a list whose entry 0 is the
  NULL placeholder ``""`` and whose entries 1.. are sorted ascending.

Nothing here imports the engine: the benchmark's references read these
arrays directly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

SF_ROWS = {
    "lineitem": 6_000_000,        # about: 1..7 per order, 4 on average
    "orders": 1_500_000,
    "customer": 150_000,
    "part": 200_000,
    "supplier": 10_000,
    "partsupp": 800_000,
    "nation": 25,
    "region": 5,
}
MIN_ROWS = {"lineitem": 100, "orders": 25, "customer": 10, "part": 10,
            "supplier": 5, "partsupp": 20, "nation": 25, "region": 5}

NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCTS = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}" for a in ("ECONOMY", "LARGE", "MEDIUM", "PROMO",
                                  "SMALL", "STANDARD")
         for b in ("ANODIZED", "BRUSHED", "BURNISHED", "PLATED", "POLISHED")
         for c in ("BRASS", "COPPER", "NICKEL", "STEEL", "TIN")]
CONTAINERS = [f"{a} {b}" for a in ("JUMBO", "LG", "MED", "SM", "WRAP")
              for b in ("BAG", "BOX", "CAN", "CASE", "DRUM", "JAR", "PACK",
                        "PKG")]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]


def day(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date (the DATE storage value)."""
    return int((np.datetime64(iso, "D")
                - np.datetime64("1970-01-01", "D")).astype(np.int64))


STARTDATE = day("1992-01-01")
ENDDATE = day("1998-12-31")
CURRENTDATE = day("1995-06-17")
# the fixed stream the lines-per-order multiset is drawn from
COUNTS_STREAM = 0x7C4


@dataclass
class Col:
    kind: str
    data: np.ndarray
    scale: int = 0
    heap: Optional[list] = None

    def strings(self) -> np.ndarray:
        """Decoded values of a varchar column (for the references)."""
        return np.asarray(self.heap, dtype=object)[self.data]


def rows(table: str, sf: float) -> int:
    return max(MIN_ROWS[table], int(SF_ROWS[table] * sf))


def _rng(seed: int, table: str):
    return np.random.default_rng([seed, sum(map(ord, table)), len(table)])


def _pick(rng, options, n) -> Col:
    """A uniform choice among ``options``, encoded over the sorted heap."""
    heap = [""] + sorted(set(options))
    return Col("varchar", rng.integers(1, len(heap), n, dtype=np.int32),
               heap=heap)


def _const_strings(values) -> Col:
    """A column whose row i holds ``values[i]`` (few rows, or distinct)."""
    uniq = sorted(set(values))
    code = {v: i + 1 for i, v in enumerate(uniq)}
    return Col("varchar",
               np.fromiter((code[v] for v in values), np.int32, len(values)),
               heap=[""] + uniq)


def _numbered(prefix: str, n: int, width: int = 9) -> Col:
    """``prefix`` + the key 1..n zero-padded: already in sorted order."""
    return Col("varchar", np.arange(1, n + 1, dtype=np.int32),
               heap=[""] + [f"{prefix}{i:0{width}d}"
                            for i in range(1, n + 1)])


def _phones(nationkey: np.ndarray) -> Col:
    """``CC-iiiiiii``: the country code (nationkey + 10), then the row
    number, so every phone is distinct."""
    return _const_strings([f"{c + 10}-{i:07d}"
                           for i, c in enumerate(nationkey.tolist())])


def _cents(rng, lo: float, hi: float, n: int) -> Col:
    """DECIMAL(2) uniform on [lo, hi] in whole cents."""
    return Col("decimal", rng.integers(round(lo * 100), round(hi * 100) + 1,
                                       n, dtype=np.int64), scale=2)


def _hundredths(rng, hi: int, n: int) -> Col:
    """FLOAT64 values k/100, k uniform on 0..hi (the repository's schema
    stores l_discount and l_tax as FLOAT64)."""
    return Col("float64", rng.integers(0, hi + 1, n) / 100.0)


def _keys(rng, n_keys: int, n: int) -> Col:
    """Uniform keys 1..n_keys."""
    return Col("int64", rng.integers(1, n_keys + 1, n, dtype=np.int64))


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part, in cents (clause 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int):
    """The i-th (0..3) supplier of each part (PS_SUPPKEY, clause 4.2.3)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def order_keys(n_orders: int) -> np.ndarray:
    """The sparse O_ORDERKEY: the first 8 of every 32 keys."""
    i = np.arange(n_orders, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def order_dates(seed: int, n_orders: int) -> np.ndarray:
    """O_ORDERDATE, uniform on STARTDATE .. ENDDATE - 151 days."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders,
                        dtype=np.int32)


def lines_per_order(seed: int, n_orders: int) -> np.ndarray:
    """1..7 lineitems per order: one multiset for a given number of orders,
    in an order the seed draws."""
    counts = np.random.default_rng(COUNTS_STREAM).integers(
        1, 8, n_orders, dtype=np.int64)
    return np.random.default_rng([seed, 2]).permutation(counts)


def generate(sf: float, seed: int, tables) -> dict[str, dict[str, Col]]:
    """``{table: {column: Col}}`` for the named tables, in schema order.

    Every table draws from its own streams, derived from ``seed`` and the
    table's name, so the rows of a table do not depend on which other
    tables are generated."""
    n = {t: rows(t, sf) for t in SF_ROWS if t != "lineitem"}
    return {t: _TABLES[t](n, seed) for t in tables}


def _region(n, seed):
    return {"r_regionkey": Col("int64", np.arange(5, dtype=np.int64)),
            "r_name": _const_strings(REGIONS),
            "r_comment": _const_strings([f"region comment {i}"
                                         for i in range(5)])}


def _nation(n, seed):
    return {"n_nationkey": Col("int64", np.arange(25, dtype=np.int64)),
            "n_name": _const_strings(NATIONS),
            "n_regionkey": Col("int64", np.asarray(NATION_REGION, np.int64)),
            "n_comment": _const_strings([f"nation comment {i}"
                                         for i in range(25)])}


def _supplier(n, seed):
    rng, k = _rng(seed, "supplier"), n["supplier"]
    nation = rng.integers(0, 25, k, dtype=np.int64)
    return {"s_suppkey": Col("int64", np.arange(1, k + 1, dtype=np.int64)),
            "s_name": _numbered("Supplier#", k),
            "s_address": _const_strings([f"addr{i}" for i in range(k)]),
            "s_nationkey": Col("int64", nation),
            "s_phone": _phones(nation),
            "s_acctbal": _cents(rng, -999.99, 9999.99, k),
            "s_comment": _pick(rng, ["reliable", "Customer Complaints pending",
                                     "quick", "slow"], k)}


def _customer(n, seed):
    rng, k = _rng(seed, "customer"), n["customer"]
    nation = rng.integers(0, 25, k, dtype=np.int64)
    return {"c_custkey": Col("int64", np.arange(1, k + 1, dtype=np.int64)),
            "c_name": _numbered("Customer#", k),
            "c_address": _const_strings([f"caddr{i}" for i in range(k)]),
            "c_nationkey": Col("int64", nation),
            "c_phone": _phones(nation),
            "c_acctbal": _cents(rng, -999.99, 9999.99, k),
            "c_mktsegment": _pick(rng, SEGMENTS, k),
            "c_comment": _pick(rng, ["loyal", "new", "angry"], k)}


def _part(n, seed):
    rng, k = _rng(seed, "part"), n["part"]
    key = np.arange(1, k + 1, dtype=np.int64)
    return {"p_partkey": Col("int64", key),
            "p_name": _pick(rng, ["ivory azure", "blanched chiffon",
                                  "forest green", "ghost lavender",
                                  "antique metallic"], k),
            "p_mfgr": _pick(rng, [f"Manufacturer#{i}" for i in range(1, 6)],
                            k),
            "p_brand": _pick(rng, BRANDS, k),
            "p_type": _pick(rng, TYPES, k),
            "p_size": Col("int64", rng.integers(1, 51, k, dtype=np.int64)),
            "p_container": _pick(rng, CONTAINERS, k),
            "p_retailprice": Col("decimal", retail_cents(key), scale=2),
            "p_comment": _pick(rng, ["fine", "regular", "special"], k)}


def _partsupp(n, seed):
    rng, p, s = _rng(seed, "partsupp"), n["part"], n["supplier"]
    key = np.repeat(np.arange(1, p + 1, dtype=np.int64), 4)
    k = key.size
    return {"ps_partkey": Col("int64", key),
            "ps_suppkey": Col("int64", supplier_of(
                key, np.tile(np.arange(4, dtype=np.int64), p), s)),
            "ps_availqty": Col("int64",
                               rng.integers(1, 10000, k, dtype=np.int64)),
            "ps_supplycost": _cents(rng, 1, 1000, k),
            "ps_comment": _pick(rng, ["stocked", "backordered"], k)}


def _orders(n, seed):
    rng, k = _rng(seed, "orders"), n["orders"]
    li = _lineitem(n, seed)
    counts = lines_per_order(seed, k)
    starts = np.r_[0, np.cumsum(counts)[:-1]]
    n_open = np.add.reduceat((li["l_linestatus"].data == 2).astype(np.int64),
                             starts)
    # heap ["", "F", "O", "P"]
    status = np.where(n_open == counts, 2,
                      np.where(n_open == 0, 1, 3)).astype(np.int32)
    charge = np.round(li["l_extendedprice"].data
                      * (1.0 + li["l_tax"].data)
                      * (1.0 - li["l_discount"].data))
    cust = n["customer"]
    # uniform over the custkeys that are not multiples of 3
    j = rng.integers(0, cust - cust // 3, k, dtype=np.int64)
    return {"o_orderkey": Col("int64", order_keys(k)),
            "o_custkey": Col("int64", (j // 2) * 3 + j % 2 + 1),
            "o_orderstatus": Col("varchar", status,
                                 heap=["", "F", "O", "P"]),
            "o_totalprice": Col("decimal", np.add.reduceat(
                charge, starts).astype(np.int64), scale=2),
            "o_orderdate": Col("date", order_dates(seed, k)),
            "o_orderpriority": _pick(rng, PRIORITIES, k),
            "o_clerk": _pick(rng, [f"Clerk#{i:09d}" for i in
                                   range(1, max(1, k // 1500) + 1)], k),
            "o_shippriority": Col("int64", np.zeros(k, dtype=np.int64)),
            "o_comment": _pick(rng, ["rush", "normal", "special requests"],
                               k)}


def _lineitem(n, seed):
    rng, n_orders = _rng(seed, "lineitem"), n["orders"]
    counts = lines_per_order(seed, n_orders)
    k = int(counts.sum())
    starts = np.r_[0, np.cumsum(counts)[:-1]]
    odate = np.repeat(order_dates(seed, n_orders), counts)
    part = rng.integers(1, n["part"] + 1, k, dtype=np.int64)
    supp = supplier_of(part, rng.integers(0, 4, k, dtype=np.int64),
                       n["supplier"])
    qty = rng.integers(1, 51, k, dtype=np.int64)
    ship = odate + rng.integers(1, 122, k, dtype=np.int32)
    commit = odate + rng.integers(30, 91, k, dtype=np.int32)
    del odate
    receipt = ship + rng.integers(1, 31, k, dtype=np.int32)
    # heaps ["", "A", "N", "R"] and ["", "F", "O"]
    flag = np.where(receipt <= CURRENTDATE,
                    np.where(rng.random(k) < 0.5, 3, 1), 2).astype(np.int32)
    status = np.where(ship > CURRENTDATE, 2, 1).astype(np.int32)
    return {"l_orderkey": Col("int64", np.repeat(order_keys(n_orders),
                                                 counts)),
            "l_partkey": Col("int64", part),
            "l_suppkey": Col("int64", supp),
            "l_linenumber": Col("int64", np.arange(k, dtype=np.int64)
                                - np.repeat(starts, counts) + 1),
            "l_quantity": Col("float64", qty.astype(np.float64)),
            "l_extendedprice": Col("decimal", qty * retail_cents(part),
                                   scale=2),
            "l_discount": _hundredths(rng, 10, k),
            "l_tax": _hundredths(rng, 8, k),
            "l_returnflag": Col("varchar", flag, heap=["", "A", "N", "R"]),
            "l_linestatus": Col("varchar", status, heap=["", "F", "O"]),
            "l_shipdate": Col("date", ship),
            "l_commitdate": Col("date", commit),
            "l_receiptdate": Col("date", receipt),
            "l_shipinstruct": _pick(rng, INSTRUCTS, k),
            "l_shipmode": _pick(rng, SHIPMODES, k),
            "l_comment": _pick(rng, ["quick", "slow", "deposits"], k)}


_TABLES = {"region": _region, "nation": _nation, "supplier": _supplier,
           "customer": _customer, "part": _part, "partsupp": _partsupp,
           "orders": _orders, "lineitem": _lineitem}
