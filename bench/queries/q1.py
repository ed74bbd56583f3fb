"""TPC-H Q1, the pricing summary report (specification clause 2.4.1), with
its validation parameter DELTA = 90 days."""

from __future__ import annotations

import numpy as np

from ..common import column_bytes, compare_rows, decimal, group_sums
from ..datagen.tpch import day

SQL = """
SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty,
       avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-09-02'
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

COLUMNS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax"]}
EXACT = ["l_returnflag", "l_linestatus", "count_order"]
FLOATS = ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
          "avg_qty", "avg_price", "avg_disc"]
# Largest relative gap of any aggregate, and exact cells that differ.
# Readings and the reason for each limit: PERF.md section 2.
LIMITS = {"q1_rel_err": 1e-9, "q1_mismatch": 0}


def reference(data: dict, dtype=np.float64) -> dict:
    li = data["lineitem"]
    keep = li["l_shipdate"].data <= day("1998-09-02")
    rf, ls = li["l_returnflag"], li["l_linestatus"]
    n_ls = len(ls.heap)
    # heaps are sorted, so ascending codes are ascending strings
    gid = rf.data[keep].astype(np.int64) * n_ls + ls.data[keep]
    one = dtype(1)
    qty = li["l_quantity"].data[keep].astype(dtype)
    price = decimal(li["l_extendedprice"], dtype)[keep]
    disc = li["l_discount"].data[keep].astype(dtype)
    tax = li["l_tax"].data[keep].astype(dtype)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    keys, counts, (s_qty, s_price, s_disc_price, s_charge, s_disc) = \
        group_sums(gid, [qty, price, disc_price, charge, disc], dtype)
    n = counts.astype(dtype)
    return {"l_returnflag": np.asarray(rf.heap, object)[keys // n_ls],
            "l_linestatus": np.asarray(ls.heap, object)[keys % n_ls],
            "sum_qty": s_qty, "sum_base_price": s_price,
            "sum_disc_price": s_disc_price, "sum_charge": s_charge,
            "avg_qty": s_qty / n, "avg_price": s_price / n,
            "avg_disc": s_disc / n, "count_order": counts}


def logical_bytes(data: dict) -> int:
    return column_bytes(data, COLUMNS)


def compare(got: dict, want: dict) -> dict:
    return compare_rows(got, want, EXACT, FLOATS, "q1")
