"""TPC-H Q3, the shipping priority query (specification clause 2.4.3), with
its validation parameters: SEGMENT BUILDING, DATE 1995-03-15."""

from __future__ import annotations

import numpy as np

from ..common import (NOT_COMPARABLE, column_bytes, decimal, group_sums,
                      rel_err)
from ..datagen.tpch import day

SQL = """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
"""

LIMIT = 10
COLUMNS = {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"],
           "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                        "l_discount"]}
# Readings and the reason for each limit: PERF.md section 2.
LIMITS = {"q3_rel_err": 1e-9, "q3_mismatch": 0}


def groups(data: dict, dtype=np.float64) -> dict:
    """Every qualifying group: ``{orderkey: (revenue, orderdate,
    shippriority)}``."""
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    seg = cu["c_mktsegment"]
    ckey = cu["c_custkey"].data
    building = np.zeros(int(ckey.max()) + 1, bool)
    building[ckey] = np.asarray(seg.heap, object)[seg.data] == "BUILDING"
    cutoff = day("1995-03-15")
    okeys = od["o_orderkey"].data
    order_ok = np.zeros(int(okeys.max()) + 1, bool)
    order_ok[okeys] = (building[od["o_custkey"].data]
                       & (od["o_orderdate"].data < cutoff))
    okey = li["l_orderkey"].data
    keep = order_ok[okey] & (li["l_shipdate"].data > cutoff)
    one = dtype(1)
    revenue = decimal(li["l_extendedprice"], dtype)[keep] \
        * (one - li["l_discount"].data[keep].astype(dtype))
    keys, _, (sums,) = group_sums(okey[keep], [revenue], dtype)
    by_key = np.argsort(okeys, kind="stable")
    row = by_key[np.searchsorted(okeys, keys, sorter=by_key)]
    date = od["o_orderdate"].data[row]
    prio = od["o_shippriority"].data[row]
    return {int(k): (s, int(d), int(p))
            for k, s, d, p in zip(keys, sums, date, prio)}


def reference(data: dict, dtype=np.float64) -> dict:
    g = groups(data, dtype)
    top = sorted(g.items(), key=lambda kv: (-kv[1][0], kv[1][1]))[:LIMIT]
    return {"l_orderkey": np.array([k for k, _ in top], np.int64),
            "revenue": np.array([v[0] for _, v in top], dtype),
            "o_orderdate": np.array([v[1] for _, v in top], np.int64),
            "o_shippriority": np.array([v[2] for _, v in top], np.int64),
            "_groups": g}


def logical_bytes(data: dict) -> int:
    return column_bytes(data, COLUMNS)


def compare(got: dict, want: dict) -> dict:
    """Top-k with ties: each returned order must be a qualifying group
    with its exact date and priority and its revenue, and the i-th
    revenue returned must be the i-th best, so a better group left out
    or a wrong order shows as a revenue gap."""
    cols = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
    if any(c not in got for c in cols) \
            or len(got["l_orderkey"]) != len(want["l_orderkey"]):
        return {"q3_mismatch": max(len(want["l_orderkey"]), 1),
                "q3_rel_err": NOT_COMPARABLE}
    table = want["_groups"]
    bad, own = 0, []
    for k, rev, d, p in zip(*(list(got[c]) for c in cols)):
        hit = table.get(int(k))
        if hit is None or hit[1] != int(d) or hit[2] != int(p):
            bad += 1
            own.append(NOT_COMPARABLE)
        else:
            own.append(rel_err([rev], [hit[0]]))
    rank = rel_err(got["revenue"], want["revenue"])
    return {"q3_mismatch": bad, "q3_rel_err": max(own + [rank])}
