"""TPC-H Q6, the forecasting revenue change query (specification clause
2.4.6), with its validation parameters: DATE 1994-01-01, DISCOUNT 0.06,
QUANTITY 24."""

from __future__ import annotations

import numpy as np

from ..common import column_bytes, compare_rows, decimal
from ..datagen.tpch import day

SQL = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24
"""

COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]}
# Readings and the reason for each limit: PERF.md section 2.
LIMITS = {"q6_rel_err": 1e-9, "q6_mismatch": 0}


def reference(data: dict, dtype=np.float64) -> dict:
    li = data["lineitem"]
    ship = li["l_shipdate"].data
    disc = li["l_discount"].data.astype(dtype)
    keep = ((ship >= day("1994-01-01")) & (ship < day("1995-01-01"))
            & (disc >= dtype(0.05)) & (disc <= dtype(0.07))
            & (li["l_quantity"].data.astype(dtype) < dtype(24)))
    price = decimal(li["l_extendedprice"], dtype)[keep]
    revenue = (price * disc[keep]).sum(dtype=dtype)
    return {"revenue": np.array([revenue], dtype=dtype)}


def logical_bytes(data: dict) -> int:
    return column_bytes(data, COLUMNS)


def compare(got: dict, want: dict) -> dict:
    return compare_rows(got, want, [], ["revenue"], "q6")
