"""Synthetic datasets: the ACS-like wide survey table (paper §4.3).

The American Community Survey benchmark uses a 274-column mixed-type table
(~millions of census rows).  We synthesize the same shape: person records
with replicate weights, demographic categoricals, and numeric amounts, so
examples/acs_survey.py can run the paper's load + statistics pipeline."""

from __future__ import annotations

import numpy as np

from ..core.types import DBType

N_WEIGHT_REPLICATES = 80       # pwgtp1..80, as in the real ACS
STATES = ["AL", "CA", "NY", "TX", "WA"]


def generate_acs(n_rows: int = 50_000, seed: int = 11):
    """Returns (columns, types, scales) — 274 columns like the ACS PUMS."""
    rng = np.random.default_rng(seed)
    cols: dict = {}
    types: dict = {}
    D = DBType

    def add(name, arr, t, scale=None):
        cols[name] = arr
        types[name] = t

    add("serialno", np.arange(n_rows, dtype=np.int64), D.INT64)
    add("st", np.asarray(STATES, dtype=object)[
        rng.integers(0, len(STATES), n_rows)], D.VARCHAR)
    add("puma", rng.integers(100, 990, n_rows).astype(np.int64), D.INT64)
    add("agep", rng.integers(0, 95, n_rows).astype(np.int64), D.INT64)
    add("sex", rng.integers(1, 3, n_rows).astype(np.int64), D.INT64)
    add("pwgtp", rng.integers(1, 300, n_rows).astype(np.int64), D.INT64)
    # income-ish numerics with NULLs (children have no earnings)
    wage = rng.exponential(30000, n_rows)
    wage[cols["agep"] < 16] = np.nan
    add("wagp", wage, D.FLOAT64)
    add("pincp", np.where(np.isnan(wage), np.nan,
                          wage * rng.uniform(1.0, 1.4, n_rows)), D.FLOAT64)
    add("schl", rng.integers(1, 25, n_rows).astype(np.int64), D.INT64)
    add("esr", rng.integers(0, 7, n_rows).astype(np.int64), D.INT64)
    add("hicov", rng.integers(1, 3, n_rows).astype(np.int64), D.INT64)
    add("mar", rng.integers(1, 6, n_rows).astype(np.int64), D.INT64)
    # 80 replicate weights (the survey-package workload reads these)
    base = cols["pwgtp"]
    for i in range(1, N_WEIGHT_REPLICATES + 1):
        add(f"pwgtp{i}",
            np.maximum(1, base + rng.integers(-40, 41, n_rows)).astype(
                np.int64), D.INT64)
    # filler categoricals/numerics up to 274 columns
    i = 0
    while len(cols) < 274:
        i += 1
        if i % 3 == 0:
            add(f"cat{i}", rng.integers(0, 9, n_rows).astype(np.int64),
                D.INT64)
        elif i % 3 == 1:
            add(f"amt{i}", np.round(rng.uniform(0, 1000, n_rows), 2),
                D.FLOAT64)
        else:
            add(f"flag{i}", rng.integers(0, 2, n_rows).astype(np.int64),
                D.INT64)
    return cols, types, {}


def load_acs(db, n_rows: int = 50_000, seed: int = 11,
             table: str = "acs_pums"):
    cols, types, scales = generate_acs(n_rows, seed)
    db.create_table(table, cols, types=types, scales=scales)
    return db.table(table)
