"""The benchmark's generator keeps the repository generator's schema and
storage types, makes keys, counts, dates, prices and flags as the TPC-H
specification's clause 4.2.3 says, and is a function of the seed."""

import numpy as np
import pytest

from bench.datagen import tpch as gen

TABLES = list(gen.SF_ROWS)
SF = 0.01


@pytest.fixture(scope="module")
def ours():
    return gen.generate(SF, 11, TABLES)


@pytest.mark.parametrize("table", TABLES)
def test_schema_and_types_match_the_repository(ours, table):
    from repro.core.table import Table
    from repro.data import tpch as repo
    cols, types, scales = repo.generate(SF, 11)[table]
    want = Table.from_dict(table, cols, types, scales)
    assert list(ours[table]) == list(want.schema.names)
    for name, col in ours[table].items():
        w = want.columns[name]
        assert col.data.dtype == w.data.dtype, name
        assert col.scale == w.scale, name
        assert (col.heap is None) == (w.heap is None), name
        if col.heap is not None:
            assert col.heap[1:] == sorted(col.heap[1:]), name


@pytest.mark.parametrize("table", [t for t in TABLES if t != "lineitem"])
def test_row_counts_are_the_scale_factors(ours, table):
    assert all(len(c.data) == gen.rows(table, SF)
               for c in ours[table].values())


def test_keys_are_sparse_and_lineitem_is_in_orderkey_order(ours):
    od, li = ours["orders"], ours["lineitem"]
    okey = od["o_orderkey"].data
    assert okey[:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33, 34]
    assert np.all((okey - 1) % 32 < 8) and np.all(np.diff(okey) > 0)
    lkey = li["l_orderkey"].data
    assert np.all(np.diff(lkey) >= 0)
    counts = np.unique(lkey, return_counts=True)[1]
    assert np.array_equal(np.unique(lkey), okey)
    assert counts.min() >= 1 and counts.max() <= 7
    line = li["l_linenumber"].data
    starts = np.r_[0, np.cumsum(counts)[:-1]]
    assert np.all(line[starts] == 1)
    assert np.array_equal(np.maximum.reduceat(line, starts), counts)
    assert np.all(od["o_custkey"].data % 3 != 0)
    assert od["o_custkey"].data.max() <= gen.rows("customer", SF)


def test_dates_flags_and_prices_follow_the_specification(ours):
    od, li = ours["orders"], ours["lineitem"]
    odate = od["o_orderdate"].data
    assert odate.min() >= gen.STARTDATE
    assert odate.max() <= gen.ENDDATE - 151
    per_line = np.repeat(odate, np.unique(li["l_orderkey"].data,
                                          return_counts=True)[1])
    ship, commit, receipt = (li[c].data for c in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    assert np.all((ship - per_line >= 1) & (ship - per_line <= 121))
    assert np.all((commit - per_line >= 30) & (commit - per_line <= 90))
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    status = li["l_linestatus"].strings()
    assert np.array_equal(status == "O", ship > gen.CURRENTDATE)
    flag = li["l_returnflag"].strings()
    assert np.array_equal(flag == "N", receipt > gen.CURRENTDATE)
    assert set(flag.tolist()) == {"A", "N", "R"}
    part = li["l_partkey"].data
    assert np.array_equal(li["l_extendedprice"].data,
                          li["l_quantity"].data.astype(np.int64)
                          * gen.retail_cents(part))
    supp = ours["partsupp"]
    pairs = set(zip(supp["ps_partkey"].data.tolist(),
                    supp["ps_suppkey"].data.tolist()))
    assert set(zip(part.tolist(), li["l_suppkey"].data.tolist())) <= pairs
    assert set(od["o_orderstatus"].strings().tolist()) <= {"F", "O", "P"}


def test_same_seed_same_data_other_seed_other_data_same_size():
    a = gen.generate(SF, 2**31 + 9, ["lineitem"])["lineitem"]
    b = gen.generate(SF, 2**31 + 9, ["lineitem"])["lineitem"]
    c = gen.generate(SF, 2**31 + 10, ["lineitem"])["lineitem"]
    assert all(np.array_equal(a[k].data, b[k].data) for k in a)
    assert not np.array_equal(a["l_extendedprice"].data,
                              c["l_extendedprice"].data)
    assert len(a["l_orderkey"].data) == len(c["l_orderkey"].data)


def test_a_table_does_not_depend_on_the_others_generated():
    a = gen.generate(SF, 5, ["lineitem"])["lineitem"]
    b = gen.generate(SF, 5, ["customer", "orders", "lineitem"])["lineitem"]
    assert all(np.array_equal(a[k].data, b[k].data) for k in a)
