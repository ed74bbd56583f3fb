"""Unified physical planner (core/physplan.py).

Contracts under test:

* **SQL–builder parity**: every TPC-H query with both entry points runs
  through SQL and the builder API across the {unlimited, 1 MiB, 64 KiB}
  host-budget matrix and must be *bit-identical* with identical tier
  annotations — one planner, many frontends (paper §3).
* **SQL hits the device tier** (the ROADMAP regression): normalization
  elides the SQL front-end's rename projection, so a SQL TPC-H Q1 routes
  device-resident/streamed exactly like the builder plan — asserted with a
  monkeypatch fence that makes any host fallback fail loudly.
* **Normalization** unit behaviour: identity-projection elision,
  rename-push into aggregates (only when column order is preserved),
  filter-conjunct canonicalization.
* **Smarter admission**: ``choose_device_tier`` biases borderline resident
  placement by the device cache's hit history.
* **Budgeted result materialization**: over-budget final tables stream to
  memmapped columns (``result_spills``), bit-identical, no leaked files.
* **Golden physical plans** for TPC-H Q1/Q3 under a forced 4-CPU-device
  topology (the ``physplan`` CI job), so tier annotations are pinned.
"""

import os

import numpy as np
import pytest

from repro.core import Col, startup
from repro.core.physplan import (TIER_DEVICE_RESIDENT, TIER_DEVICE_STREAMED,
                                 TIER_IN_MEMORY, TIER_SPILL,
                                 choose_device_tier, find_scan_agg_core,
                                 match_scan_agg, normalize, plan_physical)
from repro.data import tpch
from repro.data.tpch_queries import ALL_QUERIES, SQL_QUERIES

SF = 0.002
BUDGET_MATRIX = (None, 1 << 20, 64 << 10)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture(scope="module")
def dbs():
    out = {}
    for budget in BUDGET_MATRIX:
        db = startup(memory_budget=budget)
        tpch.load_into(db, sf=SF, seed=3)
        out[budget] = db
    return out


def _assert_bits(a: dict, b: dict, ctx: str):
    assert set(a) == set(b), ctx
    for c in a:
        av, bv = np.asarray(a[c]), np.asarray(b[c])
        if av.dtype == object or bv.dtype == object:
            assert list(map(str, av)) == list(map(str, bv)), (ctx, c)
        else:
            np.testing.assert_array_equal(av, bv, err_msg=f"{ctx} col={c}")


# ---------------------------------------------------------------------------
# differential SQL-vs-builder parity across the budget matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname", list(SQL_QUERIES))
@pytest.mark.parametrize("budget", BUDGET_MATRIX)
def test_sql_builder_parity_budget_matrix(dbs, qname, budget):
    """Both entry points produce bit-identical results and identical tier
    annotations in every cell of the budget matrix."""
    db = dbs[budget]
    sql_plan = db.sql(SQL_QUERIES[qname]).plan
    builder_plan = ALL_QUERIES[qname](db).plan
    sql_res = db.sql(SQL_QUERIES[qname]).execute().to_pydict()
    b_res = ALL_QUERIES[qname](db).execute().to_pydict()
    _assert_bits(sql_res, b_res, f"{qname} budget={budget}")
    sql_phys = plan_physical(sql_plan, db)
    b_phys = plan_physical(builder_plan, db)
    assert sql_phys.tier_summary() == b_phys.tier_summary(), \
        (qname, budget, sql_phys.render(), b_phys.render())


def test_q1_q6_plans_fully_converge(dbs):
    """Q1/Q6 SQL and builder plans are *identical* after normalization
    (not just tier-equal): the rename projection folds away entirely."""
    db = dbs[None]
    for qname in ("q1", "q6"):
        sql_phys = plan_physical(db.sql(SQL_QUERIES[qname]).plan, db)
        b_phys = plan_physical(ALL_QUERIES[qname](db).plan, db)
        assert sql_phys.render() == b_phys.render(), qname


# ---------------------------------------------------------------------------
# SQL plans hit the device tier (ROADMAP regression)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def devdb():
    db = startup(device_budget=64 << 20)
    tpch.load_into(db, sf=SF, seed=3)
    return db


def test_sql_q1_routes_device_like_builder(devdb, monkeypatch):
    """SQL TPC-H Q1 routes device-resident/streamed identically to the
    builder plan.  The monkeypatch fence makes the ParallelExecutor's host
    program unreachable, so any silent fallback fails the test instead of
    hiding the routing regression."""
    from repro.core.parallel import ParallelExecutor

    def _fence(self, prog):
        raise AssertionError("host fallback — scan-agg core missed the "
                             "device tier")

    monkeypatch.setattr(ParallelExecutor, "run_program", _fence)
    b = ALL_QUERIES["q1"](devdb).execute(distributed=True).to_pydict()
    b_stats = devdb.last_stats
    assert b_stats.device_tier in ("resident", "streamed")
    b_plan = b_stats.plan_repr
    s = devdb.sql(SQL_QUERIES["q1"]).execute(distributed=True).to_pydict()
    s_stats = devdb.last_stats
    assert s_stats.device_tier == b_stats.device_tier
    assert s_stats.plan_repr == b_plan, "entry points must lower identically"
    # the SQL run reuses the builder run's cached device blocks: the
    # acceptance bar for "one planner, many frontends"
    assert s_stats.device_cache_hits > 0
    assert s_stats.device_bytes_h2d == 0
    _assert_bits(b, s, "q1 device parity")


def test_sql_q6_global_agg_routes_device(devdb):
    """Q6 (zero group keys, Project(Agg(Filter(Scan))) from SQL) also
    lowers to the device tier through normalization."""
    devdb.sql(SQL_QUERIES["q6"]).execute(distributed=True)
    assert devdb.last_stats.device_tier in ("resident", "streamed")


def test_suffix_runs_order_by_on_host(devdb):
    """ORDER BY above the scan-agg core no longer knocks the plan off the
    device tier: the core runs on devices, the suffix sorts the (tiny)
    assembled aggregate on host."""
    q = ALL_QUERIES["q1"](devdb)          # ends in .order_by(...)
    phys = plan_physical(q.plan, devdb, distributed=True)
    assert phys.agg_tier in (TIER_DEVICE_RESIDENT, TIER_DEVICE_STREAMED)
    assert phys.suffix_plan is not None
    out = q.execute(distributed=True).to_pydict()
    assert devdb.last_stats.device_tier in ("resident", "streamed")
    host = q.execute().to_pydict()        # host reference, same order
    rf = list(map(str, out["l_returnflag"]))
    assert rf == sorted(rf)
    np.testing.assert_allclose(
        np.asarray(out["sum_qty"], float),
        np.asarray(host["sum_qty"], float), rtol=1e-9)


# ---------------------------------------------------------------------------
# normalization units
# ---------------------------------------------------------------------------


def _mkdb(n=100):
    db = startup()
    db.create_table("t", {
        "g": (np.arange(n) % 4).astype(np.int64),
        "h": (np.arange(n) % 3).astype(np.int64),
        "x": np.linspace(0.0, 1.0, n),
    })
    return db


def test_normalize_elides_identity_projection():
    db = _mkdb()
    q = db.scan("t").select("g", "h", "x")
    from repro.core.relalg import ProjectNode, ScanNode
    norm = normalize(q.plan, db.catalog)
    assert isinstance(norm, ScanNode)
    # a column-dropping projection is NOT identity: it must survive
    norm2 = normalize(db.scan("t").select("g").plan, db.catalog)
    assert isinstance(norm2, ProjectNode)


def test_normalize_pushes_renames_into_aggregate():
    db = _mkdb()
    sql_plan = db.sql(
        "SELECT g, sum(x) AS total, count(*) AS n FROM t GROUP BY g").plan
    from repro.core.relalg import AggregateNode
    norm = normalize(sql_plan, db.catalog)
    assert isinstance(norm, AggregateNode)
    assert [a.name for a in norm.aggs] == ["total", "n"]


def test_normalize_keeps_reordering_projection():
    """SELECT order that permutes keys and aggregates is observable result
    column order — the projection must survive normalization."""
    db = _mkdb()
    sql_plan = db.sql(
        "SELECT sum(x) AS total, g FROM t GROUP BY g").plan
    from repro.core.relalg import ProjectNode
    norm = normalize(sql_plan, db.catalog)
    assert isinstance(norm, ProjectNode)
    res = db.sql("SELECT sum(x) AS total, g FROM t GROUP BY g").execute()
    assert res.schema.names == ("total", "g")


def test_normalize_canonicalizes_filter_conjuncts():
    db = _mkdb()
    a = db.scan("t").filter((Col("x") > 0.1) & (Col("g") < 3)).plan
    b = db.scan("t").filter(Col("g") < 3).filter(Col("x") > 0.1).plan
    na, nb = normalize(a, db.catalog), normalize(b, db.catalog)
    assert repr(na.predicate) == repr(nb.predicate)


def test_matcher_sees_through_suffix():
    """find_scan_agg_core locates the aggregate under order/limit/project
    chains and builds a suffix plan over the '#agg' result scan."""
    db = _mkdb(n=8192)
    q = (db.scan("t").filter(Col("x") > 0.5).group_by("g")
         .agg(s=("sum", "x")).order_by(("s", True)).limit(2))
    core, suffix = find_scan_agg_core(
        normalize(q.plan, db.catalog), db.catalog)
    assert core is not None and suffix is not None
    assert match_scan_agg(core, db.catalog) is not None
    from repro.core.relalg import LimitNode, OrderByNode
    assert isinstance(suffix, LimitNode)
    assert isinstance(suffix.child, OrderByNode)


# ---------------------------------------------------------------------------
# smarter admission: hit-history-biased residency
# ---------------------------------------------------------------------------


def test_choose_device_tier_hit_history_promotes_borderline():
    budget = 1 << 20
    batch = 64 << 10                       # streamable: 2*batch <= budget
    borderline = int(0.8 * budget)         # fits, but would crowd the cache
    small = int(0.2 * budget)
    # borderline + no history: stream (blocks still populate the cache)
    assert choose_device_tier(borderline, batch, budget,
                              hit_history=0) == "streamed"
    # borderline + repeat-access evidence: flip to resident
    assert choose_device_tier(borderline, batch, budget,
                              hit_history=1) == "resident"
    # small tables are resident immediately — history not required
    assert choose_device_tier(small, batch, budget,
                              hit_history=0) == "resident"
    # over-budget stays streamed no matter the history
    assert choose_device_tier(2 * budget, batch, budget,
                              hit_history=99) == "streamed"
    # unbudgeted placement is unchanged
    assert choose_device_tier(borderline, batch, None,
                              hit_history=0) == "resident"


def test_borderline_table_flips_streamed_to_resident():
    """End-to-end: the first query on a borderline table streams (no
    repeat-access evidence yet); streamed-mode blocks still populate the
    cache, so a repeat query observes hits and the table is promoted to
    resident."""
    n = 16384
    # table ≈ 272 KiB resident: fits the 400 KiB budget but takes > half
    db = startup(device_budget=400 << 10, device_batch_rows=4096)
    db.create_table("t", {"g": (np.arange(n) % 5).astype(np.int64),
                          "x": np.ones(n)})
    q = db.scan("t").group_by("g").agg(s=("sum", "x"))
    r1 = q.execute(distributed=True).to_pydict()
    assert db.last_stats.device_tier == "streamed", \
        "cold borderline table must stream, not monopolize the cache"
    assert db.device_manager.hit_history("t") == 0
    r2 = q.execute(distributed=True).to_pydict()   # hits accrue here
    assert db.last_stats.device_cache_hits > 0
    assert db.device_manager.hit_history("t") > 0
    r3 = q.execute(distributed=True).to_pydict()
    assert db.last_stats.device_tier == "resident", \
        "repeat queries on a borderline table must be promoted"
    for other in (r2, r3):
        _assert_bits(r1, other, "borderline promote")


def test_drop_table_forgets_admission_history():
    """DROP TABLE clears the hit history (a future table reusing the name
    must earn residency from scratch); appends keep it (repeat-access
    evidence is about the workload, not one table version)."""
    from repro.core.device_cache import DeviceBufferManager
    m = DeviceBufferManager(budget=None)
    m.put(("t", "c", 0, 0), np.zeros(64))
    m.get(("t", "c", 0, 0))
    assert m.hit_history("t") == 1
    m.invalidate_table("t")                  # append path: history kept
    assert m.hit_history("t") == 1
    m.invalidate_table("t", drop_history=True)   # DROP TABLE
    assert m.hit_history("t") == 0


def test_demoted_core_renders_host_annotation():
    """A device attempt that fails at runtime re-renders honestly: the
    core shows the host tier (no '(fused)' children, host byte model) and
    the stats do NOT claim device execution."""
    from repro.core.parallel import ParallelExecutor
    n = 8192
    db = startup(device_budget=64 << 20)
    db.create_table("t", {"g": (np.arange(n) % 5).astype(np.int64),
                          "x": np.ones(n)})
    # the extra LimitNode keeps the ORDER BY off the device (only a sort
    # DIRECTLY above the core fuses), so the host suffix path still runs
    q = (db.scan("t").group_by("g").agg(s=("sum", "x"))
         .order_by("g").limit(3))
    ref = q.execute().to_pydict()
    orig = ParallelExecutor._run_suffix
    try:
        def boom(self, sp, t):
            raise RuntimeError("suffix gap")
        ParallelExecutor._run_suffix = boom
        out = q.execute(distributed=True).to_pydict()
    finally:
        ParallelExecutor._run_suffix = orig
    st = db.last_stats
    assert st.device_tier == "", "host recompute must not claim the device"
    assert "(fused)" not in st.plan_repr
    assert st.device_fallback == "RuntimeError: suffix gap"
    assert "scan-agg core kept on host (RuntimeError: suffix gap)" \
        in st.plan_repr
    _assert_bits(ref, out, "demoted")


def test_device_manager_hit_history_accounting():
    from repro.core.device_cache import DeviceBufferManager
    m = DeviceBufferManager(budget=None)
    m.put(("t", "c", 0, 0), np.zeros(64))
    m.put(("#carry", "p", 0, 0), np.zeros(64))
    assert m.hit_history("t") == 0
    m.get(("t", "c", 0, 0))
    m.get(("t", "c", 0, 0))
    m.get(("#carry", "p", 0, 0))
    assert m.hit_history("t") == 2
    assert m.hit_history("#carry") == 0    # intermediates never count
    m.cleanup()
    assert m.hit_history("t") == 0


# ---------------------------------------------------------------------------
# budgeted result materialization
# ---------------------------------------------------------------------------


def test_result_spills_to_memmap_bit_identical():
    n = 30_000
    data = {"k": np.arange(n, dtype=np.int64),
            "s": np.asarray([f"name-{i % 257}" for i in range(n)],
                            dtype=object),
            "x": np.linspace(-1.0, 1.0, n)}
    base = startup()
    db = startup(memory_budget=64 << 10)
    base.create_table("t", dict(data))
    db.create_table("t", dict(data))
    q = lambda d: (d.scan("t").filter(Col("x") > -0.5)
                   .project(k=Col("k"), s=Col("s"), y=Col("x") * 2.0))
    ref = q(base).execute().to_pydict()
    assert base.last_stats.result_spills == 0
    out = q(db).execute().to_pydict()
    assert db.last_stats.result_spills == 1
    assert db.buffer_manager.stats.result_spills == 1
    assert db.buffer_manager.active_files == 0, \
        "memmapped result files must be unlinked immediately"
    _assert_bits(ref, out, "result spill")


def test_result_spill_columns_are_memmapped():
    n = 30_000
    db = startup(memory_budget=32 << 10)
    db.create_table("t", {"x": np.arange(n, dtype=np.int64)})
    t = db.scan("t").project(y=Col("x") + 1).execute()
    assert isinstance(t.columns["y"].data, np.memmap)
    np.testing.assert_array_equal(np.asarray(t.columns["y"].data[:5]),
                                  np.arange(1, 6))


def test_small_results_stay_in_ram():
    db = startup(memory_budget=1 << 20)
    db.create_table("t", {"x": np.arange(100, dtype=np.int64)})
    t = db.scan("t").agg(s=("sum", "x")).execute()
    assert not isinstance(t.columns["s"].data, np.memmap)
    assert db.last_stats.result_spills == 0


# ---------------------------------------------------------------------------
# EXPLAIN observability
# ---------------------------------------------------------------------------


def test_explain_physical_shows_tiers():
    db = _mkdb(n=1000)
    txt = (db.scan("t").group_by("g").agg(s=("sum", "x"))
           .explain(physical=True))
    assert "physical plan" in txt
    assert TIER_IN_MEMORY in txt
    small = startup(memory_budget=1 << 10)
    small.create_table("t", {"k": np.arange(4096, dtype=np.int64),
                             "x": np.ones(4096)})
    txt2 = (small.scan("t").group_by("k").agg(s=("sum", "x"))
            .explain(physical=True))
    assert TIER_SPILL in txt2
    assert "memory_budget=1024" in txt2


def test_exec_stats_carry_plan_repr():
    db = _mkdb(n=500)
    db.scan("t").group_by("g").agg(s=("sum", "x")).execute()
    assert "physical plan" in db.last_stats.plan_repr
    assert "Aggregate" in db.last_stats.plan_repr


# ---------------------------------------------------------------------------
# golden physical plans (forced 4 CPU devices — the `physplan` CI job)
# ---------------------------------------------------------------------------


def _golden_db():
    db = startup(memory_budget=256 << 10, device_budget=64 << 20,
                 device_batch_rows=4096)
    tpch.load_into(db, sf=SF, seed=3)
    return db


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_golden_physical_plan(qname):
    import jax
    if jax.device_count() != 4:
        pytest.skip("golden plans are pinned to a forced 4-device topology "
                    "(CI: XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    db = _golden_db()
    got = ALL_QUERIES[qname](db).explain(physical=True, distributed=True)
    path = os.path.join(GOLDEN_DIR, f"physplan_{qname}.txt")
    if os.environ.get("PHYSPLAN_REGOLD"):
        with open(path, "w") as f:
            f.write(got + "\n")
    with open(path) as f:
        want = f.read().rstrip("\n")
    assert got == want, f"golden physical plan drifted for {qname}:\n{got}"
