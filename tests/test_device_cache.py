"""Device-tier column cache (core/device_cache.py + DistributedScanAgg).

Contracts under test:

* the device budget matrix (unbudgeted / generous / tight) is
  **bit-identical** over TPC-H Q1-shaped aggregates — the batch
  decomposition, not the budget, fixes the arithmetic; budgets only change
  transfer/caching behaviour — with ``device_bytes_peak <= device_budget``
  in every budgeted cell and LRU evictions in the tight cell;
* a repeated scan is served from the cross-query cache: second run has
  ``device_cache_hits > 0`` and moves **zero** new host→device bytes;
* inputs that don't fit even one morsel batch fall back to the host tier
  (same results, no device traffic);
* DeviceBufferManager unit behaviour: LRU order, pin protection, dirty
  writeback + transparent re-upload, invalidation, budget validation.
"""

import numpy as np
import pytest

from repro.core import Col, DateLit, startup
from repro.core.device_cache import (DeviceBlockKeys, DeviceBudgetError,
                                     DeviceBufferManager)

BATCH = 4096              # fixed across cells: identical batching -> bits
GENEROUS = 64 << 20
TIGHT = 512 << 10         # > 2 batch working sets, < the table: streams
TINY = 8 << 10            # < one batch working set: host fallback


@pytest.fixture(scope="module")
def lineitem():
    from repro.data import tpch
    return tpch.generate(0.01)["lineitem"]


def _mkdb(lineitem, device_budget, **kw):
    li, types, scales = lineitem
    db = startup(device_budget=device_budget, device_batch_rows=BATCH, **kw)
    db.create_table("lineitem", li, types, scales)
    return db


def _q1(db):
    """TPC-H Q1 shape: filter + dense VARCHAR group keys + the full agg
    mix (sum / avg / count / min / max)."""
    return (db.scan("lineitem")
            .filter(Col("l_shipdate") <= DateLit("1998-09-02"))
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_qty=("sum", Col("l_quantity")),
                 sum_base_price=("sum", Col("l_extendedprice")),
                 avg_qty=("avg", Col("l_quantity")),
                 min_qty=("min", Col("l_quantity")),
                 max_disc=("max", Col("l_discount")),
                 count_order=("count", None)))


def _run(db):
    return _q1(db).execute(distributed=True).to_pydict()


def _assert_bits(a: dict, b: dict, ctx: str):
    assert list(a) == list(b), ctx
    for c in a:
        av, bv = np.asarray(a[c]), np.asarray(b[c])
        if av.dtype == object:
            assert list(map(str, av)) == list(map(str, bv)), (ctx, c)
        else:
            np.testing.assert_array_equal(av, bv, err_msg=f"{ctx} col={c}")


# ---------------------------------------------------------------------------
# budget matrix: bit-identity + peak <= budget + evictions when tight
# ---------------------------------------------------------------------------


def test_device_budget_matrix_bit_identical(lineitem):
    cells = {}
    stats = {}
    tiers = {}
    for budget in (None, GENEROUS, TIGHT):
        db = _mkdb(lineitem, budget)
        cells[budget] = _run(db)
        stats[budget] = db.buffer_manager.stats
        tiers[budget] = db.last_stats.device_tier
        assert db.last_stats.device_tier in ("resident", "streamed"), \
            "Q1 must run on the device tier in every cell"
    for budget in (GENEROUS, TIGHT):
        _assert_bits(cells[None], cells[budget], f"device_budget={budget}")
        st = stats[budget]
        assert st.device_bytes_peak <= budget, (st.device_bytes_peak, budget)
    # tight cell: the table outgrows the budget -> streamed with eviction
    assert tiers[TIGHT] == "streamed"
    assert stats[TIGHT].device_evictions > 0
    # generous cell: fully resident, nothing evicted
    assert tiers[GENEROUS] == "resident"
    assert stats[GENEROUS].device_evictions == 0


def test_device_matches_sequential(lineitem):
    db = _mkdb(lineitem, TIGHT)
    seq = _q1(db).execute().to_pydict()
    dev = _run(db)
    for c in seq:
        a, b = np.asarray(seq[c]), np.asarray(dev[c])
        if a.dtype == object:
            assert list(map(str, a)) == list(map(str, b))
        else:
            np.testing.assert_allclose(a.astype(float), b.astype(float),
                                       rtol=1e-9)


def test_streamed_prefetch_overlaps(lineitem):
    """Streaming issues batch N+1's transfer ahead of use."""
    db = _mkdb(lineitem, TIGHT)
    _run(db)
    assert db.last_stats.device_prefetch_hits > 0
    assert db.buffer_manager.stats.device_prefetch_hits > 0


# ---------------------------------------------------------------------------
# cross-query cache: repeat scans skip the host→device transfer
# ---------------------------------------------------------------------------


def test_repeated_query_hits_cache_no_new_h2d(lineitem):
    db = _mkdb(lineitem, GENEROUS)
    first = _run(db)
    s1 = db.last_stats
    assert s1.device_bytes_h2d > 0          # cold: base columns transferred
    assert s1.device_cache_hits == 0
    second = _run(db)
    s2 = db.last_stats
    assert s2.device_cache_hits > 0
    assert s2.device_bytes_h2d == 0, \
        "cached base columns must not be re-transferred"
    _assert_bits(first, second, "repeat")


def test_unbudgeted_does_not_retain_blocks(lineitem):
    """device_budget=None is zero-config: no silent device-memory growth —
    query blocks are dropped on completion."""
    db = _mkdb(lineitem, None)
    _run(db)
    assert db.device_manager.resident_blocks == 0
    assert db.last_stats.device_tier == "resident"


def test_appended_version_invalidates_cache(lineitem):
    """Keys carry the table version: appending produces a new version whose
    blocks miss the cache (no stale reads)."""
    li, types, scales = lineitem
    db = _mkdb(lineitem, GENEROUS)
    base = _run(db)
    one = {c: np.asarray(v[:1]) for c, v in li.items()}
    db.append("lineitem", one, types, scales)
    bumped = _run(db)
    assert db.last_stats.device_bytes_h2d > 0     # new version: fresh blocks
    n0 = np.asarray(base["count_order"], dtype=np.int64).sum()
    n1 = np.asarray(bumped["count_order"], dtype=np.int64).sum()
    assert n1 == n0 + 1


# ---------------------------------------------------------------------------
# host fallback: inputs the device tier cannot place
# ---------------------------------------------------------------------------


def test_tiny_budget_falls_back_to_host(lineitem):
    db = _mkdb(lineitem, TINY)
    res = _run(db)
    assert db.last_stats.device_tier == ""        # routed to the host tier
    assert db.buffer_manager.stats.device_bytes_h2d == 0
    ref = _q1(db).execute().to_pydict()
    _assert_bits(ref, res, "fallback")


# ---------------------------------------------------------------------------
# DeviceBufferManager unit behaviour
# ---------------------------------------------------------------------------


def _blk(i, n=1024):
    return np.full(n, i, dtype=np.float64)        # 8 KiB per block


def test_lru_eviction_order():
    m = DeviceBufferManager(budget=3 * 8192)
    for i in range(3):
        m.put(("t", "c", 0, i), _blk(i))
    assert m.get(("t", "c", 0, 0)) is not None    # bump 0 to most-recent
    m.put(("t", "c", 0, 3), _blk(3))              # evicts LRU: block 1
    assert ("t", "c", 0, 1) not in m
    assert ("t", "c", 0, 0) in m and ("t", "c", 0, 2) in m
    assert m.stats.device_evictions == 1
    assert m.stats.device_bytes_peak <= 3 * 8192


def test_pinned_blocks_never_evicted():
    m = DeviceBufferManager(budget=2 * 8192)
    m.put(("t", "c", 0, 0), _blk(0), pin=True)
    m.put(("t", "c", 0, 1), _blk(1), pin=True)
    with pytest.raises(DeviceBudgetError):
        m.put(("t", "c", 0, 2), _blk(2))
    m.unpin(("t", "c", 0, 0))
    m.put(("t", "c", 0, 2), _blk(2))              # now block 0 can go
    assert ("t", "c", 0, 0) not in m
    assert m.resident_bytes <= 2 * 8192


def test_oversized_block_rejected():
    m = DeviceBufferManager(budget=4096)
    with pytest.raises(DeviceBudgetError):
        m.put(("t", "c", 0, 0), _blk(0))


def test_dirty_writeback_roundtrip():
    """Evicted intermediates are copied back to host and transparently
    re-uploaded on next use — bit-exact."""
    import jax
    jax.config.update("jax_enable_x64", True)     # the engine's dtype mode
    m = DeviceBufferManager(budget=2 * 8192)
    vals = np.linspace(-1.0, 1.0, 1024)
    dev = jax.device_put(vals)
    m.adopt(("#q", "carry", 1, 0), dev, dirty=True)
    m.put(("t", "c", 0, 0), _blk(0))
    m.put(("t", "c", 0, 1), _blk(1))              # pressure: carry evicted
    assert m.stats.device_writebacks == 1
    assert ("#q", "carry", 1, 0) not in m
    back = m.get(("#q", "carry", 1, 0))           # re-upload from host copy
    assert back is not None
    np.testing.assert_array_equal(np.asarray(back), vals)


def test_clean_eviction_drops_without_writeback():
    m = DeviceBufferManager(budget=8192)
    m.put(("t", "c", 0, 0), _blk(0))
    m.put(("t", "c", 0, 1), _blk(1))
    assert m.stats.device_writebacks == 0
    assert m.get(("t", "c", 0, 0)) is None        # clean: host has the data


def test_invalidate_table():
    m = DeviceBufferManager(budget=None)
    m.put(DeviceBlockKeys.column("a", "x", 0, 0), _blk(0))
    m.put(DeviceBlockKeys.column("b", "x", 0, 0), _blk(1))
    m.invalidate_table("a")
    assert DeviceBlockKeys.column("a", "x", 0, 0) not in m
    assert DeviceBlockKeys.column("b", "x", 0, 0) in m
    assert m.resident_bytes == 8192


def test_cache_hit_accounting():
    m = DeviceBufferManager(budget=None)
    key = DeviceBlockKeys.column("t", "x", 3, 7)
    m.put(key, _blk(0))
    assert m.stats.device_cache_hits == 0
    assert m.get(key) is not None
    assert m.get(key) is not None
    assert m.stats.device_cache_hits == 2
    assert m.stats.device_bytes_h2d == 8192       # one transfer only


def test_budget_validation():
    with pytest.raises(ValueError):
        DeviceBufferManager(budget=0)
    with pytest.raises(ValueError):
        DeviceBufferManager(budget=-1)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The persistent compile cache honours JAX_COMPILATION_CACHE_DIR and
    otherwise sits at one fixed path inside the checkout, so every process
    of this checkout finds the same entries."""
    import os
    import subprocess
    import sys

    import jax

    from repro.core import device_cache as dc
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # JAX reads the variable at import: check it in a fresh interpreter
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(checkout, "src"))
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.core.device_cache import jax_runtime; "
         "print(jax_runtime().config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.strip() == str(tmp_path)
    # unset: two first calls place the same fixed in-checkout path
    was = jax.config.jax_compilation_cache_dir
    placed = []
    try:
        for _ in range(2):
            jax.config.update("jax_compilation_cache_dir", None)
            monkeypatch.setattr(dc, "_jax_configured", False)
            placed.append(dc.jax_runtime().config.jax_compilation_cache_dir)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert placed == [os.path.join(checkout, ".jax_cache")] * 2


def test_carry_eviction_mid_query_reuploads(lineitem, monkeypatch):
    """Force the merge carry (the only dirty block a query owns) out of the
    cache after every batch: the streaming loop must write it back, re-
    upload it, and still produce bit-identical results."""
    from repro.core import device_cache
    baseline = _run(_mkdb(lineitem, TIGHT))

    orig_adopt = device_cache.DeviceBufferManager.adopt

    def evicting_adopt(self, key, arr, **kw):
        out = orig_adopt(self, key, arr, **kw)
        if key[0] == device_cache.CARRY_TABLE and self.budget is not None:
            with self._lock:
                blk = self._blocks.get(key)
                if blk is not None and blk.pins == 0:
                    self._evict(key)              # budget-pressure stand-in
        return out

    monkeypatch.setattr(device_cache.DeviceBufferManager, "adopt",
                        evicting_adopt)
    db = _mkdb(lineitem, TIGHT)
    res = _run(db)
    st = db.buffer_manager.stats
    assert db.last_stats.device_tier == "streamed", \
        "carry churn must not knock the query off the device tier"
    assert st.device_writebacks > 0
    assert st.device_bytes_peak <= TIGHT
    _assert_bits(baseline, res, "carry-evict")


def test_cache_keys_include_batch_geometry(lineitem):
    """Two slicings of the same column version are distinct blocks: a
    second query with different batch geometry must not get cache hits on
    the first one's blocks (it would aggregate the wrong row ranges)."""
    from repro.core.parallel import DistributedScanAgg, match_scan_agg
    from repro.core.optimizer import optimize
    db = _mkdb(lineitem, GENEROUS)
    ref = _q1(db).execute().to_pydict()          # host-tier reference
    plan = optimize(_q1(db).plan, db.catalog)
    spec = match_scan_agg(plan, db.catalog)
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
    outs = {}
    for m in (1536, 2560):                       # different row slicings
        agg = DistributedScanAgg(db, spec, mesh, batch_rows=m)
        outs[m] = agg.run()
    np.testing.assert_allclose(outs[1536], outs[2560], rtol=1e-9)
    # and both agree with the host tier (wrong-rows bugs show up here)
    cnt = {m: np.sort(o[:, -1][o[:, -1] > 0]) for m, o in outs.items()}
    ref_cnt = np.sort(np.asarray(ref["count_order"], dtype=np.float64))
    for m in outs:
        np.testing.assert_array_equal(cnt[m], ref_cnt)


def test_snapshot_namespace_prevents_stale_hits():
    """A transaction snapshot's table reuses the version number the next
    committed write will get; its device blocks live under a unique key
    namespace in the SHARED manager (one budget), so later committed-data
    queries can never hit the snapshot's (possibly rolled-back) rows."""
    from repro.core.optimizer import optimize
    from repro.core.parallel import DistributedScanAgg, match_scan_agg
    import jax
    from jax.sharding import Mesh
    n = 8192
    db = startup(device_budget=64 << 20, device_batch_rows=4096)
    db.create_table("t", {"g": (np.arange(n) % 5).astype(np.int64),
                          "x": np.ones(n)})
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))

    def _agg(d):
        plan = optimize(d.scan("t").group_by("g").agg(s=("sum", "x")).plan,
                        d.catalog)
        spec = match_scan_agg(plan, d.catalog)
        out = DistributedScanAgg(d, spec, mesh).run()
        return out[:, 0]                        # per-group sums

    # snapshot view: same table name at the version the next commit gets
    # (version 1), but with DIFFERENT data — exactly a txn's uncommitted
    # append — sharing the parent's device manager under its own namespace
    snap = startup()
    snap.catalog.tables["t"] = db.table("t").append_table(
        db.table("t"))                          # version 1, 2n rows
    snap.device_manager = db.device_manager
    snap.device_key_namespace = 7
    snap_sums = _agg(snap)
    assert snap_sums.sum() == 2 * n
    db.device_manager.invalidate_namespace(7)
    assert not any(isinstance(k[2], tuple) and k[2][0] == 7
                   for k in db.device_manager._blocks)
    # the real commit: version 1 on the parent, one extra row
    db.append("t", {"g": np.array([0], dtype=np.int64),
                    "x": np.array([1.0])})
    assert db.table("t").version == 1
    sums = _agg(db)
    assert sums.sum() == n + 1, \
        "committed-version query must not hit the snapshot's blocks"


def test_heap_renumber_invalidates_step_cache():
    """VARCHAR literal codes are baked into jitted traces; an append that
    introduces a novel string renumbers the whole heap, so the compiled
    step must not be reused (its key includes the heap fingerprint)."""
    rng = np.random.default_rng(5)
    n = 20_000
    cities = np.asarray(["nyc", "sfo"], dtype=object)[rng.integers(0, 2, n)]
    db = startup(device_budget=64 << 20, device_batch_rows=4096)
    db.create_table("t", {"city": cities,
                          "hour": rng.integers(0, 8, n).astype(np.int64),
                          "x": rng.uniform(0, 1, n)})

    def q():
        return (db.scan("t").filter(Col("city") == "nyc")
                .group_by("hour").agg(s=("sum", "x"), c=("count", None)))

    r1 = q().execute(distributed=True).to_pydict()
    assert db.last_stats.device_tier != ""
    np.testing.assert_array_equal(
        np.asarray(r1["c"], np.int64), np.asarray(
            q().execute().to_pydict()["c"], np.int64))
    # novel string sorting BEFORE "nyc": merge renumbers every code
    db.append("t", {"city": np.asarray(["ams"], dtype=object),
                    "hour": np.array([0], dtype=np.int64),
                    "x": np.array([0.5])})
    r2 = q().execute(distributed=True).to_pydict()
    seq = q().execute().to_pydict()
    np.testing.assert_array_equal(np.asarray(r2["c"], np.int64),
                                  np.asarray(seq["c"], np.int64))
    np.testing.assert_allclose(np.asarray(r2["s"], float),
                               np.asarray(seq["s"], float), rtol=1e-9)


def test_mixed_meshes_share_database_without_fallback(lineitem):
    """Block keys carry mesh identity: blocks cached for one mesh must not
    be served to a query on another mesh (the jitted step would raise on
    incompatible device placement and silently fall off the device tier)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs a multi-device backend (CI forces 4)")
    db = _mkdb(lineitem, GENEROUS)
    mesh_all = Mesh(np.array(devs).reshape(-1), ("data",))
    mesh_one = Mesh(np.array(devs[:1]).reshape(-1), ("data",))
    plan = _q1(db).plan
    a = db.execute_plan(plan, distributed=True, mesh=mesh_all).to_pydict()
    assert db.last_stats.device_tier != ""
    b = db.execute_plan(plan, distributed=True, mesh=mesh_one).to_pydict()
    assert db.last_stats.device_tier != "", \
        "second mesh must run on the device tier, not fall back"
    for c in a:
        av, bv = np.asarray(a[c]), np.asarray(b[c])
        if av.dtype == object:
            assert list(map(str, av)) == list(map(str, bv))
        else:
            np.testing.assert_allclose(av.astype(float), bv.astype(float),
                                       rtol=1e-9)


def test_append_keeps_base_blocks_replace_frees_all(lineitem):
    """Delta-store cache lifecycle: an append lands as a delta chunk, so the
    immutable base's device blocks SURVIVE it (epoch-keyed caching — only
    tail-overlapping entries are invalidated), while a DELETE rewrites rows
    and must still free every block of the table."""
    li, types, scales = lineitem
    db = _mkdb(lineitem, GENEROUS)
    _run(db)
    before = db.device_manager.resident_blocks
    assert before > 0
    db.append("lineitem", {c: np.asarray(v[:1]) for c, v in li.items()},
              types, scales)
    t = db.catalog.table("lineitem")
    assert t.delta_rows == 1           # the append took the delta path
    assert db.device_manager.resident_blocks == before, \
        "base-version blocks must survive a delta append"
    db.delete("lineitem", Col("l_quantity") >= 0)
    assert db.device_manager.resident_blocks == 0


# ---------------------------------------------------------------------------
# get_or_put under builder failure (multi-thread stress)
# ---------------------------------------------------------------------------


class TestGetOrPutBuilderFailure:
    def test_stress_builder_raises_mid_upload(self):
        """Hammer one key from many threads while the builder fails on a
        schedule: failed builds must not poison attachers (they retry as
        builders), must not leak pinned bytes, and the budget invariant
        ``device_bytes_peak <= device_budget`` must hold throughout."""
        import threading

        block = np.ones(4096, dtype=np.float64)            # 32 KiB
        budget = 4 * block.nbytes
        dm = DeviceBufferManager(budget=budget)
        key = ("#stress", "c", 0, 0)
        counter = threading.Lock()
        attempts = [0]

        def build():
            with counter:
                attempts[0] += 1
                n = attempts[0]
            if n % 3 == 1:          # every third build dies mid-upload
                raise RuntimeError("upload failed")
            return block

        successes, failures, errors = [], [], []

        def worker():
            try:
                for i in range(40):
                    try:
                        arr = dm.get_or_put(key, build, pin=True)
                        assert float(np.asarray(arr)[0]) == 1.0
                        successes.append(1)
                        assert dm.resident_bytes <= budget
                        dm.unpin(key)
                    except RuntimeError:
                        failures.append(1)   # this thread was the builder
                    if i % 10 == 9:
                        dm.drop(key)         # force periodic rebuilds
            except Exception as e:           # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=worker) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert not errors, errors
        assert successes, "no thread ever completed a get_or_put"
        assert failures, "the failure schedule never fired"
        assert dm.stats.device_bytes_peak <= budget
        dm.drop(key)
        # a failed build must leave nothing behind: no block, no pinned
        # bytes, no residual accounting
        assert dm.resident_bytes == 0
        assert dm.resident_blocks == 0

    def test_builder_failure_leaves_no_flight_slot(self):
        """After a failed build the single-flight table is empty — the
        next caller becomes a fresh builder, it does not attach to a dead
        flight."""
        dm = DeviceBufferManager(budget=1 << 20)
        key = ("#once", "c", 0, 0)

        def boom():
            raise RuntimeError("no")

        with pytest.raises(RuntimeError):
            dm.get_or_put(key, boom, pin=True)
        assert len(dm._flight._calls) == 0
        assert dm.resident_bytes == 0
        arr = dm.get_or_put(key, lambda: np.arange(8.0), pin=False)
        assert np.asarray(arr).shape == (8,)
