"""Device-tier joins and sorts (physplan join-agg matching + the
DistributedJoinAgg streams + device-resident assembly).

Differential budget-matrix contracts:

* **Bit-identity**: TPC-H Q3 across device budgets {unlimited, 64 MiB,
  4 MiB, 2 MiB} x skipping {on, forced-off} is *bit-identical* in every
  device cell — the budget only changes residency (resident vs streamed),
  never a result byte — and every cell matches the host join tier.  The
  matrix runs on two order-key layouts: dense keys (a dense build domain)
  and the specification's sparse keys, the first 8 of every 32 (a ranked
  build domain).
* **Peak accounting**: ``device_bytes_peak <= device_budget`` in every
  budgeted cell; the 2 MiB cell actually streams (``join-streamed``).
* **Fences**: monkeypatch fences prove the host hash join is never
  entered on the device path and the (n_groups, K) partial matrix is
  never finalized on host (assembly is device-resident).
* **Soundness gates**: duplicate build keys trip the on-device
  uniqueness witness and fall back to the (correct) host join; NULL
  probe keys never match; on ranked keys, a probe key between, below or
  above the build's keys never matches, nor a NULL on either side.  Those
  cases run on the benchmark's TPC-H generator and are also held to
  ``bench/queries/q3.py``'s numpy reference.
* **Fused ORDER BY**: the device lexsort permutation equals the host
  suffix sort's (``device_sorted`` claims the fusion), for both the
  join tier and the scan-agg tier.
"""

import functools

import numpy as np
import pytest

from bench.datagen import tpch as spec_gen
from bench.queries import q3 as q3_ref
from bench.run import load
from repro.core import Col, startup
from repro.core.expression import Lit
from repro.core.indexes import IMPRINT_BLOCK
from repro.core.types import NULL_SENTINEL, DBType
from repro.data.tpch import generate
from repro.data.tpch_queries import q3

DEVICE_BUDGETS = (None, 64 << 20, 4 << 20, 2 << 20)
BATCH_ROWS = 8192          # small enough that the 2 MiB cell streams

_TPCH = generate(0.01, 7)
_Q3_TABLES = ("customer", "orders", "lineitem")
_N_ORDERS = len(_TPCH["orders"][0]["o_orderkey"])
# order-key layouts: the generator's dense 0..n-1, and the TPC-H
# specification's (clause 4.2.3) first 8 of every 32 keys
LAYOUTS = ("dense", "sparse")


def _sparse(k):
    return (k // 8) * 32 + k % 8 + 1


def _mkdb(layout="dense", **kw):
    db = startup(**kw)
    for name in _Q3_TABLES:
        cols, types, scales = _TPCH[name]
        if layout == "sparse" and name in ("orders", "lineitem"):
            key = "o_orderkey" if name == "orders" else "l_orderkey"
            cols = {**cols, key: _sparse(cols[key])}
        db.create_table(name, cols, types=types, scales=scales)
    return db


def _rows(d: dict):
    """Row-major view of a to_pydict result, exact on every dtype."""
    cols = []
    for c in d.values():
        v = np.asarray(c)
        cols.append(list(map(str, v)) if v.dtype == object else list(v))
    return list(zip(*cols))


def _assert_matches(got: dict, want: dict, ctx: str, exact: bool):
    assert list(got) == list(want), ctx
    for c in got:
        gv, wv = np.asarray(got[c]), np.asarray(want[c])
        if gv.dtype == object or wv.dtype == object:
            assert list(map(str, gv)) == list(map(str, wv)), (ctx, c)
        elif exact:
            np.testing.assert_array_equal(gv, wv, err_msg=f"{ctx} col={c}")
        else:
            np.testing.assert_allclose(np.asarray(gv, float),
                                       np.asarray(wv, float),
                                       rtol=1e-9, err_msg=f"{ctx} col={c}")


# ---------------------------------------------------------------------------
# differential harness: Q3 across the device budget matrix
# ---------------------------------------------------------------------------


@functools.cache
def _host_q3(layout="dense"):
    db = _mkdb(layout)
    try:
        return q3(db).execute().to_pydict()
    finally:
        db.shutdown()


@pytest.fixture(scope="module", params=LAYOUTS)
def device_cells(request):
    """Q3 in every (device_budget, skipping) cell, one cold db per cell,
    on one order-key layout: ``(layout, {cell: outcome})``."""
    out = {}
    for budget in DEVICE_BUDGETS:
        for skipping in (True, False):
            db = _mkdb(request.param, device_budget=budget,
                       device_batch_rows=BATCH_ROWS,
                       data_skipping=skipping)
            try:
                res = q3(db).execute(distributed=True).to_pydict()
                s = db.last_stats
                out[budget, skipping] = (
                    res, s.device_tier, s.device_sorted,
                    s.device_bytes_peak, s.join_ranked_builds,
                    s.join_rank_keys)
            finally:
                db.shutdown()
    return request.param, out


def test_q3_matrix_runs_on_device(device_cells):
    """Budgeted cells run the device join; the tight 2 MiB budget must
    actually stream (resident state exceeds it), and the lifetime HBM
    peak stays under the budget in every budgeted cell.  The orders build
    runs on ranks exactly when its keys are sparse."""
    layout, cells = device_cells
    ranked = (1, _N_ORDERS) if layout == "sparse" else (0, 0)
    for (budget, skipping), (_res, tier, _srt, peak, *rank) in cells.items():
        if budget is not None:
            assert tier.startswith("join-"), (budget, skipping, tier)
            assert peak <= budget, (budget, skipping, peak)
        if tier:
            assert tuple(rank) == ranked, (budget, skipping, rank)
    assert cells[2 << 20, True][1] == "join-streamed"
    assert cells[64 << 20, True][1] == "join-resident"


def test_q3_matrix_bit_identical(device_cells):
    """The budget (and skipping) are pure optimizations: every device
    cell that ran the join tier returns byte-identical results."""
    ran = {k: v for k, v in device_cells[1].items()
           if v[1].startswith("join-")}
    assert len(ran) >= 6
    items = list(ran.items())
    ref_key, (ref, *_rest) = items[0]
    for key, (res, *_s) in items[1:]:
        _assert_matches(res, ref, f"{key} vs {ref_key}", exact=True)


def test_q3_matrix_matches_host(device_cells):
    """Every device cell agrees with the host join tier (same rows, same
    order — the fused device sort reproduces the suffix sort)."""
    layout, cells = device_cells
    for key, (res, tier, sorted_, *_s) in cells.items():
        _assert_matches(res, _host_q3(layout), f"{key} tier={tier}",
                        exact=False)
        if tier.startswith("join-"):
            assert sorted_, key     # Q3's ORDER BY ... LIMIT 10 fused


@pytest.mark.parametrize("layout", LAYOUTS)
def test_q3_explain_annotates_device_join_and_sort(layout):
    """The sparse layout's orders build shows its ranked domain on its
    join; the dense one keeps its offset domain and shows none."""
    db = _mkdb(layout, device_budget=64 << 20)
    try:
        txt = q3(db).explain(physical=True, distributed=True)
        assert ":: device-join" in txt
        assert ":: device-sort" in txt
        assert "mode=resident" in txt
        join = next(line for line in txt.splitlines()
                    if "Join(inner, ['l_orderkey']=['o_orderkey'])" in line)
        if layout == "sparse":
            assert join.endswith(f"(fused) ranked keys: {_N_ORDERS}")
        else:
            assert "ranked keys" not in txt
    finally:
        db.shutdown()


# ---------------------------------------------------------------------------
# fences: the device path must never touch the host join or finalize
# ---------------------------------------------------------------------------


def test_fence_host_join_never_entered(monkeypatch):
    """Poison both host join kernels: a device-tier Q3 that silently fell
    back to the host join fails loudly."""
    from repro.core import executor as ex
    from repro.core import spill
    want = _host_q3()

    def _fence(*a, **kw):
        raise AssertionError("host hash join entered on the device path")

    monkeypatch.setattr(ex, "_hash_join", _fence)
    monkeypatch.setattr(spill, "partitioned_hash_join", _fence)
    db = _mkdb(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    try:
        res = q3(db).execute(distributed=True).to_pydict()
        assert db.last_stats.device_tier.startswith("join-")
        _assert_matches(res, want, "host-join fence", exact=False)
    finally:
        db.shutdown()


def test_fence_partials_never_finalized_on_host(monkeypatch):
    """Assembly is device-resident: the (n_groups, K) carry must be
    finalized/compacted by the jitted assembly step, never by the host
    ``finalize_partials``."""
    from repro.core import parallel as par
    want = _host_q3()

    def _fence(*a, **kw):
        raise AssertionError("partials reached host finalize_partials")

    monkeypatch.setattr(par, "finalize_partials", _fence)
    db = _mkdb(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    try:
        res = q3(db).execute(distributed=True).to_pydict()
        assert db.last_stats.device_tier.startswith("join-")
        _assert_matches(res, want, "host-finalize fence", exact=False)
    finally:
        db.shutdown()


# ---------------------------------------------------------------------------
# soundness gates: duplicate build keys, NULL probe keys
# ---------------------------------------------------------------------------


def _star(db, dim_rows, group=("fk", "grp")):
    """Small star schema: a fact table probing one dimension build,
    grouped at build-key granularity (the Q3 shape)."""
    rng = np.random.default_rng(11)
    n = 20_000
    db.create_table("dim", dim_rows)
    db.create_table("fact", {
        "fk": rng.integers(0, 180, n).astype(np.int64),
        "v": rng.standard_normal(n),
    })
    return (db.scan("fact")
            .join(db.scan("dim"), left_on="fk", right_on="k")
            .group_by(*group)
            .agg(s=("sum", Col("v")), n=("count", None))
            .order_by(*group))


def test_duplicate_build_keys_fall_back_to_host_join():
    """The dupmax witness: a duplicated build key would double-count in
    the dense build matrix, so the device join must refuse at runtime
    and the host join must produce the (duplicated-row) truth."""
    dim = {
        "k": np.concatenate([np.arange(200),
                             np.asarray([7])]).astype(np.int64),
        "grp": np.concatenate([np.arange(200) % 5,
                               np.asarray([3])]).astype(np.int64),
    }
    dev = startup(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    host = startup()
    try:
        qd, qh = _star(dev, dim), _star(host, dim)
        got = qd.execute(distributed=True).to_pydict()
        assert dev.last_stats.device_tier == ""      # witness fired
        _assert_matches(got, qh.execute().to_pydict(), "dup keys",
                        exact=False)
    finally:
        dev.shutdown()
        host.shutdown()


@pytest.mark.parametrize("case", ["clean", "duplicate_keys",
                                  "lowering_error"])
def test_device_fallback_reason(monkeypatch, case):
    """``ExecStats.device_fallback`` says why a device-planned query was
    recomputed on the host: empty on a clean device run, the declared
    fallback's reason for duplicate build keys, and the exception class
    of any other failure (here a step the compiler refuses).  The host
    answer is returned either way."""
    from repro.core import parallel as par
    keys = np.arange(200)
    if case == "duplicate_keys":
        keys = np.concatenate([keys, [7]])
    dim = {"k": keys.astype(np.int64), "grp": (keys % 5).astype(np.int64)}
    if case == "lowering_error":
        def refused(*a, **kw):
            raise NotImplementedError("step refused by the compiler")
        monkeypatch.setattr(par, "_cached_join_probe_step", refused)
    dev = startup(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    host = startup()
    try:
        got = _star(dev, dim).execute(distributed=True).to_pydict()
        st = dev.last_stats
        _assert_matches(got, _star(host, dim).execute().to_pydict(), case,
                        exact=False)
    finally:
        dev.shutdown()
        host.shutdown()
    if case == "clean":
        assert st.device_tier == "join-resident"
        assert st.device_fallback == ""
        return
    want = {"duplicate_keys": "_DeviceJoinFallback: duplicate join keys "
                              "in build table dim",
            "lowering_error": "NotImplementedError: step refused by the "
                              "compiler"}[case]
    assert st.device_tier == ""
    assert st.device_fallback == want
    assert f"join-agg core kept on host ({want})" in st.plan_repr


def test_null_probe_keys_never_match():
    """NULL fact keys are sentinel-coded; the probe mask must reject them
    (an inner join drops NULL keys) — differential vs the host join."""
    dim = {"k": np.arange(200).astype(np.int64),
           "grp": (np.arange(200) % 5).astype(np.int64)}
    dev = startup(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    host = startup()
    try:
        qd, qh = _star(dev, dim), _star(host, dim)
        for db in (dev, host):
            db.delete("fact", Col("fk") < Lit(0))    # no-op, keeps shape
            db.append("fact", {"fk": [None] * 64,
                               "v": np.ones(64)})
        got = qd.execute(distributed=True).to_pydict()
        assert dev.last_stats.device_tier.startswith("join-")
        _assert_matches(got, qh.execute().to_pydict(), "null keys",
                        exact=False)
    finally:
        dev.shutdown()
        host.shutdown()


def test_payload_only_grouping_stays_on_host():
    """The device tier groups at build-key granularity: GROUP BY a
    dimension attribute alone (coarser — needs a re-merge) must NOT be
    claimed by the device join, and the host result is authoritative."""
    dim = {"k": np.arange(200).astype(np.int64),
           "grp": (np.arange(200) % 5).astype(np.int64)}
    dev = startup(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    host = startup()
    try:
        qd = _star(dev, dim, group=("grp",))
        qh = _star(host, dim, group=("grp",))
        got = qd.execute(distributed=True).to_pydict()
        assert dev.last_stats.device_tier == ""
        want = qh.execute().to_pydict()
        assert len(np.asarray(got["grp"])) == 5
        _assert_matches(got, want, "payload-only grouping", exact=False)
    finally:
        dev.shutdown()
        host.shutdown()


# ---------------------------------------------------------------------------
# fused device sort on the scan-agg tier
# ---------------------------------------------------------------------------


def test_scan_agg_device_sort_matches_host():
    """ORDER BY over a grouped scan-agg fuses onto the device assembly
    (``device_sorted``) and reproduces the host suffix sort exactly —
    including DESC on an aggregate and a LIMIT."""
    rng = np.random.default_rng(3)
    n = 40_000
    data = {"g": (np.arange(n) % 97).astype(np.int64),
            "v": rng.standard_normal(n)}
    dev = startup(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    host = startup()
    try:
        for db in (dev, host):
            db.create_table("t", data)
        q = lambda d: (d.scan("t").group_by("g")
                       .agg(s=("sum", Col("v")), n=("count", None))
                       .order_by(("s", True), "g", limit=20))
        got = q(dev).execute(distributed=True).to_pydict()
        s = dev.last_stats
        assert s.device_tier == "resident" and s.device_sorted
        want = q(host).execute().to_pydict()
        assert _rows({k: np.round(np.asarray(v, float), 6)
                      for k, v in got.items()}) \
            == _rows({k: np.round(np.asarray(v, float), 6)
                      for k, v in want.items()})
        _assert_matches(got, want, "scan-agg device sort", exact=False)
    finally:
        dev.shutdown()
        host.shutdown()


# ---------------------------------------------------------------------------
# intra-batch skipping: gathered boundary batches
# ---------------------------------------------------------------------------


def test_intra_batch_gather_reduces_h2d_bit_identically():
    """Block-clustered alternating data, one 32768-row batch: every other
    imprint block qualifies, so the batch is live but half its blocks are
    dead — the gathered trace uploads only candidate slots.  h2d bytes
    drop, ``bytes_skipped_h2d`` accounts the savings, and the result is
    bit-identical to the ungathered run (and the host)."""
    n = 16 * IMPRINT_BLOCK
    blk_vals = np.where(np.arange(16) % 2 == 0, 100, 900)
    rng = np.random.default_rng(5)
    data = {"ship": np.repeat(blk_vals, IMPRINT_BLOCK).astype(np.int32),
            "qty": rng.integers(1, 51, n).astype(np.float64),
            "flag": np.asarray(["A", "N", "R"],
                               dtype=object)[rng.integers(0, 3, n)]}

    def mk(**kw):
        db = startup(**kw)
        db.create_table("li", data, types={"ship": DBType.DATE})
        return db

    def q(db):
        return (db.scan("li").filter(Col("ship") <= Lit(500))
                .group_by("flag")
                .agg(total=("sum", Col("qty")), n=("count", None))
                .order_by("flag"))

    on = mk(device_budget=64 << 20, device_batch_rows=n)
    off = mk(device_budget=64 << 20, device_batch_rows=n,
             data_skipping=False)
    host = mk()
    try:
        r_on = q(on).execute(distributed=True).to_pydict()
        r_off = q(off).execute(distributed=True).to_pydict()
        s_on, s_off = on.last_stats, off.last_stats
        # one live batch, so ALL savings here are intra-batch gather
        assert s_on.bytes_skipped_h2d > 0
        assert s_on.device_bytes_h2d < s_off.device_bytes_h2d
        assert s_off.bytes_skipped_h2d == 0
        _assert_matches(r_on, r_off, "gather on/off", exact=True)
        _assert_matches(r_on, q(host).execute().to_pydict(), "vs host",
                        exact=False)
    finally:
        on.shutdown()
        off.shutdown()
        host.shutdown()


# ---------------------------------------------------------------------------
# ranked build keys on the benchmark's TPC-H generator (sparse O_ORDERKEY)
# ---------------------------------------------------------------------------

SPEC_SEED = 2**31 + 17


def _spec_data() -> dict:
    """Q3's columns at 1% of SF 1 from the benchmark's generator, whose
    order keys are the specification's (the first 8 of every 32)."""
    data = spec_gen.generate(0.01, SPEC_SEED, list(q3_ref.COLUMNS))
    return {t: {c: data[t][c] for c in cols}
            for t, cols in q3_ref.COLUMNS.items()}


def _with(col, data) -> "spec_gen.Col":
    return spec_gen.Col(col.kind, data, col.scale, col.heap)


def _rows_where(table: dict, keep) -> dict:
    return {c: _with(col, col.data[keep]) for c, col in table.items()}


def _spec_db(data, **kw):
    db = startup(**kw)
    load(db, data)
    return db


def _q3_device_host(data):
    """Q3 through SQL on the device join and on the host executor, which
    must agree; returns the device answer and its stats."""
    dev = _spec_db(data, device_budget=64 << 20,
                   device_batch_rows=BATCH_ROWS)
    host = _spec_db(data)
    try:
        got = dev.sql(q3_ref.SQL).execute(distributed=True).to_pydict()
        st = dev.last_stats
        want = host.sql(q3_ref.SQL).execute().to_pydict()
    finally:
        dev.shutdown()
        host.shutdown()
    _assert_matches(got, want, "device vs host", exact=False)
    return got, st


def _assert_reference(got: dict, want: dict):
    checks = q3_ref.compare(got, want)
    assert checks["q3_mismatch"] == 0, checks
    assert checks["q3_rel_err"] <= q3_ref.LIMITS["q3_rel_err"], checks


def test_ranked_probe_keys_outside_the_build_never_match():
    """Lineitem keys between two order keys (next to a qualifying order,
    where ``searchsorted`` lands on its rank), below the smallest and
    above the largest find no order: the device drops them as the host
    and the reference (over the rows that can match) do.  Also the rank
    span and counters of a ranked build."""
    data = _spec_data()
    li, od = data["lineitem"], data["orders"]
    okeys = od["o_orderkey"].data
    quals = np.asarray(sorted(q3_ref.groups(data)), np.int64)
    near = np.concatenate([quals - 1, quals + 1])
    near = near[~np.isin(near, okeys)]
    orphans = np.concatenate([near, [-5, 0, okeys.min() - 1,
                                     okeys.max() + 1, okeys.max() + 1000]])
    assert near.size >= 10
    rows = np.flatnonzero(li["l_shipdate"].data > spec_gen.day("1995-03-15"))
    rows = rows[::max(1, rows.size // orphans.size)][:orphans.size]
    lkey = li["l_orderkey"].data.copy()
    lkey[rows] = orphans
    data["lineitem"] = {**li, "l_orderkey": _with(li["l_orderkey"], lkey)}
    got, st = _q3_device_host(data)
    assert st.device_tier == "join-resident" and not st.device_fallback
    assert (st.join_ranked_builds, st.join_rank_keys) == (1, okeys.size)
    assert st.span_n["rank"] == 1 and st.span_ms["rank"] > 0
    matched = {**data, "lineitem": _rows_where(data["lineitem"],
                                               np.isin(lkey, okeys))}
    _assert_reference(got, q3_ref.reference(matched))


def test_ranked_null_keys_never_match():
    """NULL order keys on either side of a ranked join: a NULL lineitem
    key matches nothing, and a NULL order key is no build row (it is not
    among the ranked keys), so the lineitems of those orders drop out."""
    data = _spec_data()
    li, od = data["lineitem"], data["orders"]
    sent = NULL_SENTINEL[DBType.INT64]
    quals = np.asarray(sorted(q3_ref.groups(data)), np.int64)
    okey = od["o_orderkey"].data.copy()
    okey[np.isin(okey, quals[::3])] = sent
    lkey = li["l_orderkey"].data.copy()
    lkey[np.isin(lkey, quals[1::3])] = sent
    data["orders"] = {**od, "o_orderkey": _with(od["o_orderkey"], okey)}
    data["lineitem"] = {**li, "l_orderkey": _with(li["l_orderkey"], lkey)}
    got, st = _q3_device_host(data)
    assert st.device_tier == "join-resident" and not st.device_fallback
    assert st.join_rank_keys == np.count_nonzero(okey != sent)
    _assert_reference(got, q3_ref.reference({
        "customer": data["customer"],
        "orders": _rows_where(data["orders"], okey != sent),
        "lineitem": _rows_where(data["lineitem"], lkey != sent)}))


def test_ranked_duplicate_build_keys_fall_back_to_host_join():
    """A repeated sparse order key ranks twice into one build row: the
    dupmax witness records ``_DeviceJoinFallback`` and the host join
    answers, each lineitem of such an order joined twice."""
    data = _spec_data()
    od = data["orders"]
    quals = np.asarray(sorted(q3_ref.groups(data)), np.int64)
    twice = np.isin(od["o_orderkey"].data, quals[:5])
    rows = np.concatenate([np.arange(twice.size), np.flatnonzero(twice)])
    data["orders"] = _rows_where(od, rows)
    got, st = _q3_device_host(data)
    assert st.device_tier == ""
    assert st.device_fallback == ("_DeviceJoinFallback: duplicate join "
                                  "keys in build table orders")
    want = q3_ref.reference(_spec_data())
    doubled = {k: (2 * v[0], v[1], v[2]) if k in quals[:5] else v
               for k, v in want["_groups"].items()}
    top = sorted(doubled.items(), key=lambda kv: (-kv[1][0], kv[1][1]))
    top = top[:q3_ref.LIMIT]
    _assert_reference(got, {
        "l_orderkey": np.array([k for k, _ in top], np.int64),
        "revenue": np.array([v[0] for _, v in top]),
        "_groups": doubled})


def test_ranked_keys_rebuild_after_append():
    """An append to orders (and lineitem) adds order keys: the ranks are
    built again for the new version, the old ranks leave HBM, and the
    answer is the host's and the reference's over all rows."""
    data = _spec_data()
    od, li = data["orders"], data["lineitem"]
    quals = np.asarray(sorted(q3_ref.groups(data)), np.int64)[:40]
    src = np.flatnonzero(np.isin(od["o_orderkey"].data, quals))
    new_keys = spec_gen.order_keys(od["o_orderkey"].data.size + src.size)[
        -src.size:]
    lsrc = np.flatnonzero(np.isin(li["l_orderkey"].data, quals))
    remap = dict(zip(od["o_orderkey"].data[src], new_keys))
    new_orders = {c: col.data[src] for c, col in od.items()}
    new_orders["o_orderkey"] = new_keys
    new_lines = {c: col.data[lsrc] for c, col in li.items()}
    new_lines["l_orderkey"] = np.array(
        [remap[k] for k in li["l_orderkey"].data[lsrc]], np.int64)
    new_lines["l_extendedprice"] = new_lines["l_extendedprice"] + 1
    dev = _spec_db(data, device_budget=64 << 20,
                   device_batch_rows=BATCH_ROWS)
    host = _spec_db(data)
    try:
        dev.sql(q3_ref.SQL).execute(distributed=True)
        assert dev.last_stats.join_rank_keys == od["o_orderkey"].data.size
        for db in (dev, host):
            db.append("orders", new_orders)
            db.append("lineitem", new_lines)
        got = dev.sql(q3_ref.SQL).execute(distributed=True).to_pydict()
        st = dev.last_stats
        want = host.sql(q3_ref.SQL).execute().to_pydict()
        ranks = [k for k in dev.device_manager._blocks
                 if k[1] == "#ranks:o_orderkey"]
    finally:
        dev.shutdown()
        host.shutdown()
    assert st.device_tier == "join-resident" and not st.device_fallback
    assert st.join_rank_keys == od["o_orderkey"].data.size + src.size
    assert len(ranks) == 1, ranks
    _assert_matches(got, want, "after append", exact=False)
    both = {t: {c: _with(col, np.concatenate([col.data, extra[c]]))
                for c, col in data[t].items()}
            for t, extra in (("orders", new_orders),
                             ("lineitem", new_lines))}
    _assert_reference(got, q3_ref.reference({**data, **both}))


def test_ranked_keys_changed_since_planning_fall_back():
    """A plan's ranked keys are held against the table that
    ``DistributedJoinAgg`` streams: an order appended between planning and
    its construction makes it refuse (``_DeviceJoinFallback``), so the new
    order's lineitems are never dropped for want of a rank."""
    from repro.core.parallel import DistributedJoinAgg, _DeviceJoinFallback
    from repro.core.physplan import default_mesh, plan_physical
    data = _spec_data()
    od = data["orders"]
    dev = _spec_db(data, device_budget=64 << 20,
                   device_batch_rows=BATCH_ROWS)
    try:
        spec = plan_physical(dev.sql(q3_ref.SQL).plan, dev,
                             distributed=True).join_agg
        assert [b.ranked is not None for b in spec.builds] == [False, True]
        extra = {c: col.data[:1] for c, col in od.items()}
        extra["o_orderkey"] = od["o_orderkey"].data[-1:] + 1
        dev.append("orders", extra)
        with pytest.raises(_DeviceJoinFallback,
                           match="join keys of build table orders changed"):
            DistributedJoinAgg(dev, spec, default_mesh())
    finally:
        dev.shutdown()
