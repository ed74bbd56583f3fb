"""Device-tier joins and sorts (physplan join-agg matching + the
DistributedJoinAgg streams + device-resident assembly).

Differential budget-matrix contracts:

* **Bit-identity**: TPC-H Q3 across device budgets {unlimited, 64 MiB,
  4 MiB, 2 MiB} x skipping {on, forced-off} is *bit-identical* in every
  device cell — the budget only changes residency (resident vs streamed),
  never a result byte — and every cell matches the host join tier.
* **Peak accounting**: ``device_bytes_peak <= device_budget`` in every
  budgeted cell; the 2 MiB cell actually streams (``join-streamed``).
* **Fences**: monkeypatch fences prove the host hash join is never
  entered on the device path and the (n_groups, K) partial matrix is
  never finalized on host (assembly is device-resident).
* **Soundness gates**: duplicate build keys trip the on-device
  uniqueness witness and fall back to the (correct) host join; NULL
  probe keys never match.
* **Fused ORDER BY**: the device lexsort permutation equals the host
  suffix sort's (``device_sorted`` claims the fusion), for both the
  join tier and the scan-agg tier.
"""

import numpy as np
import pytest

from repro.core import Col, startup
from repro.core.expression import Lit
from repro.core.indexes import IMPRINT_BLOCK
from repro.core.types import DBType
from repro.data.tpch import generate
from repro.data.tpch_queries import q3

DEVICE_BUDGETS = (None, 64 << 20, 4 << 20, 2 << 20)
BATCH_ROWS = 8192          # small enough that the 2 MiB cell streams

_TPCH = generate(0.01, 7)
_Q3_TABLES = ("customer", "orders", "lineitem")


def _mkdb(**kw):
    db = startup(**kw)
    for name in _Q3_TABLES:
        cols, types, scales = _TPCH[name]
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


@pytest.fixture(scope="module")
def host_q3():
    db = _mkdb()
    try:
        yield q3(db).execute().to_pydict()
    finally:
        db.shutdown()


@pytest.fixture(scope="module")
def device_cells():
    """Q3 in every (device_budget, skipping) cell, one cold db per cell."""
    out = {}
    for budget in DEVICE_BUDGETS:
        for skipping in (True, False):
            db = _mkdb(device_budget=budget,
                       device_batch_rows=BATCH_ROWS,
                       data_skipping=skipping)
            try:
                res = q3(db).execute(distributed=True).to_pydict()
                s = db.last_stats
                out[budget, skipping] = (
                    res, s.device_tier, s.device_sorted,
                    s.device_bytes_peak)
            finally:
                db.shutdown()
    return out


def test_q3_matrix_runs_on_device(device_cells):
    """Budgeted cells run the device join; the tight 2 MiB budget must
    actually stream (resident state exceeds it), and the lifetime HBM
    peak stays under the budget in every budgeted cell."""
    for (budget, skipping), (_res, tier, _srt, peak) in device_cells.items():
        if budget is not None:
            assert tier.startswith("join-"), (budget, skipping, tier)
            assert peak <= budget, (budget, skipping, peak)
    assert device_cells[2 << 20, True][1] == "join-streamed"
    assert device_cells[64 << 20, True][1] == "join-resident"


def test_q3_matrix_bit_identical(device_cells):
    """The budget (and skipping) are pure optimizations: every device
    cell that ran the join tier returns byte-identical results."""
    ran = {k: v for k, v in device_cells.items()
           if v[1].startswith("join-")}
    assert len(ran) >= 6
    items = list(ran.items())
    ref_key, (ref, *_rest) = items[0]
    for key, (res, *_s) in items[1:]:
        _assert_matches(res, ref, f"{key} vs {ref_key}", exact=True)


def test_q3_matrix_matches_host(device_cells, host_q3):
    """Every device cell agrees with the host join tier (same rows, same
    order — the fused device sort reproduces the suffix sort)."""
    for key, (res, tier, sorted_, _peak) in device_cells.items():
        _assert_matches(res, host_q3, f"{key} tier={tier}", exact=False)
        if tier.startswith("join-"):
            assert sorted_, key     # Q3's ORDER BY ... LIMIT 10 fused


def test_q3_explain_annotates_device_join_and_sort():
    db = _mkdb(device_budget=64 << 20)
    try:
        txt = q3(db).explain(physical=True, distributed=True)
        assert ":: device-join" in txt
        assert ":: device-sort" in txt
        assert "mode=resident" in txt
    finally:
        db.shutdown()


# ---------------------------------------------------------------------------
# fences: the device path must never touch the host join or finalize
# ---------------------------------------------------------------------------


def test_fence_host_join_never_entered(monkeypatch, host_q3):
    """Poison both host join kernels: a device-tier Q3 that silently fell
    back to the host join fails loudly."""
    from repro.core import executor as ex
    from repro.core import spill

    def _fence(*a, **kw):
        raise AssertionError("host hash join entered on the device path")

    monkeypatch.setattr(ex, "_hash_join", _fence)
    monkeypatch.setattr(spill, "partitioned_hash_join", _fence)
    db = _mkdb(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    try:
        res = q3(db).execute(distributed=True).to_pydict()
        assert db.last_stats.device_tier.startswith("join-")
        _assert_matches(res, host_q3, "host-join fence", exact=False)
    finally:
        db.shutdown()


def test_fence_partials_never_finalized_on_host(monkeypatch, host_q3):
    """Assembly is device-resident: the (n_groups, K) carry must be
    finalized/compacted by the jitted assembly step, never by the host
    ``finalize_partials``."""
    from repro.core import parallel as par

    def _fence(*a, **kw):
        raise AssertionError("partials reached host finalize_partials")

    monkeypatch.setattr(par, "finalize_partials", _fence)
    db = _mkdb(device_budget=64 << 20, device_batch_rows=BATCH_ROWS)
    try:
        res = q3(db).execute(distributed=True).to_pydict()
        assert db.last_stats.device_tier.startswith("join-")
        _assert_matches(res, host_q3, "host-finalize fence", exact=False)
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


def test_duplicate_build_keys_fall_back_to_host_join(host_q3):
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
