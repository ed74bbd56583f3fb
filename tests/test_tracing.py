"""Spans on the device query path (core/tracing.py).

Contracts under test:

* span totals accumulate per name in the query's ``ExecStats``
  (``span_ms`` / ``span_n``), nested spans each counting their whole
  time, with the profiler off;
* two threads running different device-tier queries get disjoint totals,
  each no larger than its own wall time;
* ``step`` closes once per live batch: a zone-map-skipped batch adds none;
  a resident batch gets no other span, a streamed one at most an ``h2d``;
* ``programs_built`` counts the step programs a query built: at least one
  on a new shape, none on a repeat;
* every step program has a stable name (``jit_scan_agg_step``, ...), and
  the spans reach a profiler trace as ``mdb.<name>`` host events.
"""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import Col, startup
from repro.core import parallel as par
from repro.core import tracing
from repro.core.executor import ExecStats
from repro.core.expression import Lit
from repro.core.indexes import IMPRINT_BLOCK
from repro.core.types import DBType
from repro.data.tpch import generate
from repro.data.tpch_queries import q3

BATCH = 4096
N = 16 * IMPRINT_BLOCK                 # 8 batches of BATCH rows
N_BATCHES = N // BATCH


def _data():
    """Sorted by ``ship`` so its zone maps prune; the other columns are
    unsorted and prune nothing."""
    rng = np.random.default_rng(11)
    flags = np.asarray(["A", "N", "R"], dtype=object)
    return {"ship": np.sort(rng.integers(8000, 9200, N)).astype(np.int32),
            "qty": rng.integers(1, 51, N).astype(np.float64),
            "price": np.round(rng.uniform(900, 105000, N), 2),
            "flag": flags[rng.integers(0, 3, N)]}


_DATA = _data()


def _mkdb(device_budget):
    db = startup(device_budget=device_budget, device_batch_rows=BATCH)
    db.create_table("li", _DATA, types={"ship": DBType.DATE})
    return db


@pytest.fixture(scope="module")
def db():
    db = _mkdb(64 << 20)
    yield db
    db.shutdown()


def _grouped(db):
    return (db.scan("li").filter(Col("qty") < Lit(40.0))
            .group_by("flag").agg(sq=("sum", "qty"), n=("count", None)))


def _scalar(db):
    return (db.scan("li").filter(Col("price") > Lit(1000.0))
            .agg(rev=("sum", Col("price") * Col("qty"))))


def test_span_totals_accumulate_and_nest():
    st = ExecStats()
    with tracing.span("outer", st):
        for _ in range(3):
            with tracing.span("inner", st):
                time.sleep(0.002)
    assert st.span_n == {"outer": 1, "inner": 3}
    assert st.span_ms["inner"] >= 6.0
    assert st.span_ms["outer"] >= st.span_ms["inner"]
    # a span opened at its first piece of work: never opened adds nothing,
    # opened and closed twice counts once
    lazy = tracing.span("lazy", st)
    assert lazy.close() == 0.0 and "lazy" not in st.span_n
    lazy.open()
    lazy.open()
    ms = lazy.close()
    assert lazy.close() == 0.0
    assert st.span_n["lazy"] == 1 and st.span_ms["lazy"] == ms


def test_concurrent_queries_keep_their_own_totals(db):
    """Two query shapes, two threads each, on one database: every query's
    stats hold exactly one query, one batch loop and its own batches."""
    for q in (_grouped, _scalar):                  # warm: build, upload
        q(db).execute(distributed=True)
    runs, errors = [], []

    def client(q):
        try:
            for _ in range(4):
                t0 = time.perf_counter()
                q(db).execute(distributed=True)
                wall_ms = (time.perf_counter() - t0) * 1e3
                runs.append((db.last_stats, wall_ms))
        except Exception as e:       # surfaced by the assertion below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(q,))
               for q in (_grouped, _scalar, _grouped, _scalar)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(runs) == 16
    assert len({id(st) for st, _ in runs}) == 16
    assert len({st.query_id for st, _ in runs}) == 16
    for st, wall_ms in runs:
        assert st.device_tier == "resident"
        assert st.span_n["query"] == 1
        assert st.span_n["prepare"] == 1
        assert st.span_n["device_lock"] == 1
        assert st.span_n["loop"] == 1
        assert st.span_n["step"] == N_BATCHES
        assert "h2d" not in st.span_n and st.programs_built == 0
        assert st.span_ms["query"] <= wall_ms
        assert st.span_ms["loop"] <= st.span_ms["query"]
        assert st.device_lock_wait_ms == pytest.approx(
            st.span_ms["device_lock"])


def test_step_spans_count_live_batches(db, monkeypatch):
    """A filter on the sorted column leaves some batches to the zone maps:
    they get no step span."""
    seen = []
    real = par.DistributedScanAgg._run_locked

    def spy(self, *a, **kw):
        seen.append((len(self.live_batches), self.n_batches))
        return real(self, *a, **kw)

    monkeypatch.setattr(par.DistributedScanAgg, "_run_locked", spy)
    cut = int(np.quantile(_DATA["ship"], 0.3))
    (db.scan("li").filter(Col("ship") <= Lit(cut))
     .group_by("flag").agg(n=("count", None)).execute(distributed=True))
    st = db.last_stats
    [(live, n_batches)] = seen
    assert 0 < live < n_batches == N_BATCHES
    assert st.span_n["step"] == live
    assert st.span_n.get("h2d", 0) <= live


@pytest.mark.parametrize("budget, tier", [(64 << 20, "resident"),
                                          (192 << 10, "streamed")])
def test_spans_per_batch(budget, tier):
    """Warm: a resident batch gets its step span alone; a streamed batch
    its step and at most one h2d span (its blocks are built and copied
    together, ahead of the step that reads them)."""
    db = _mkdb(budget)
    try:
        _grouped(db).execute(distributed=True)
        _grouped(db).execute(distributed=True)
        st = db.last_stats
    finally:
        db.shutdown()
    assert st.device_tier == tier
    assert st.span_n["step"] == N_BATCHES
    if tier == "resident":
        assert "h2d" not in st.span_n
    else:
        assert 1 <= st.span_n["h2d"] <= N_BATCHES
        assert st.device_bytes_h2d > 0


def test_programs_built_counts_new_programs_only(db):
    def q():
        # a literal no other query here uses: a step no query built yet
        return (db.scan("li").filter(Col("qty") <= Lit(17.5))
                .agg(s=("sum", "price")))

    q().execute(distributed=True)
    first = db.last_stats
    q().execute(distributed=True)
    again = db.last_stats
    assert first.programs_built >= 1
    assert first.span_n["compile"] >= first.programs_built
    assert again.programs_built == 0 and "compile" not in again.span_n


@pytest.fixture(scope="module")
def tpch_db():
    data = generate(0.01, 7)
    db = startup(device_budget=64 << 20, device_batch_rows=8192)
    for name in ("customer", "orders", "lineitem"):
        cols, types, scales = data[name]
        db.create_table(name, cols, types=types, scales=scales)
    yield db
    db.shutdown()


@pytest.mark.parametrize("factory, name", [
    ("build_batch_step", "scan_agg"),
    ("build_join_build_step", "join_build"),
    ("build_join_probe_step", "join_probe")])
def test_step_programs_are_named(tpch_db, monkeypatch, factory, name):
    """The module each step program lowers to is named for it, so a device
    trace tells the scan step from the join's build and probe."""
    modules = []
    real = getattr(par, factory)

    def recording(*a, **kw):
        init, step = real(*a, **kw)
        modules.append(init.lower().as_text().split(" ", 2)[1])

        def call(*args):
            modules.append(step.lower(*args).as_text().split(" ", 2)[1])
            return step(*args)

        return init, call

    monkeypatch.setattr(par, factory, recording)
    monkeypatch.setattr(par, "_STEP_CACHE", {})
    if name == "scan_agg":
        (tpch_db.scan("lineitem").group_by("l_returnflag")
         .agg(n=("count", None)).execute(distributed=True))
        assert tpch_db.last_stats.device_tier == "resident"
    else:
        q3(tpch_db).execute(distributed=True)
        assert tpch_db.last_stats.device_tier == "join-resident"
    assert f"@jit_{name}_init" in modules
    assert f"@jit_{name}_step" in modules


def test_spans_reach_the_profiler_trace(db, tmp_path):
    import jax
    from jax.profiler import ProfileData
    _grouped(db).execute(distributed=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _grouped(db).execute(distributed=True)
    finally:
        jax.profiler.stop_trace()
    st = db.last_stats
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(tracing.PREFIX)]
    # the query id is metadata of the event, not part of its name
    assert names.count("mdb.query") == 1
    assert names.count("mdb.step") == st.span_n["step"] == N_BATCHES
    assert names.count("mdb.loop") == 1
