"""Smoke test of the device tier on TPU: TPC-H Q1, Q6 and Q3 through the
embedded API, checked against the host in-memory executor.

    python chip_smoke.py [--sf 1] [--chips 4]

One process drives the chip.  With no ``--chips`` option the run covers
three device-budget cells on whatever devices JAX reports (one chip on a
one-chip host):

* ``zero-config`` -- ``startup()`` with no budget;
* ``resident``    -- an 8 GiB ``device_budget``; every query runs twice and
  the repeat must be served from the device block cache;
* ``streamed``    -- a budget below the smallest resident footprint of the
  three queries and above their streaming working sets, which forces the
  streamed tiers with prefetch and eviction.

``--chips 4`` runs only the zero-config cell, on the default mesh over four
devices, and its comparison with the host executor.

Every query must run on the tier its cell implies, record no device
fallback (``ExecStats.device_fallback``), report ``device_sorted`` wherever
EXPLAIN shows a fused device sort, and agree with the host executor: exactly
for integer, date and string columns, within rtol 1e-9 for floats.  Each
printed wall time is one smoke reading, not a benchmark number: the first
cell's times include compilation, later cells run warm.

The last line of standard output is ``{"ok": true, "device": {...}}`` only
when every check passed on a TPU; otherwise the script exits non-zero
without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "src"))

TABLES = ["lineitem", "orders", "customer"]
QUERIES = ("q1", "q6", "q3")
RESIDENT_BUDGET = 8 << 30
SEED = 7                        # data generator seed of every cell
FLOAT_RTOL = 1e-9


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def same_answer(got: dict, want: dict, what: str) -> None:
    """Exact for integer/date/string columns, rtol 1e-9 for floats (the
    device merges partials in another order than the host)."""
    check(list(got) == list(want),
          f"{what}: columns {list(got)} != host {list(want)}")
    for name, w in want.items():
        g = np.asarray(got[name])
        w = np.asarray(w)
        check(g.shape == w.shape,
              f"{what}.{name}: shape {g.shape} != host {w.shape}")
        if w.dtype.kind == "f":
            ok = np.allclose(g, w, rtol=FLOAT_RTOL, atol=0.0, equal_nan=True)
        else:
            ok = np.array_equal(g, w)
        check(ok, f"{what}.{name}: device {g[:5]} != host {w[:5]}")


def entry_points(db) -> dict:
    """(query, entry) -> Query: each query through the builder API and
    through the SQL front end."""
    from repro.data.tpch_queries import ALL_QUERIES, SQL_QUERIES
    out = {}
    for name in QUERIES:
        out[(name, "builder")] = ALL_QUERIES[name](db)
        out[(name, "sql")] = db.sql(SQL_QUERIES[name])
    return out


def streamed_budget(db) -> int:
    """A device budget under which every query must stream: above each
    query's streaming working set, below its resident footprint."""
    from repro.core.physplan import plan_physical
    need, resident = 0, []
    for query in entry_points(db).values():
        phys = plan_physical(query.plan, db, distributed=True)
        if phys.join_geometry is not None:
            g = phys.join_geometry
            need = max(need, g.working_bytes)
        elif phys.geometry is not None:
            g = phys.geometry
            need = max(need, 2 * g.batch_bytes)
        else:
            continue                    # planned for the host at this scale
        resident.append(g.resident_bytes)
    budget = (need + min(resident)) // 2
    check(need < budget < min(resident),
          f"no streamed budget exists: working set {need} B, smallest "
          f"resident footprint {min(resident)} B")
    return budget


def want_tier(cell: str, name: str, sf: float) -> str:
    """The tier each query must report.  Q3 joins on o_orderkey, whose
    dense domain (1.5M keys per unit of scale) fits the device join only up
    to MAX_DEVICE_JOIN_DOMAIN; above that the planner keeps it on the host
    ("")."""
    from repro.core.physplan import MAX_DEVICE_JOIN_DOMAIN
    from repro.data.tpch import SF_ROWS
    mode = "streamed" if cell == "streamed" else "resident"
    if name != "q3":
        return mode
    if SF_ROWS["orders"] * sf > MAX_DEVICE_JOIN_DOMAIN:
        return ""
    return "join-" + mode


def run_cell(cell: str, db, host: dict, sf: float) -> None:
    """Run every entry point with ``distributed=True`` and check tier,
    fallback, fused sort and answer.  In the resident cell the SQL run
    repeats the builder run's plan, so it must hit the block cache."""
    for (name, entry), query in entry_points(db).items():
        what = f"{cell}/{name}/{entry}"
        tier = want_tier(cell, name, sf)
        fused = ":: device-sort" in query.explain(physical=True,
                                                  distributed=True)
        t0 = time.perf_counter()
        got = query.execute(distributed=True).to_pydict()
        secs = time.perf_counter() - t0
        st = db.last_stats
        check(st.device_fallback == "",
              f"{what}: device fallback: {st.device_fallback}")
        check(st.device_tier == tier,
              f"{what}: tier {st.device_tier!r}, want {tier!r}")
        check(st.device_sorted == fused,
              f"{what}: device_sorted={st.device_sorted}, "
              f"EXPLAIN fused sort={fused}")
        if cell == "resident" and entry == "sql" and tier:
            check(st.device_cache_hits > 0 and st.device_bytes_h2d == 0,
                  f"{what}: repeat missed the block cache "
                  f"(hits={st.device_cache_hits}, h2d={st.device_bytes_h2d})")
        same_answer(got, host[(name, entry)], what)
        print(f"{what}: tier={st.device_tier} sorted={st.device_sorted} "
              f"h2d={st.device_bytes_h2d} hits={st.device_cache_hits} "
              f"evictions={st.device_evictions} wall={secs:.3f}s",
              flush=True)


def run(sf: float, chips) -> None:
    from repro.core import startup
    from repro.core.device_cache import jax_runtime
    from repro.core.physplan import default_mesh
    from repro.data import tpch

    jax = jax_runtime()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)
    if chips is not None:
        n = default_mesh().devices.size
        check(n == chips, f"default mesh spans {n} devices, want {chips}")
    cells = ["zero-config"] if chips else \
        ["zero-config", "resident", "streamed"]
    budgets = {"zero-config": None, "resident": RESIDENT_BUDGET}
    host = None
    for cell in cells:
        t0 = time.perf_counter()
        db = startup(device_budget=budgets[cell])
        tpch.load_into(db, sf, seed=SEED, tables=TABLES)
        print(f"{cell}: sf={sf} device_budget={budgets[cell]} "
              f"load={time.perf_counter() - t0:.1f}s", flush=True)
        if host is None:
            # every cell generates the same data from the seed: one host
            # in-memory reference, and one geometry for the streamed budget
            host = {k: q.execute().to_pydict()
                    for k, q in entry_points(db).items()}
            if "streamed" in cells:
                budgets["streamed"] = streamed_budget(db)
        run_cell(cell, db, host, sf)
        db.shutdown()
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--chips", type=int, default=None,
                    help="run only the mesh path over this many devices")
    args = ap.parse_args(argv)

    from repro.core.device_cache import jax_runtime
    jax = jax_runtime()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {devices[0].platform})",
              file=sys.stderr)
        return 2
    try:
        run(args.sf, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
