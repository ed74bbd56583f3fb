"""Concurrent serving layer: throughput and tail latency vs client threads.

MonetDBLite is embedded in multi-threaded analytical hosts, so the unit
under test is the whole serving stack at once: N client threads each run a
repeat-heavy query mix (the plan cache's target workload) against one
database, contending for one ``memory_budget``/``device_budget`` through
the admission gate and sharing base column blocks through the device cache.

The scaling story is work *elimination*, not CPU parallelism: the cold
cost of a query mix — plan lowering, XLA compilation of the fused steps,
and the host→device upload of every column block — is paid ONCE per
database regardless of how many clients run the mix, because the plan
cache, the locked compiled-step cache and the single-flight block cache
all deduplicate it.  Aggregate throughput therefore grows with N even on
a single core: N clients amortize the same cold work over N times the
queries.  Each thread-count level starts with the engine's compiled-step
cache and JAX's in-memory caches emptied and runs with the persistent
compile cache off, so no traced or compiled step leaks between levels or
in from earlier runs.  Everything runs in one process: a chip belongs to
the process that first touches JAX.

Measured per level N ∈ {1, 2, 4, 8}:

* throughput (queries/s) and P50/P99 per-query latency — the acceptance
  bar is ≥2x the N=1 throughput at N=8 on this mix;
* bit-identity — every client's results equal a serial single-client
  reference run;
* budget invariants — ``peak <= memory_budget`` and
  ``device_bytes_peak <= device_budget`` after every run: admission plus
  atomic ``try_pin`` keep concurrent queries inside the same envelope one
  query gets;
* shared scans — host→device bytes stay at ~one table upload at every N
  (concurrent cold queries attach to one in-flight upload, not N).

Results land in ``BENCH_concurrent.json`` (cwd) for machine consumption.
"""

from __future__ import annotations

import json
import threading
import time

N_ROWS = 400_000
MEMORY_BUDGET = 256 << 20
DEVICE_BUDGET = 256 << 20
THREAD_COUNTS = (1, 2, 4, 8)
QUERIES_PER_THREAD = 12


def _dataset():
    import numpy as np
    rng = np.random.default_rng(23)
    return {
        "g": rng.integers(0, 16, N_ROWS).astype(np.int64),
        "h": rng.integers(0, 5, N_ROWS).astype(np.int64),
        "x": rng.uniform(0, 100, N_ROWS),
        "w": rng.integers(-50, 50, N_ROWS).astype(np.int64),
    }


def _mix(db):
    """Repeat-heavy mix: four distinct device-tier plans cycled by every
    client, so the plan cache, the compiled-step cache and the shared block
    cache see the same queries over and over — the serving layer's target
    workload."""
    from repro.core import Col

    def q1():
        return (db.scan("t").group_by("g")
                .agg(s=("sum", Col("x")), n=("count", None))
                .execute(distributed=True))

    def q2():
        return (db.scan("t").filter(Col("w") > 0).group_by("h")
                .agg(mx=("max", Col("x")), s=("sum", Col("w")))
                .execute(distributed=True))

    def q3():
        return (db.scan("t").group_by("g", "h")
                .agg(s=("sum", Col("w")), a=("avg", Col("x")))
                .execute(distributed=True))

    def q4():
        return (db.scan("t").filter(Col("x") > 5.0).group_by("g")
                .agg(mn=("min", Col("w")), s=("sum", Col("x")))
                .execute(distributed=True))

    return [q1, q2, q3, q4]


def _canon(res):
    import numpy as np
    return {k: np.asarray(v) for k, v in res.to_pydict().items()}


def _run_clients(db, n_threads):
    """Every thread runs the full mix QUERIES_PER_THREAD times; returns
    (wall_seconds, sorted per-query latencies, per-thread results,
    per-thread final device tiers)."""
    mix = _mix(db)
    latencies = [[] for _ in range(n_threads)]
    results = [None] * n_threads
    tiers = [None] * n_threads
    errors = []
    barrier = threading.Barrier(n_threads + 1)

    def client(slot):
        try:
            barrier.wait()
            mine = {}
            for rep in range(QUERIES_PER_THREAD):
                i = rep % len(mix)
                t0 = time.perf_counter()
                r = mix[i]()
                latencies[slot].append(time.perf_counter() - t0)
                mine[i] = _canon(r)
            results[slot] = mine
            # db.last_stats is a thread-local view: this thread sees the
            # stats of ITS final query, untouched by the other clients
            tiers[slot] = db.last_stats.device_tier
        except Exception as e:       # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(s,))
               for s in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join(300)
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    flat = sorted(x for lane in latencies for x in lane)
    return wall, flat, results, tiers


def _pct(sorted_xs, p):
    i = min(len(sorted_xs) - 1, int(round(p / 100 * (len(sorted_xs) - 1))))
    return sorted_xs[i]


def _child(n_threads: int) -> dict:
    """One measurement level: N clients against one cold database, then a
    serial reference for bit-identity."""
    import numpy as np

    from repro.core import startup

    data = _dataset()
    db = startup(memory_budget=MEMORY_BUDGET, device_budget=DEVICE_BUDGET)
    db.create_table("t", data)
    wall, lats, results, tiers = _run_clients(db, n_threads)
    bst = db.buffer_manager.stats
    gate = db.admission_gate.stats

    # every client's final query ran on the device tier, resident
    assert all(t == "resident" for t in tiers), tiers
    # budget invariants survived the whole concurrent run
    assert bst.peak <= MEMORY_BUDGET, (bst.peak, MEMORY_BUDGET)
    assert bst.device_bytes_peak <= DEVICE_BUDGET, \
        (bst.device_bytes_peak, DEVICE_BUDGET)
    assert gate.host_reserved_peak <= MEMORY_BUDGET
    assert gate.device_reserved_peak <= DEVICE_BUDGET

    # bit-identity: serial single-client reference on a fresh database
    # (fresh device cache; the XLA steps are warm by now, which only makes
    # the reference faster, not different — batch geometry is pinned)
    ref_db = startup(memory_budget=MEMORY_BUDGET, device_budget=DEVICE_BUDGET)
    ref_db.create_table("t", data)
    reference = {i: _canon(q()) for i, q in enumerate(_mix(ref_db))}
    for slot_result in results:
        for i, ref in reference.items():
            got = slot_result[i]
            assert set(got) == set(ref)
            for k in ref:
                np.testing.assert_array_equal(got[k], ref[k])
    ref_db.shutdown()

    total = n_threads * QUERIES_PER_THREAD
    level = {"threads": n_threads,
             "wall_seconds": round(wall, 4),
             "qps": round(total / wall, 2),
             "p50_ms": round(_pct(lats, 50) * 1e3, 3),
             "p99_ms": round(_pct(lats, 99) * 1e3, 3),
             "plan_cache_hits": int(bst.plan_cache_hits),
             "plan_cache_misses": int(bst.plan_cache_misses),
             "shared_scan_attaches": int(bst.shared_scan_attaches),
             "admission_waits": int(bst.admission_waits),
             "h2d_bytes": int(bst.device_bytes_h2d),
             "device_bytes_peak": int(bst.device_bytes_peak),
             "peak": int(bst.peak),
             "host_reserved_peak": int(gate.host_reserved_peak),
             "device_reserved_peak": int(gate.device_reserved_peak),
             "bit_identical": True}
    db.shutdown()
    return level


def _cold_level(n_threads: int) -> dict:
    """Run one level with the compiled-step cache and JAX's in-memory
    caches emptied and the persistent compile cache off: each level pays
    (and amortizes) its own tracing, compilation and upload work."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import parallel
    with parallel._STEP_CACHE_LOCK:
        parallel._STEP_CACHE.clear()
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return _child(n_threads)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def run() -> list[str]:
    import jax

    from .common import row

    out_rows: list[str] = []
    res: dict = {"n_rows": N_ROWS, "memory_budget": MEMORY_BUDGET,
                 "device_budget": DEVICE_BUDGET,
                 "devices": len(jax.devices()),
                 "queries_per_thread": QUERIES_PER_THREAD, "levels": {}}
    for n in THREAD_COUNTS:
        level = _cold_level(n)
        res["levels"][str(n)] = level
        out_rows.append(row(f"concurrent_n{n}", level["p50_ms"] / 1e3,
                            f"qps={level['qps']:.0f} "
                            f"p99_ms={level['p99_ms']}"))

    base = res["levels"]["1"]["qps"]
    speedup = res["levels"]["8"]["qps"] / max(base, 1e-9)
    res["throughput_8v1_x"] = round(speedup, 2)
    res["bit_identical"] = all(
        lv["bit_identical"] for lv in res["levels"].values())
    # shared scans: cold upload volume must not grow with client count
    h2d = {lv["threads"]: lv["h2d_bytes"] for lv in res["levels"].values()}
    res["h2d_8v1_x"] = round(h2d[8] / max(h2d[1], 1), 2)
    out_rows.append(row("concurrent_scaling_8v1", 0.0, f"{speedup:.2f}x"))
    out_rows.append(row("concurrent_h2d_8v1", 0.0, f"{res['h2d_8v1_x']}x"))
    with open("BENCH_concurrent.json", "w") as f:
        json.dump(res, f, indent=1)
    return out_rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for line in run():
        print(line)
