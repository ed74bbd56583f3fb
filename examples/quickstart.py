"""Quickstart: the embedded analytical database in five minutes.

Mirrors the paper's embedding interface (§3.2): startup -> connect ->
query/append -> zero-copy export, plus persistence and transactions.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import Col, startup
from repro.core.exchange import export_table

# --- in-memory database (monetdb_startup(NULL)) ---------------------------
db = startup()
rng = np.random.default_rng(0)
n = 100_000
db.create_table("trips", {
    "city": np.asarray(["ams", "nyc", "sfo"], dtype=object)[
        rng.integers(0, 3, n)],
    "distance_km": rng.gamma(2.0, 5.0, n),
    "fare": rng.gamma(3.0, 7.0, n),
})

con = db.connect()
res = con.query("""
    SELECT city, count(*) AS trips, avg(fare) AS avg_fare,
           sum(fare) AS revenue
    FROM trips WHERE distance_km > 5 GROUP BY city ORDER BY revenue DESC
""")
print("SQL result:", res.to_pydict())

# --- builder API + zero-copy export ----------------------------------------
top = (db.scan("trips")
       .filter(Col("fare") > 50)
       .group_by("city")
       .agg(p90_candidates=("count", None), m=("median", "fare"))
       .order_by("city")
       .execute())
frame = export_table(top)                 # lazy, zero-copy for numerics
print("medians:", list(frame["m"]))
print("conversions performed:", frame.conversions,
      "| zero-copy columns:", frame.zero_copies)

# --- transactions (optimistic, snapshot isolation) --------------------------
txn_con = db.connect()
txn_con.begin()
txn_con.append("trips", {"city": np.asarray(["ams"], dtype=object),
                         "distance_km": np.array([1.0]),
                         "fare": np.array([4.5])})
print("inside txn:",
      txn_con.query("SELECT count(*) n FROM trips").to_pydict()["n"][0])
txn_con.rollback()
print("after rollback:",
      db.connect().query("SELECT count(*) n FROM trips").to_pydict()["n"][0])

# --- persistent mode --------------------------------------------------------
# Database is a context manager: shutdown (persist + directory-lock
# release) is guaranteed on scope exit, including on exceptions.
with tempfile.TemporaryDirectory() as d:
    with startup(os.path.join(d, "mydb")) as pdb:
        pdb.create_table("t", {"v": np.arange(10, dtype=np.int64)})
    with startup(os.path.join(d, "mydb")) as pdb2:   # reload from disk
        print("persistent rows:", pdb2.table("t").num_rows)

# --- out-of-core execution under a memory budget ----------------------------
# The paper's standard-RDBMS feature the in-memory competitors lack: pass
# memory_budget= (bytes) and blocking operators (join / group-by / sort)
# spill partitioned run files to disk whenever their working state would
# exceed it — results are bit-identical to in-memory execution.  The
# default (no argument) stays zero-config: unlimited, never spills.
#
# Two spill-pipeline knobs (both default to the fast path):
#   spill_codec="for"  — run files are block-encoded with frame-of-reference
#                        + byte-shuffle on integer key/index streams (2-8x
#                        smaller on sorted/clustered keys; floats pass
#                        through raw); "raw" disables encoding.
#   spill_prefetch=True — a background thread loads partition N+1 while
#                        partition N is processed; prefetched bytes stay
#                        pinned, so tracked peak still respects the budget.
small = startup(memory_budget=256 << 10,          # 256 KiB working-state cap
                spill_codec="for", spill_prefetch=True)
small.create_table("trips", {
    "city": np.asarray(["ams", "nyc", "sfo"], dtype=object)[
        rng.integers(0, 3, n)],
    "distance_km": rng.gamma(2.0, 5.0, n),
    "fare": rng.gamma(3.0, 7.0, n),
})
ooc = (small.scan("trips")
       .group_by("city", "fare")                  # state >> budget: spills
       .agg(n=("count", None))
       .order_by(("n", True), limit=5)
       .execute())
stats = small.buffer_manager.stats
print("out-of-core top groups:", ooc.to_pydict()["n"][:3],
      "| ops spilled:", stats.spilled_ops,
      "| peak tracked bytes:", stats.peak,
      "| spill files live:", small.buffer_manager.active_files)
# BufferStats also reports the pipeline-v2 counters: raw (logical) vs
# actually-written spill bytes, partitions served by the async prefetcher,
# and oversized partitions that were recursively re-split.
print("spilled raw -> stored:", stats.bytes_spilled_raw, "->",
      stats.bytes_spilled_compressed,
      "| prefetch hits:", stats.prefetch_hits,
      "| repartitions:", stats.repartitions)

# --- VARCHAR spilling across dictionary heaps -------------------------------
# VARCHAR columns execute as int32 codes into a duplicate-eliminated,
# order-preserving string heap (paper §3.1).  String-keyed joins spill even
# when the two sides were encoded against *different* heaps; the strategy is
# chosen per key from the heap/budget ratio:
#   * content-equal heaps (same object, or equal fingerprints — e.g. two
#     separately-loaded copies of a table): partition on plain codes;
#   * distinct heaps that fit ~budget/4: merge into one shared dictionary
#     (StringHeap.merge) and recode both sides while spooling;
#   * oversized heaps: spill decoded string bytes (offsets+bytes block
#     codec) and hash-partition on those.
# Group-by and sort on VARCHAR keys spill on their codes directly — a key
# column has one heap, and sorted-order code assignment makes code ranges
# string ranges.  `varchar_spills` (BufferStats and per-query ExecStats)
# counts blocking ops that spilled with VARCHAR keys.
sdb = startup(memory_budget=256 << 10)
sdb.create_table("trips", {
    "city": np.asarray(["ams", "nyc", "sfo"], dtype=object)[
        rng.integers(0, 3, n)],
    "fare": rng.gamma(3.0, 7.0, n),
})
sdb.create_table("cities", {          # separate load -> its own heap
    "city": np.asarray(["ams", "bos", "nyc", "sfo"], dtype=object),
    "tz": np.asarray(["CET", "EST", "EST", "PST"], dtype=object),
})
vj = (sdb.scan("trips")
      .join(sdb.scan("cities"), on="city")     # string keys, distinct heaps
      .group_by("tz").agg(rev=("sum", "fare"))
      .execute())
vstats = sdb.buffer_manager.stats
print("varchar join:", vj.to_pydict(),
      "| varchar spills:", vstats.varchar_spills,
      "| per-query:", sdb.last_stats.varchar_spills)

# --- distributed execution (paper Fig. 2 on whatever mesh exists) ----------
dist = (db.scan("trips").filter(Col("distance_km") > 5)
        .group_by("city").agg(rev=("sum", "fare"))
        .execute(distributed=True))
print("distributed result:", dist.to_pydict())

# --- device tier under an HBM budget ----------------------------------------
# The memory-hierarchy trick one level up: device_budget= (bytes) makes HBM
# a budgeted LRU cache over host memory.  Distributed scans whose columns
# fit stay *resident* — a repeated query is served entirely from the
# cross-query block cache (device_cache_hits, zero new host→device bytes).
# Larger tables *stream* morsel batches (device_batch_rows, default 65536)
# through the cache with double-buffered async prefetch and a partial-
# aggregate carry, evicting consumed blocks — so accelerators whose memory
# is smaller than the table still run the query instead of bailing to the
# host tier.  Results are bit-identical across budgets: the batch
# decomposition, never the budget, fixes the arithmetic.  Budgets too small
# for even one batch fall back to the host tier (which spills if the host
# memory_budget demands it).
hbm = startup(device_budget=32 << 20, device_batch_rows=16_384)
hbm.create_table("trips", {
    "city": np.asarray(["ams", "nyc", "sfo"], dtype=object)[
        rng.integers(0, 3, n)],
    "distance_km": rng.gamma(2.0, 5.0, n),
    "fare": rng.gamma(3.0, 7.0, n),
})
dq = (hbm.scan("trips").filter(Col("distance_km") > 5)
      .group_by("city").agg(rev=("sum", "fare"), nt=("count", None)))
cold = dq.execute(distributed=True)
print("device cold: tier:", hbm.last_stats.device_tier,
      "| h2d bytes:", hbm.last_stats.device_bytes_h2d)
hot = dq.execute(distributed=True)
# BufferStats/ExecStats report the device-tier counters alongside the host
# spill counters: device_bytes_peak, device_bytes_h2d, device_cache_hits,
# device_prefetch_hits, device_evictions, device_writebacks.
dstats = hbm.buffer_manager.stats
print("device hot: cache hits:", hbm.last_stats.device_cache_hits,
      "| new h2d bytes:", hbm.last_stats.device_bytes_h2d,
      "| peak device bytes:", dstats.device_bytes_peak,
      "| evictions:", dstats.device_evictions)

# --- EXPLAIN of physical plans ----------------------------------------------
# Every query — SQL or builder — is lowered through ONE physical planner
# (core/physplan.py) before execution.  explain(physical=True) shows the
# normalized plan with per-operator tier decisions and budget reservations:
#
#   * device-resident  — scan-agg core fully cached in device memory
#   * device-streamed  — core streams morsel batches through the HBM cache
#   * parallel-host    — core matched the device pattern but stays on host
#   * spill            — blocking op expected to exceed memory_budget
#   * in-memory        — fits; runs in RAM
#
# Tier decisions are made from data statistics, not the entry point: SQL
# and builder plans normalize to the same shape (the SQL front-end's
# rename projection folds into the aggregate), so both lower identically
# — one planner, many frontends.  Annotations marked (runtime-refined)
# are plan-time predictions; blocking instructions re-check with actual
# cardinalities through the same policy at runtime.  Device admission is
# biased by the cache's hit history: a table that fits the device budget
# but would occupy more than half of it streams on first touch and flips
# to resident once repeat queries produce cache hits.
print(dq.explain(physical=True, distributed=True))
# The same text is recorded per query on ExecStats:
print("last plan was:\n", hbm.last_stats.plan_repr)

# --- device-tier joins and sorts --------------------------------------------
# Aggregates over inner-join trees (the TPC-H Q3 shape) run on the device
# too: each dimension build becomes a dense (key_domain, 1+payload) matrix
# scatter-added in HBM, verified unique at runtime (duplicate build keys
# fall back to the host join), then the fact table streams through a probe
# step that gathers presence + payload per batch.  Assembly stays
# device-resident — finalize, compact to present groups and, when the
# ORDER BY maps onto group keys/aggregates, the lexsort permutation all
# happen in HBM (ExecStats.device_sorted) and only the surviving top-N
# rows are fetched.  EXPLAIN shows the join core as `:: device-join`
# (mode=resident|streamed from the same byte model as scans) and a fused
# sort as `:: device-sort`:
star = startup(device_budget=32 << 20, device_batch_rows=16_384)
star.create_table("dim_city", {
    "c_id": np.arange(64, dtype=np.int64),     # matcher attributes columns
    "c_pop": rng.integers(10_000, 9_000_000, 64),  # by name: keep them
})                                                 # distinct across tables
star.create_table("rides", {
    "city_id": rng.integers(0, 64, n).astype(np.int64),
    "fare": rng.gamma(3.0, 7.0, n),
})
jq = (star.scan("rides")
      .join(star.scan("dim_city"), left_on="city_id", right_on="c_id")
      .group_by("city_id", "c_pop")
      .agg(rev=("sum", "fare"), nt=("count", None))
      .order_by(("rev", True), limit=5))
print(jq.explain(physical=True, distributed=True))
top5 = jq.execute(distributed=True)
print("top cities:", top5.to_pydict())
print("join tier:", star.last_stats.device_tier,        # join-resident
      "| sort fused on device:", star.last_stats.device_sorted,
      "| peak device bytes:", star.last_stats.device_bytes_peak)
star.shutdown()

# --- imprint-driven data skipping -------------------------------------------
# Paper §3.1's column imprints (per-2048-row zone maps: min/max + a 16-bin
# presence bitmap) now feed the planner: plan_physical derives a per-scan
# skip-set from each range conjunct (`col <op> literal`), and every tier
# consumes it — DistributedScanAgg never uploads a batch whose blocks all
# fail the zone maps, the host filter path never evaluates (or spills rows
# of) a non-qualifying block, and the volcano baseline only materializes
# candidate ranges.  Skipping is sound by construction (candidate sets are
# supersets — proven by a hypothesis property test), version-revalidated
# at execution, and bit-identical on vs off: pass data_skipping=False to
# force it off.  On clustered data a selective filter reads and moves
# proportionally fewer bytes (the example below counts them).  EXPLAIN
# shows the planning-time decision as `(skip: k/N blocks)` on the scan,
# and the counters land in
# BufferStats/ExecStats: blocks_skipped, bytes_skipped_h2d,
# bytes_skipped_spill.
clustered = startup()
clustered.create_table("events", {
    "day": np.sort(rng.integers(0, 365, 8192)).astype(np.int64),
    "amount": rng.gamma(3.0, 7.0, 8192),
})
sel = (clustered.scan("events").filter(Col("day") < 30)
       .agg(total=("sum", "amount"), n=("count", None)))
print(sel.explain(physical=True))           # ...Scan events (skip: k/N blocks)
sel.execute()
print("blocks skipped:", clustered.last_stats.blocks_skipped,
      "| filter bytes never read:",
      clustered.last_stats.bytes_skipped_spill)
clustered.shutdown()

# --- streaming ingest through the delta store --------------------------------
# Appends don't rewrite the column anymore: db.append installs an immutable
# delta chunk (O(chunk) commit + WAL record), scans merge base + tail on
# read (bit-identical across all executors), and a threshold compaction
# folds the tail back into the base when it exceeds delta_compact_fraction
# of the table.  That makes bulk loading a *streaming* operation:
# db.ingest(name, iterable_of_column_dicts) pins one morsel-sized piece at
# a time inside memory_budget, so a table larger than the budget loads
# with tracked peak <= budget.  Epoch-keyed device caching means an append
# only invalidates the delta tail's device blocks — a repeat scan after an
# append re-uploads the tail, not the table.
ing = startup(memory_budget=256 << 10, delta_compact_fraction=0.5)

def trip_chunks(total, step=8_192):
    for s in range(0, total, step):
        m = min(step, total - s)
        yield {"city": np.asarray(["ams", "nyc", "sfo"], dtype=object)[
                   rng.integers(0, 3, m)],
               "fare": rng.gamma(3.0, 7.0, m)}

loaded = ing.ingest("trips", trip_chunks(200_000))   # table >> budget
istats = ing.buffer_manager.stats
print("ingested rows:", loaded,
      "| tracked peak <= budget:", istats.peak <= 256 << 10,
      "| compactions:", istats.compactions)
ing.append("trips", {"city": np.asarray(["ams"], dtype=object),
                     "fare": np.array([9.9])})
t = ing.catalog.table("trips")
print("delta tail after append:", t.delta_rows, "rows",
      "| epoch:", t.delta_epoch)
# EXPLAIN shows the merge-on-read scan: ...Scan trips (delta: k rows)
print(ing.scan("trips").agg(n=("count", None)).explain(physical=True))
ing.shutdown()

# --- budgeted result materialization ----------------------------------------
# Final tables whose columns would exceed memory_budget stream to
# memmapped columns instead of a second RAM materialization (string heaps
# stay shared in RAM); the backing files are unlinked immediately, so
# nothing leaks.  ExecStats/BufferStats count them as result_spills.
big = (small.scan("trips")
       .project(city=Col("city"), paid=Col("fare") * 1.1)
       .execute())
print("result_spills:", small.last_stats.result_spills,
      "| columns memmapped:", isinstance(big.columns["paid"].data,
                                         np.memmap))

# --- concurrent use ----------------------------------------------------------
# The database is an embedded engine inside YOUR process, and your process
# is probably multi-threaded.  One Database is safe to share across
# threads; the serving layer keeps concurrent queries honest:
#
#   * admission gate — each query's summed per-operator budget
#     reservations (from the physical plan) are reserved atomically
#     against memory_budget/device_budget BEFORE execution; queries that
#     don't fit queue with a bounded wait (AdmissionTimeout after
#     ~30 s) instead of discovering pressure mid-flight.
#   * atomic pins — BufferManager.try_pin reserves-or-fails under the
#     lock, so N threads can never jointly exceed the budget
#     (`peak <= budget` holds for the whole run, not per query).
#   * plan cache — repeated queries skip the optimize→normalize→annotate
#     lowering pass entirely; entries are invalidated by append / DROP /
#     DELETE, and table versions inside the cache key make stale hits
#     impossible either way.  Observed group cardinalities feed back
#     into the next lowering's tier estimates.
#   * shared scans — concurrent cold queries over the same table attach
#     to ONE in-flight host→device upload per block (single-flight), so
#     a repeat-heavy mix does one upload, not one per client.
#
# Concurrency guarantees an embedder can rely on — these are not just
# conventions: each one is encoded as a checked rule in
# src/repro/analysis/ (`python -m repro.analysis.lint src/` in CI) and
# the lock ORDER between managers is verified at runtime by the
# lock-order witness (REPRO_WITNESS=1 turns it on under pytest):
#
#   1. budget accounting is atomic — every read-modify-write of host or
#      device budget state happens under its manager's lock; admission
#      (gate) and reservation (try_pin / put) are single lock-held
#      decisions, never check-then-act races.
#   2. acquisitions pair with releases on ALL paths — pinned bytes,
#      spill files, admission tickets and the storage directory flock
#      are released on exceptions too (finally/except or context
#      manager), so a failing query leaks nothing: a crashed startup()
#      leaves the directory lockable, a builder that raises mid-upload
#      leaves no pinned device block behind.
#   3. device dispatch is serialized — jitted collective steps are
#      built and launched only under the module dispatch lock, so
#      concurrent queries cannot interleave multi-device collectives
#      (the classic SPMD deadlock).
#   4. stats are safe to read while queries run — shared counters
#      mutate only via locked helpers (BufferManager.bump /
#      DeviceBufferManager.bump); db.last_stats is thread-local.
#   5. lock acquisition order is acyclic — the witness records the
#      cross-thread acquisition graph over the concurrent suite and
#      fails CI on any ordering cycle or on a Condition.wait entered
#      while another engine lock is held.
#
# Per-query stats under concurrency: db.last_stats is a THREAD-LOCAL
# view — each thread sees the stats of the last query it ran, never a
# neighbour's.  Connection.query returns them on the result itself
# (Result.stats), which is the concurrency-proof API.
import threading

def worker(out, slot):
    r = (db.scan("trips").filter(Col("distance_km") > 5 + slot)
         .group_by("city").agg(rev=("sum", "fare")).execute())
    out[slot] = (r.to_pydict(), db.last_stats)

outs = [None, None]
ts = [threading.Thread(target=worker, args=(outs, s)) for s in (0, 1)]
for t in ts:
    t.start()
for t in ts:
    t.join()
print("concurrent stats are per-thread:", outs[0][1] is not outs[1][1])
# a repeated query skips lowering entirely — ExecStats says so per query:
(db.scan("trips").filter(Col("distance_km") > 5)
 .group_by("city").agg(rev=("sum", "fare")).execute())
print("repeat was a plan-cache hit:", db.last_stats.plan_cache_hit,
      "| cache hits so far:", db.buffer_manager.stats.plan_cache_hits)
print("OK")
