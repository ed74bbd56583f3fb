"""Embedding interface (paper §3.2): startup / connect / query / append.

The API mirrors MonetDBLite's C API one-to-one:

    db  = startup(path_or_None)        # monetdb_startup
    con = db.connect()                 # monetdb_connect  (dummy client ctx)
    res = con.query("SELECT ...")      # monetdb_query -> Result
    col = res.fetch(0)                 # monetdb_result_fetch (low/high level)
    con.append("tbl", {...})           # monetdb_append (bulk, no INSERT parse)
    db.shutdown()                      # in-process shutdown, state released

Deliberate fixes of the paper's own known limitations (§5.1), enabled by
explicit state instead of C globals: multiple databases per process, and
multiple in-process handles per database directory.
"""

from __future__ import annotations

import itertools
import os
import threading
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .executor import ExecStats, Executor
from .indexes import IndexManager
from .relalg import PlanNode, Query, ScanNode
from .storage import Storage
from .table import Table
from .tracing import span, take
from .transactions import Transaction, TransactionManager
from .types import DBType

_open_dirs: dict[str, "Database"] = {}
_open_lock = threading.Lock()

# device-cache key namespaces for transaction snapshots (0 = committed
# catalog; see Connection.query)
_snapshot_ns = itertools.count(1)

# the ``query`` metadata of each query's mdb.query span (ExecStats.query_id)
_query_ids = itertools.count(1)


class DatabaseError(RuntimeError):
    pass


@dataclass
class Catalog:
    tables: dict[str, Table] = field(default_factory=dict)

    def table(self, name: str) -> Table:
        if name not in self.tables:
            raise DatabaseError(f"no such table: {name!r}")
        return self.tables[name]

    def __contains__(self, name):
        return name in self.tables


class Database:
    """One embedded database instance (explicit state — no process globals).

    ``memory_budget`` (bytes) bounds the tracked working state of blocking
    query operators; queries whose intermediates exceed it spill to
    partitioned run files (out-of-core execution — the standard-RDBMS
    feature the paper contrasts against in-memory analytics tools).
    ``device_budget`` (bytes) is the same contract one tier up: it bounds
    device-resident (HBM) column blocks for distributed execution —
    over-budget inputs stream morsel batches through the device cache
    (``core.device_cache``) instead of requiring residency.  The default
    ``None`` means unlimited: zero configuration, no spilling/eviction."""

    def __init__(self, path: Optional[str] = None,
                 memory_budget: Optional[int] = None,
                 spill_codec: str = "for", spill_prefetch: bool = True,
                 device_budget: Optional[int] = None,
                 device_batch_rows: Optional[int] = None,
                 data_skipping: bool = True,
                 delta_compact_fraction: float = 0.5):
        from .buffers import BufferManager
        from .device_cache import DeviceBufferManager
        self.path = path
        self.memory_budget = memory_budget
        self.spill_codec = spill_codec
        self.spill_prefetch = spill_prefetch
        self.device_budget = device_budget
        self.device_batch_rows = device_batch_rows
        # delta-store compaction threshold: fold a table's delta tail into a
        # new base once it exceeds this fraction of memory_budget bytes (or,
        # unbudgeted, this fraction of the base rows).  0/None disables
        # automatic compaction.
        self.delta_compact_fraction = delta_compact_fraction
        # imprint-driven data skipping (paper §3.1): when True the planner
        # attaches zone-map skip-sets to scans and every tier prunes
        # non-qualifying blocks; False forces full scans (the differential
        # harness's control arm).  Results are bit-identical either way.
        self.data_skipping = data_skipping
        self.catalog = Catalog()
        self.txn_manager = TransactionManager()
        self.index_manager = IndexManager(self)
        self.storage: Optional[Storage] = None
        self._shutdown = False
        # per-thread last_stats view: one mutable attribute would be
        # clobbered by concurrent queries (thread A reads thread B's stats)
        self._stats_local = threading.local()
        if path is not None:
            self.storage = Storage(path)
            try:
                self.storage.acquire_lock()    # on-disk, cross-process
            except RuntimeError as e:
                raise DatabaseError(str(e)) from None
        try:
            if self.storage is not None:
                if self.storage.has_catalog():
                    self.catalog.tables = self.storage.load()
                # crash recovery: a previous process that died mid-query
                # may have left run files behind; the lock just acquired
                # proves no live owner exists, so the spill dir is stale.
                self.storage.reclaim_spill()
            # spill files live under the database directory in persistent
            # mode (paper §3.2: everything the instance owns is under one
            # dir), else a private temp dir; created lazily on first spill.
            self.buffer_manager = BufferManager(
                memory_budget,
                spill_dir=self.storage.spill_path()
                if self.storage is not None else None,
                codec=spill_codec, prefetch=spill_prefetch)
            # HBM tier: device blocks share the host tier's stats object so
            # one BufferStats reports both tiers (jax loads lazily on use)
            self.device_manager = DeviceBufferManager(
                device_budget, stats=self.buffer_manager.stats)
            # serving layer: plan cache + admission gate (core.serving).
            # The gate reserves each plan's summed per-operator budget
            # estimates before execution; the cache skips lowering on hot
            # repeated queries and is invalidated by append/DROP/DELETE.
            from .serving import AdmissionGate, PlanCache
            self.plan_cache = PlanCache()
            self.admission_gate = AdmissionGate(memory_budget,
                                                device_budget)
        except BaseException:
            # a failed open must not leave the directory locked forever
            if self.storage is not None:
                self.storage.release_lock()
            raise

    # ---- embedding API ------------------------------------------------------
    def connect(self) -> "Connection":
        self._check_alive()
        return Connection(self)

    def shutdown(self) -> None:
        """In-process shutdown: persist, then free all state (the paper's
        'garbage collection' challenge — everything must be reclaimable
        without process exit)."""
        if self._shutdown:
            return
        if self.storage is not None:
            self.storage.write_catalog(self.catalog.tables)
        self.catalog.tables.clear()
        self.index_manager.imprints.clear()
        self.index_manager.order_indexes.clear()
        self.buffer_manager.cleanup()
        self.device_manager.cleanup()
        self.plan_cache.clear()
        if self.storage is not None:
            self.storage.release_lock()
        self._shutdown = True
        if self.path is not None:
            with _open_lock:
                _open_dirs.pop(os.path.abspath(self.path), None)

    # ``with startup(path) as db:`` — shutdown (persist + lock release) is
    # guaranteed on scope exit, including on exceptions
    def __enter__(self) -> "Database":
        self._check_alive()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.shutdown()
        return False

    def checkpoint(self) -> None:
        """Fold the WAL into fresh column files (durability compaction)."""
        self._check_alive()
        if self.storage is not None:
            self.storage.write_catalog(self.catalog.tables)

    # ---- data definition ----------------------------------------------------
    def create_table(self, name: str, data, types=None, scales=None) -> Table:
        self._check_alive()
        t = data if isinstance(data, Table) else Table.from_dict(
            name, data, types, scales)
        if isinstance(data, Table) and data.name != name:
            t = data.rename(name)
        txn = self.txn_manager.begin(self)
        txn.create_table(t)
        txn.commit()
        return t

    def drop_table(self, name: str) -> None:
        self._check_alive()
        txn = self.txn_manager.begin(self)
        txn.drop_table(name)
        txn.commit()
        # a future table reusing this name is a different table: forget
        # the admission hit history along with the blocks
        self.device_manager.invalidate_table(name, drop_history=True)
        self.plan_cache.invalidate_table(name)

    def append(self, name: str, data, types=None, scales=None) -> None:
        """Bulk append (monetdb_append): no per-row INSERT parsing."""
        self._check_alive()
        base = self.catalog.table(name)
        chunk = data if isinstance(data, Table) else Table.from_dict(
            name, data,
            types or {c.name: c.dbtype for c in base.schema.columns},
            scales or {c.name: c.scale for c in base.schema.columns})
        txn = self.txn_manager.begin(self)
        txn.append(name, chunk)
        txn.commit()
        self._post_append(name)

    def _post_append(self, name: str) -> None:
        """Epoch-keyed cache invalidation after a committed append.

        A delta append leaves the base blocks byte-identical, so only the
        delta-tail device blocks (keyed on the old epoch) die — repeat scans
        re-upload the tail's bytes, not the table.  A rebase (VARCHAR heap
        re-sort) or a compaction changed the physical layout, so everything
        for the table is retired; version-carrying keys already keep either
        path correct — invalidation only frees dead blocks from the budget.
        The plan cache's keys carry (version, base_version, delta_epoch), so
        stale entries are unreachable and age out of the LRU on their own."""
        new = self.catalog.tables.get(name)
        if new is not None and new.delta_rows:
            self.device_manager.invalidate_delta(name)
        else:
            self.device_manager.invalidate_table(name)
            self.plan_cache.invalidate_table(name)

    def _maybe_compact(self, name: str) -> None:
        """Transaction-manager hook, called under the commit lock after an
        append install: fold an over-threshold delta tail into a plain base.
        The fold is content- and version-identical, so no validation window
        opens; with persistent storage the checkpoint folds the WAL and the
        existing GC sweeps the superseded column-version files."""
        from .delta import compact, should_compact
        t = self.catalog.tables.get(name)
        if not should_compact(t, self.delta_compact_fraction,
                              self.memory_budget):
            return
        new = compact(t, storage=self.storage, bufman=self.buffer_manager)
        self.catalog.tables[name] = new
        self.buffer_manager.bump(compactions=1)
        # same version, different physical layout: retire old base/tail
        # device blocks and cached plans for the table
        self.device_manager.invalidate_table(name)
        self.plan_cache.invalidate_table(name)
        if self.storage is not None:
            self.storage.write_catalog(self.catalog.tables)

    def ingest(self, name: str, source, types=None, scales=None) -> int:
        """Chunked bulk ingest: stream ``source`` — an iterable of
        ``{col: values}`` dicts or ``Table`` chunks — into ``name`` as delta
        appends.

        Each incoming chunk is re-chunked into budget-sized pieces
        (``choose_morsel_rows``) and pinned through ``BufferManager``
        accounting while its commit is in flight, so a table far larger
        than ``memory_budget`` loads with tracked ``peak <= budget``;
        threshold compaction (``delta_compact_fraction``) periodically folds
        the growing tail to disk in persistent mode.  The table is created
        from the first chunk's schema when absent.  Returns rows ingested."""
        from .buffers import choose_morsel_rows
        self._check_alive()
        total = 0
        for data in source:
            if name in self.catalog:
                base = self.catalog.table(name)
                chunk = data if isinstance(data, Table) else Table.from_dict(
                    name, data,
                    types or {c.name: c.dbtype for c in base.schema.columns},
                    scales or {c.name: c.scale for c in base.schema.columns})
            else:
                chunk = data if isinstance(data, Table) else Table.from_dict(
                    name, data, types, scales)
                # seed a zero-row base carrying the first chunk's schema and
                # heaps: subsequent pieces whose strings are covered by those
                # heaps append as O(delta) deltas instead of rebasing
                self.create_table(name, chunk.slice_rows(0, 0))
            row_bytes = max(1, sum(c.data.dtype.itemsize
                                   for c in chunk.columns.values()))
            rows = choose_morsel_rows(row_bytes, self.memory_budget)
            n = chunk.num_rows
            for s in range(0, n, rows):
                piece = chunk.slice_rows(s, min(s + rows, n))
                with self.buffer_manager.pinned(piece.nbytes):
                    txn = self.txn_manager.begin(self)
                    txn.append(name, piece)
                    txn.commit()
                self._post_append(name)
                total += piece.num_rows
        return total

    # ---- querying -------------------------------------------------------------
    def scan(self, name: str) -> Query:
        self._check_alive()
        self.catalog.table(name)
        return Query(ScanNode(name), self)

    def sql(self, text: str) -> Query:
        from .sqlparser import parse_sql
        self._check_alive()
        parsed = ExecStats()        # the parse's span, until a query runs
        with span("parse", parsed):
            plan = parse_sql(text, self.catalog)
        return Query(plan, self, spans=parsed)

    def delete(self, name: str, predicate) -> int:
        """DELETE FROM name WHERE predicate.  Tables are immutable values,
        so deletion installs a new filtered version through the normal
        begin/commit path (``txn.replace`` — first-committer-wins against
        concurrent appenders, validated under the commit lock like any
        write); per the paper's index lifecycle (§3.1), imprints/hash/order
        indexes on the table are destroyed (replace -> invalidate, unlike
        append's prefix-preserving merge path)."""
        import numpy as np
        from .expression import EvalContext
        self._check_alive()
        self.catalog.table(name)            # DatabaseError when unknown
        txn = self.txn_manager.begin(self)
        try:
            t = txn.snapshot[name]
            arrays = {c: np.asarray(col.data)
                      for c, col in t.columns.items()}
            meta = {c: (col.dbtype, col.heap, col.scale)
                    for c, col in t.columns.items()}
            r = predicate.eval(EvalContext(arrays, meta, xp=np))
            kill = np.asarray(r.values) != 0
            if r.null is not None:
                kill &= ~np.asarray(r.null)
            keep = np.nonzero(~kill)[0]
            new = Table(t.schema,
                        {c: col.take(keep) for c, col in t.columns.items()},
                        version=t.version + 1)
            txn.replace(name, new)
            txn.commit()
        except BaseException:
            # a failed delete (conflict, bad predicate) must not leak an
            # open transaction
            if txn.state == "open":
                txn.rollback()
            raise
        self.device_manager.invalidate_table(name)
        self.plan_cache.invalidate_table(name)
        if self.storage is not None:
            self.storage.write_catalog(self.catalog.tables)
        return int(kill.sum())

    def create_order_index(self, table: str, column: str):
        """CREATE ORDER INDEX (paper §3.1): explicit sorted index used for
        point/range lookups (binary search) and merge joins."""
        self._check_alive()
        self.catalog.table(table)
        return self.index_manager.create_order_index(table, column)

    # ``last_stats`` is a thread-local view: each thread sees the stats of
    # the last query IT ran — one shared mutable attribute would be
    # clobbered under concurrency (thread A reading thread B's spill
    # counts).  Per-result stats travel on ``Result.stats`` as well, which
    # is the concurrency-proof API.
    @property
    def last_stats(self):
        return getattr(self._stats_local, "stats", None)

    @last_stats.setter
    def last_stats(self, value) -> None:
        self._stats_local.stats = value

    def execute_plan(self, plan: PlanNode, do_optimize: bool = True,
                     distributed: bool = False, mesh=None,
                     spans: Optional[ExecStats] = None) -> Table:
        """Run ``plan``; ``spans`` holds span totals recorded for it
        before it ran (the SQL parse), moved into this query's stats."""
        self._check_alive()
        if distributed:
            from .parallel import ParallelExecutor
            ex = ParallelExecutor(self, mesh=mesh)
        else:
            ex = Executor(self)
        self.last_stats = st = ex.stats
        if spans is not None:
            take(st, spans)
        st.query_id = next(_query_ids)
        # query scope: cleanup() defers spill-file deletion while we run
        with self.buffer_manager.query_scope(), \
                span("query", st, query=st.query_id):
            return ex.execute(plan, do_optimize=do_optimize)

    # ---- hooks (storage + indexes) -------------------------------------------
    def _commit(self, txn: Transaction) -> None:
        self.txn_manager.commit(self, txn)

    def _on_table_created(self, table: Table) -> None:
        if self.storage is not None:
            self.storage.write_catalog(self.catalog.tables)

    def _on_append(self, table: Table, chunk: Table) -> None:
        if self.storage is not None:
            self.storage.log_append(table, chunk)

    def _on_replace(self, name: str) -> None:
        # a replace rewrites rows wholesale: indexes over the old contents
        # are dead (unlike append's prefix-preserving merge path)
        self.index_manager.invalidate_table(name)

    def _check_alive(self):
        if self._shutdown:
            raise DatabaseError("database has been shut down")

    # ---- introspection -----------------------------------------------------
    def table_names(self) -> list[str]:
        return sorted(self.catalog.tables)

    def table(self, name: str) -> Table:
        return self.catalog.table(name)


def startup(path: Optional[str] = None,
            memory_budget: Optional[int] = None,
            spill_codec: str = "for",
            spill_prefetch: bool = True,
            device_budget: Optional[int] = None,
            device_batch_rows: Optional[int] = None,
            data_skipping: bool = True,
            delta_compact_fraction: float = 0.5) -> Database:
    """monetdb_startup: persistent when ``path`` given, else in-memory.

    ``memory_budget`` (bytes, default unlimited) enables out-of-core
    execution: blocking operators spill partitioned run files to disk when
    their working state would exceed the budget, and over-budget final
    result tables stream to memmapped columns instead of a second RAM
    materialization (``result_spills`` in ``BufferStats``/``ExecStats``).
    Tier routing — spill vs in-memory vs the device tiers — is decided by
    the unified physical planner (``core.physplan``); inspect it with
    ``Query.explain(physical=True)`` or ``db.last_stats.plan_repr``.

    ``spill_codec`` selects the run-file encoding: ``"for"`` (default,
    frame-of-reference + byte-shuffle on integer streams — several-fold
    smaller spills on sorted/clustered keys) or ``"raw"``.
    ``spill_prefetch`` toggles double-buffered background loading of spill
    partitions (default on); prefetched bytes stay pinned inside the
    budget.  Both are no-ops until a query actually spills.

    ``device_budget`` (bytes, default unlimited) is the HBM analogue for
    distributed execution: all device-resident column blocks live under
    this budget in an LRU cache keyed on (table, column, version, shard).
    Inputs that fit stay resident (repeat scans skip the host→device
    transfer entirely); larger inputs stream morsel batches through the
    cache with double-buffered async prefetch and partial-aggregate carry
    — results are bit-identical across budgets.  ``device_batch_rows``
    fixes the streaming batch size (default 65536; the batch decomposition
    — not the budget — determines floating-point summation order).

    ``data_skipping`` (default True) wires the paper's §3.1 column imprints
    into every tier: the physical planner derives a per-scan skip-set (a
    block-qualification bitmap from per-2048-row zone maps) for simple
    range filters, and the device tier never uploads, the spill tier never
    spills, and the host/volcano paths never materialize a block the zone
    maps prove non-qualifying.  Observability: ``blocks_skipped`` /
    ``bytes_skipped_h2d`` / ``bytes_skipped_spill`` in ``BufferStats`` and
    ``ExecStats``, plus a ``(skip: k/N blocks)`` annotation in
    ``Query.explain(physical=True)``.  Skipping is sound by construction
    (bitmaps are supersets of qualifying blocks, re-validated against table
    versions at execution), so results are bit-identical with it off.

    VARCHAR keys spill too, even when the join sides were dictionary-encoded
    against different heaps: small dictionaries merge into one shared heap
    (codes recoded while spooling), oversized ones partition on decoded
    string bytes.  ``BufferStats.varchar_spills`` /
    ``ExecStats.varchar_spills`` count blocking ops that spilled with
    VARCHAR keys.

    Unlike the original (paper §5.1), several databases may be open in one
    process; a directory is single-owner ("database locked") to preserve the
    paper's on-disk locking contract."""
    if path is None:
        return Database(None, memory_budget=memory_budget,
                        spill_codec=spill_codec,
                        spill_prefetch=spill_prefetch,
                        device_budget=device_budget,
                        device_batch_rows=device_batch_rows,
                        data_skipping=data_skipping,
                        delta_compact_fraction=delta_compact_fraction)
    ap = os.path.realpath(path)      # symlink aliases are the same database
    with _open_lock:
        if ap in _open_dirs and not _open_dirs[ap]._shutdown:
            raise DatabaseError(f"database locked: {ap}")
        db = Database(ap, memory_budget=memory_budget,
                      spill_codec=spill_codec,
                      spill_prefetch=spill_prefetch,
                      device_budget=device_budget,
                      device_batch_rows=device_batch_rows,
                      data_skipping=data_skipping,
                      delta_compact_fraction=delta_compact_fraction)
        _open_dirs[ap] = db
    return db


@dataclass
class ResultColumnMeta:
    """High-level column header (paper Listing 2)."""
    name: str
    dbtype: DBType
    null_value: object
    scale: float
    count: int


class Result:
    """monetdb_result: semi-opaque header + per-column fetch.

    ``stats`` carries the query's own ``ExecStats`` — under concurrency
    this is THE reliable way to read per-query counters (``db.last_stats``
    is a per-thread convenience view and sees only the calling thread's
    last query)."""

    def __init__(self, table: Table, stats=None):
        self._table = table
        self.nrows = table.num_rows
        self.ncols = table.num_cols
        self.names = list(table.schema.names)
        self.stats = stats

    def fetch_raw(self, i: int) -> np.ndarray:
        """Low-level fetch: the engine's own packed array, zero-copy
        (requires knowledge of sentinel encoding — for wrappers)."""
        col = self._table.columns[self.names[i]]
        from .exchange import zero_copy_view
        return zero_copy_view(col)

    def fetch(self, i: int):
        """High-level fetch: decoded numpy + header struct."""
        from .types import NULL_SENTINEL
        name = self.names[i]
        col = self._table.columns[name]
        meta = ResultColumnMeta(name, col.dbtype,
                                NULL_SENTINEL[col.dbtype],
                                10.0 ** -col.scale if col.scale else 1.0,
                                len(col))
        return col.to_numpy(), meta

    def to_pydict(self):
        return self._table.to_pydict()


class Connection:
    """Dummy client context (paper §3.2): holds a query/transaction scope;
    many connections per database give inter-query parallelism + isolation."""

    def __init__(self, database: Database):
        self.database = database
        self._txn: Optional[Transaction] = None

    # -- transactions -----------------------------------------------------------
    def begin(self) -> None:
        if self._txn is not None:
            raise DatabaseError("transaction already open")
        self._txn = self.database.txn_manager.begin(self.database)

    def commit(self) -> None:
        if self._txn is None:
            raise DatabaseError("no open transaction")
        self._txn.commit()
        self._txn = None

    def rollback(self) -> None:
        if self._txn is None:
            raise DatabaseError("no open transaction")
        self._txn.rollback()
        self._txn = None

    # -- queries -----------------------------------------------------------------
    def query(self, sql: str, **kw) -> Result:
        from .sqlparser import parse_statement
        db = self.database
        kind, t, c = parse_statement(sql)
        if kind == "create_order_index":
            db.create_order_index(t, c)
            from .table import Table
            from .types import TableSchema
            return Result(Table(TableSchema("result", ()), {}))
        if self._txn is not None:
            # run against the snapshot: materialize a view database
            snap_db = Database(None, memory_budget=db.memory_budget,
                               spill_codec=db.spill_codec,
                               spill_prefetch=db.spill_prefetch,
                               device_budget=db.device_budget,
                               device_batch_rows=db.device_batch_rows,
                               data_skipping=db.data_skipping,
                               delta_compact_fraction=db.delta_compact_fraction)
            # a FRESH IndexManager over the snapshot catalog: skip-sets and
            # imprints derive from the snapshot's own (uncommitted) tables,
            # never from the committed table sharing the version number
            snap_db.catalog.tables = self._txn.tables()
            snap_db.index_manager = IndexManager(snap_db)
            snap_db.buffer_manager = db.buffer_manager   # shared accounting
            # ONE admission accounting too: snapshot queries reserve
            # against the same gate as committed-catalog queries (the
            # budgets are shared, so the reservations must be).  The plan
            # cache stays the snapshot's own throwaway instance — snapshot
            # tables reuse the version number the next committed write
            # gets, so parent-cache entries could alias them
            snap_db.admission_gate = db.admission_gate
            # the parent's device manager is shared too — ONE budget
            # accounting, so physical device residency stays under
            # device_budget even while a snapshot query runs — but under a
            # unique key namespace: a snapshot table reuses the version
            # number the next committed write will get, and namespaced
            # keys keep rolled-back rows from ever being served to later
            # queries as cache hits.  The namespace is invalidated when
            # the query ends (its blocks are uncommitted by definition).
            snap_db.device_manager = db.device_manager
            ns = next(_snapshot_ns)
            snap_db.device_key_namespace = ns
            try:
                table = snap_db.sql(sql).execute(**kw)
            finally:
                db.device_manager.invalidate_namespace(ns)
            # thread per-query stats (spilled_ops, varchar_spills, spill
            # byte deltas) to the parent database: the snapshot view is
            # discarded, but db.last_stats must reflect the last query run
            # through this connection regardless of transaction scope.
            # Both sides are thread-local properties now, so the copy-back
            # moves this thread's snapshot stats into this thread's parent
            # view — concurrent queries on other threads are untouched
            db.last_stats = snap_db.last_stats
        else:
            table = db.sql(sql).execute(**kw)
        return Result(table, stats=db.last_stats)

    def append(self, name: str, data, **kw) -> None:
        if self._txn is not None:
            base = self._txn.table(name)
            chunk = Table.from_dict(
                name, data,
                {c.name: c.dbtype for c in base.schema.columns},
                {c.name: c.scale for c in base.schema.columns})
            self._txn.append(name, chunk)
        else:
            self.database.append(name, data, **kw)
