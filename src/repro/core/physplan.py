"""Unified physical planner: one logical→physical lowering pass.

Every query — builder API, SQL, transaction-scoped — flows through
``plan_physical`` before execution.  The pass has three jobs, matching the
paper's one-planner-many-frontends architecture (§3: the same optimizer and
execution machinery serve every entry point, choosing strategies from data
statistics rather than per-API code paths):

1. **Normalization** — SQL and builder plans converge to identical shapes:
   trivial (identity) projections are elided, pure-rename projections over
   an aggregate are pushed into the aggregate's output names, and filter
   conjuncts are merged + canonically ordered.  This is what fixes "SQL
   plans never match the device tier": ``parse_sql`` wraps aggregates in a
   rename ProjectNode that used to hide the Aggregate(Filter*(Scan)) shape
   from ``match_scan_agg``.

2. **Tier annotation** — each operator gets a tier decision
   (``device-resident`` / ``device-streamed`` / ``parallel-host`` /
   ``spill`` / ``in-memory``) and a budget reservation.  The byte models
   and routing thresholds that used to be smeared across ``executor.py``,
   ``parallel.py``, ``volcano.py`` and ``optimizer.py`` live here, in ONE
   costed policy (``TierPolicy``).  Plan-time annotations are predictions
   from level-1 statistics (``optimizer.estimate_rows``); at runtime the
   executors refine the blocking-operator decisions with actual
   cardinalities — through the *same* policy object, so there is exactly
   one definition of every threshold.  Device admission is biased by the
   ``DeviceBufferManager``'s cache-hit history: repeated queries on a
   borderline table flip from streamed to resident.

3. **Observability** — ``PhysicalPlan.render()`` is the EXPLAIN text
   surfaced through ``Query.explain(physical=True)`` and
   ``ExecStats.plan_repr``, so tier choices are golden-testable.

The executors are *consumers* of this plan: ``executor.py`` asks the policy
per blocking instruction, ``parallel.py`` reads the scan-agg core + device
tier + suffix, ``volcano.py`` asks for its row-spool estimate.  Adding the
next tier (device joins/sorts) means a new annotation here — not a fifth
ad-hoc routing fork.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from .expression import BinOp, Col, DateLit, Expr, Lit
from .optimizer import estimate_bytes, estimate_rows, optimize, \
    split_conjuncts
from .relalg import (AggregateNode, AggSpec, FilterNode, JoinNode, LimitNode,
                     OrderByNode, PlanNode, ProjectNode, ScanNode, node_line)
from .types import DBType, NULL_SENTINEL

# ---------------------------------------------------------------------------
# tier names (the vocabulary of the physical plan)
# ---------------------------------------------------------------------------

TIER_DEVICE_RESIDENT = "device-resident"
TIER_DEVICE_STREAMED = "device-streamed"
TIER_DEVICE_JOIN = "device-join"
TIER_DEVICE_SORT = "device-sort"
TIER_PARALLEL_HOST = "parallel-host"
TIER_SPILL = "spill"
TIER_IN_MEMORY = "in-memory"

# tiers whose reservations count against the DEVICE budget at admission
DEVICE_TIERS = (TIER_DEVICE_RESIDENT, TIER_DEVICE_STREAMED,
                TIER_DEVICE_JOIN, TIER_DEVICE_SORT)

# pattern limits for the device scan-agg tier (previously in parallel.py)
MAX_DENSE_GROUPS = 4096
MIN_ROWS_TO_SHARD = 4096      # paper: don't split small columns
DEVICE_BATCH_ROWS = 1 << 16   # morsel batch streamed through the device
                              # cache; fixed per database (not per budget)
                              # so results are budget-invariant
SUPPORTED_DEVICE_AGGS = {"count", "sum", "avg", "min", "max"}

# device join tier: the build matrix has one row per distinct build key
# (dense or ranked domain, see ``DeviceBuild``) and may exceed the scan-agg
# group cap because the merged partial matrix never materializes on host —
# device-resident assembly compacts it in HBM first.  Build keys must be
# unique (verified at runtime; duplicates fall back to the host join).
MAX_DEVICE_JOIN_DOMAIN = 1 << 21
# build-payload columns are scatter-added as float64 and must decode
# exactly; integer-coded types only (|v| < 2^53 for the int64 widths the
# engine stores — the sentinel -2^63 is a power of two and round-trips)
DEVICE_JOIN_PAYLOAD_TYPES = (DBType.INT32, DBType.INT64, DBType.DATE,
                             DBType.BOOL, DBType.VARCHAR)
DEVICE_JOIN_KEY_TYPES = (DBType.INT32, DBType.INT64, DBType.DATE)

# smarter admission (ROADMAP): a table that fits the device budget but
# would monopolize more than this fraction of the cache is only admitted
# *resident* once its cache-hit history proves repeat access; until then it
# streams (whose blocks still populate the cache, accruing that history).
DEVICE_BORDERLINE_FRACTION = 0.5
DEVICE_PROMOTE_HITS = 1

# table name of the materialized scan-agg core inside a suffix plan ("#"
# prefix: never collides with SQL identifiers, same convention as the
# device cache's pseudo-columns)
AGG_RESULT_NAME = "#agg"


# ---------------------------------------------------------------------------
# scan-agg pattern (THE device-tier shape) — single definition
# ---------------------------------------------------------------------------


@dataclass
class ScanAggSpec:
    table: str
    conjuncts: list[Expr]
    group_keys: list[str]
    key_domains: list[tuple[float, int]]     # (offset, cardinality) per key
    aggs: list[AggSpec]
    n_groups: int
    columns: list[str]                       # all referenced base columns


def match_scan_agg(plan: PlanNode, catalog) -> Optional[ScanAggSpec]:
    """Aggregate( Filter* ( Scan ) ) with dense-domain group keys."""
    if not isinstance(plan, AggregateNode):
        return None
    if any(a.fn not in SUPPORTED_DEVICE_AGGS for a in plan.aggs):
        return None
    node = plan.child
    conjuncts: list[Expr] = []
    while isinstance(node, FilterNode):
        conjuncts = split_conjuncts(node.predicate) + conjuncts
        node = node.child
    if not isinstance(node, ScanNode):
        return None
    table = catalog.table(node.table)
    # dense domains for the keys
    domains = []
    n_groups = 1
    for k in plan.group_by:
        col = table.column(k)
        if col.dbtype == DBType.VARCHAR:
            offset, card = 0.0, len(col.heap)
        elif col.dbtype == DBType.BOOL:
            offset, card = 0.0, 2
        elif col.dbtype in (DBType.INT32, DBType.INT64, DBType.DATE):
            v = np.asarray(col.data)
            nn = v[v != NULL_SENTINEL[col.dbtype]]
            if nn.size == 0:
                return None
            mn, mx = int(nn.min()), int(nn.max())
            offset, card = float(mn), mx - mn + 1
        else:
            return None
        if card > MAX_DENSE_GROUPS:
            return None
        domains.append((offset, card))
        n_groups *= card
    if n_groups > MAX_DENSE_GROUPS:
        return None
    cols: set[str] = set(plan.group_by)
    for c in conjuncts:
        cols |= c.columns()
    for a in plan.aggs:
        if a.expr is not None:
            cols |= a.expr.columns()
    if not cols:
        cols = {table.schema.names[0]}
    return ScanAggSpec(node.table, conjuncts, list(plan.group_by),
                       domains, list(plan.aggs), n_groups, sorted(cols))


SUFFIX_NODES = (OrderByNode, LimitNode, ProjectNode, FilterNode)


def find_scan_agg_core(plan: PlanNode, catalog
                       ) -> tuple[Optional[AggregateNode],
                                  Optional[PlanNode]]:
    """Locate the scan-agg core under a chain of order/limit/project/filter
    suffix operators.  Returns ``(core, suffix)`` where ``core`` is the
    topmost AggregateNode reachable from the root through suffix nodes (or
    None), and ``suffix`` re-applies those nodes over a scan of the core's
    materialized result (``AGG_RESULT_NAME``), or None when the core IS the
    root.  The suffix runs on the host over the (tiny) assembled aggregate,
    which is what lets ORDER BY / LIMIT / HAVING queries keep their
    scan-agg core on the device tier."""
    path = []
    node = plan
    while isinstance(node, SUFFIX_NODES):
        path.append(node)
        node = node.children[0]
    if not isinstance(node, AggregateNode):
        return None, None
    if not path:
        return node, None
    suffix: PlanNode = ScanNode(AGG_RESULT_NAME,
                                tuple(node.output_columns(catalog)))
    for n in reversed(path):
        suffix = n.with_children((suffix,))
    return node, suffix


# ---------------------------------------------------------------------------
# join-agg pattern (the device JOIN tier's shape)
# ---------------------------------------------------------------------------


def build_keys(table, column: str) -> np.ndarray:
    """Sorted distinct non-NULL values of an integer column, computed once
    per table version: they are cached on the table object, which every
    write replaces."""
    cache = table.__dict__.setdefault("_build_keys", {})
    keys = cache.get(column)
    if keys is None:
        col = table.column(column)
        v = np.asarray(col.data)
        keys = cache[column] = np.unique(v[v != NULL_SENTINEL[col.dbtype]])
    return keys


@dataclass(eq=False)
class RankedKeys:
    """A sparse build key's ranked domain: its sorted distinct non-NULL
    values, one build-matrix row each.  A key codes to its rank (its
    position here) only when it equals the value stored there; ranks keep
    key order."""
    table: str
    column: str
    version: int
    keys: np.ndarray

    @property
    def ident(self) -> tuple:
        """What a compiled step depends on.  The keys themselves reach the
        step as an argument, never as a constant."""
        return ("ranked", self.table, self.column, self.version,
                len(self.keys))


@dataclass
class DeviceBuild:
    """One build side of a device join: a filtered base scan whose unique
    key codes the row of a (card, 1 + n_payload) scatter-add matrix in
    HBM.  A key whose distinct values fill their span has a dense domain
    (a key's row is its distance from the smallest); a sparser key is
    ranked (``ranked``; ``domain`` is then ``(0.0, distinct count)``).
    Column 0 is the presence count (== 1 for a unique key, verified at
    runtime); the payload columns are the build's group-key contributions,
    recovered at assembly time by gathering the matrix at the surviving
    key codes."""
    table: str
    conjuncts: list                      # filters on this table's columns
    key: str                             # build-side join key column
    domain: tuple[float, int]            # (offset, cardinality)
    payload: list                        # build columns consumed at assembly
    probe_edges: list                    # [(earlier build idx, local col)]
    columns: list                        # all referenced columns
    ranked: Optional[RankedKeys] = None  # the ranked domain, if sparse

    @property
    def table_bytes(self) -> int:
        return self.domain[1] * (1 + len(self.payload)) * 8

    @property
    def state_bytes(self) -> int:
        """The build matrix plus, for a ranked domain, its keys in HBM."""
        ranks = 0 if self.ranked is None else self.ranked.keys.nbytes
        return self.table_bytes + ranks

    @property
    def domain_key(self) -> tuple:
        """The domain as a compiled step sees it."""
        return self.domain if self.ranked is None else self.ranked.ident


@dataclass
class JoinAggSpec:
    """Aggregate over an inner-equi-join tree rooted at one probe (fact)
    table, every other table a ``DeviceBuild``.  Execution: build matrices
    bottom-up (each build's stream probes its children's matrices), then
    stream probe batches — gather presence, mask, segment-sum partials by
    the probe-side key code.  Soundness of the single-key gid: every group
    key is either the probe↔group-build join key itself or a column of the
    group build, and a *unique* build key functionally determines those —
    one code, one group."""
    probe_table: str
    probe_conjuncts: list
    probe_edges: list                    # [(build idx, probe-side column)]
    builds: list                         # bottom-up build order
    group_build: Optional[int]           # index of B*, None for global aggs
    group_keys: list
    group_sources: list                  # per key: ("key",) | ("payload", j)
    aggs: list
    n_groups: int
    key_domain: tuple[float, int]        # domain of the group build's key
    columns: list                        # probe-side referenced columns

    # ScanAggSpec-compatible views for the shared partial-matrix layout /
    # fragment machinery: the probe phase IS a scan-agg over the probe
    # table grouped by the (single) join-key code.
    @property
    def table(self) -> str:
        return self.probe_table

    @property
    def conjuncts(self) -> list:
        return self.probe_conjuncts

    def probe_spec(self) -> "ScanAggSpec":
        keys = [self.probe_key] if self.group_build is not None else []
        doms = [self.key_domain] if self.group_build is not None else []
        return ScanAggSpec(self.probe_table, list(self.probe_conjuncts),
                           keys, doms, list(self.aggs), self.n_groups,
                           list(self.columns))

    @property
    def probe_key(self) -> Optional[str]:
        if self.group_build is None:
            return None
        for bidx, col in self.probe_edges:
            if bidx == self.group_build:
                return col
        return None

    def state_bytes(self) -> int:
        k = len(partial_layout(self.probe_spec()).kinds)
        return self.n_groups * k * 8 \
            + sum(b.state_bytes for b in self.builds)


def _flatten_join_tree(node: PlanNode):
    """Flatten Filter*/Join/Scan shapes into (tables, edges, loose) where
    ``tables`` maps each base table to its own-column conjuncts, ``edges``
    are single-key inner equi-join pairs and ``loose`` are conjuncts found
    above a join (attributed to a table by column ownership later).  None
    when any node breaks the shape (outer joins, multi-key joins,
    self-joins, non-scan leaves)."""
    tables: dict = {}
    edges: list = []
    loose: list = []

    def walk(n: PlanNode) -> bool:
        conjs: list = []
        while isinstance(n, FilterNode):
            conjs = split_conjuncts(n.predicate) + conjs
            n = n.child
        if isinstance(n, ScanNode):
            if n.table in tables:
                return False                      # self-join: host tier
            tables[n.table] = conjs
            return True
        if isinstance(n, JoinNode):
            if n.how != "inner" or len(n.left_keys) != 1:
                return False
            loose.extend(conjs)
            edges.append((n.left_keys[0], n.right_keys[0]))
            return walk(n.left) and walk(n.right)
        return False

    if not walk(node):
        return None
    return tables, edges, loose


def match_join_agg(plan: PlanNode, catalog) -> Optional[JoinAggSpec]:
    """Aggregate( Filter* ( Join tree of filtered base scans ) ) where the
    join graph is a tree rooted at the probe table (the one the aggregate
    expressions read), every build key is an integer with at most
    ``MAX_DEVICE_JOIN_DOMAIN`` distinct values, and all group keys are
    functionally dependent on ONE probe-adjacent build."""
    if not isinstance(plan, AggregateNode):
        return None
    if any(a.fn not in SUPPORTED_DEVICE_AGGS for a in plan.aggs):
        return None
    flat = _flatten_join_tree(plan.child)
    if flat is None or len(flat[0]) < 2:
        return None
    tables, edges, loose = flat

    # column ownership: every referenced column must belong to exactly one
    # of the joined tables (TPC-H-style prefixed names)
    owner: dict = {}
    cats: dict = {}
    for t in tables:
        try:
            cats[t] = catalog.table(t)
        except Exception:
            return None
        for name in cats[t].schema.names:
            if name in owner:
                owner[name] = None                # ambiguous
            else:
                owner[name] = t

    def owner_of(cols) -> Optional[str]:
        owners = {owner.get(c) for c in cols}
        if len(owners) != 1 or None in owners:
            return None
        return owners.pop()

    for conj in loose:
        t = owner_of(conj.columns())
        if t is None:
            return None
        tables[t].append(conj)

    # the probe table: where the aggregate expressions read from
    agg_cols: set = set()
    for a in plan.aggs:
        if a.expr is not None:
            agg_cols |= a.expr.columns()
    if agg_cols:
        probe = owner_of(agg_cols)
        if probe is None:
            return None
    else:
        probe = max(tables, key=lambda t: cats[t].num_rows)

    # join graph must be a tree spanning all tables, rooted at the probe
    if len(edges) != len(tables) - 1:
        return None
    adj: dict = {t: [] for t in tables}
    for ca, cb in edges:
        ta, tb = owner.get(ca), owner.get(cb)
        if ta is None or tb is None or ta == tb:
            return None
        adj[ta].append((tb, cb, ca))
        adj[tb].append((ta, ca, cb))
    order = [probe]
    parent_edge: dict = {}                   # table -> (parent, key, pcol)
    seen = {probe}
    i = 0
    while i < len(order):
        t = order[i]
        i += 1
        for (other, okey, tcol) in adj[t]:
            if other in seen:
                continue
            seen.add(other)
            parent_edge[other] = (t, okey, tcol)
            order.append(other)
    if len(seen) != len(tables):
        return None                          # disconnected (cross join)

    # bottom-up build order: children before the builds that probe them
    build_tables = list(reversed(order[1:]))
    bidx = {t: i for i, t in enumerate(build_tables)}

    # group keys: all must resolve to ONE probe-adjacent build (B*)
    group_build: Optional[str] = None
    for g in plan.group_by:
        t = owner.get(g)
        if t is None:
            return None
        if t == probe:
            cand = [other for other, okey, tcol in adj[probe] if tcol == g]
            if len(cand) != 1:
                return None
            t = cand[0]
        if group_build is None:
            group_build = t
        elif group_build != t:
            return None
    if group_build is not None:
        if parent_edge[group_build][0] != probe:
            return None                      # FD chain only one hop deep

    builds = []
    for t in build_tables:
        par, key, pcol = parent_edge[t]
        col = cats[t].column(key)
        if col.dbtype not in DEVICE_JOIN_KEY_TYPES:
            return None
        keys = build_keys(cats[t], key)
        if keys.size == 0 or keys.size > MAX_DEVICE_JOIN_DOMAIN:
            return None
        lo, span = int(keys[0]), int(keys[-1]) - int(keys[0]) + 1
        if keys.size == span:
            dom, ranked = (float(lo), span), None
        else:
            dom = (0.0, int(keys.size))
            ranked = RankedKeys(t, key, cats[t].version, keys)
        payload = []
        if t == group_build:
            for g in plan.group_by:
                if owner.get(g) == t and g != key:
                    pc = cats[t].column(g)
                    if pc.dbtype not in DEVICE_JOIN_PAYLOAD_TYPES:
                        return None
                    payload.append(g)
        pedges = [(bidx[other], tcol)
                  for other, okey, tcol in adj[t]
                  if other != par and other in bidx]
        cols = set(payload) | {key} | {c for _, c in pedges}
        for conj in tables[t]:
            cols |= conj.columns()
        builds.append(DeviceBuild(
            t, tables[t], key, dom, payload, pedges, sorted(cols), ranked))

    probe_edges = [(bidx[other], tcol)
                   for other, okey, tcol in adj[probe] if other in bidx]
    if group_build is not None:
        gb = bidx[group_build]
        key_domain = builds[gb].domain
        n_groups = key_domain[1]
        pk = [c for b, c in probe_edges if b == gb][0]
    else:
        gb, key_domain, n_groups, pk = None, (0.0, 1), 1, None
    group_sources: list = []
    for g in plan.group_by:
        t = owner.get(g)
        if t == probe or g == builds[gb].key:
            group_sources.append(("key",))
        else:
            group_sources.append(("payload", builds[gb].payload.index(g)))
    if group_sources and ("key",) not in group_sources:
        # the device groups at build-key granularity; payload-only group
        # keys (e.g. GROUP BY a dimension attribute) are coarser and
        # would need a second merge — leave those to the host join
        return None

    pcols: set = set() if pk is None else {pk}
    pcols |= {c for _, c in probe_edges}
    pcols |= agg_cols
    for conj in tables[probe]:
        pcols |= conj.columns()
    if not pcols:
        pcols = {cats[probe].schema.names[0]}

    return JoinAggSpec(probe, tables[probe], probe_edges, builds, gb,
                       list(plan.group_by), group_sources, list(plan.aggs),
                       n_groups, key_domain, sorted(pcols))


# ---------------------------------------------------------------------------
# physical layout of the device partial-aggregate matrix
# ---------------------------------------------------------------------------


@dataclass
class PartialLayout:
    """Column layout of the raw-partial matrix one device batch step emits.

    Columns ``[0, n_sum)`` combine by addition (cnt_star, then per-agg
    count and — for sum/avg — value-sum slots, in agg order); the remaining
    columns are one min- or max-combining slot per min/max aggregate.
    Ratios and NULL masking are *not* applied on device — partials stay
    mergeable across batches and ``parallel.finalize_partials`` applies
    them once at the end, so the arithmetic is identical no matter how many
    batches the input was split into."""
    n_sum: int
    plans: list                  # (agg_idx, kind, cnt_col, val_col)
    minmax: list                 # (agg_idx, fn, cnt_col, out_col)
    kinds: np.ndarray            # (K,) int8: 0 add / 1 min / 2 max
    init: np.ndarray             # (K,) float64 combine identity per column


def partial_layout(spec: ScanAggSpec) -> PartialLayout:
    plans, minmax = [], []
    n_sum = 1                                   # col 0: cnt_star
    for i, a in enumerate(spec.aggs):
        if a.expr is None:
            plans.append((i, "count_star", 0, 0))
            continue
        cnt = n_sum
        n_sum += 1
        if a.fn in ("sum", "avg"):
            plans.append((i, a.fn, cnt, n_sum))
            n_sum += 1
        elif a.fn == "count":
            plans.append((i, "count", cnt, 0))
        else:
            minmax.append([i, a.fn, cnt, 0])
    k = n_sum
    for mm in minmax:
        mm[3] = k
        k += 1
    kinds = np.zeros(k, dtype=np.int8)
    init = np.zeros(k, dtype=np.float64)
    for _, fn, _, c in minmax:
        kinds[c] = 1 if fn == "min" else 2
        init[c] = np.inf if fn == "min" else -np.inf
    return PartialLayout(n_sum, plans, [tuple(m) for m in minmax],
                         kinds, init)


@dataclass
class ScanAggGeometry:
    """Batch decomposition + byte footprint of one device scan-agg.  The
    geometry depends only on (table, shard count, batch_rows config) —
    never on the budget — which is what keeps the budget matrix
    bit-identical."""
    batch_rows: int
    n_batches: int
    row_bytes: int
    carry_nbytes: int
    batch_bytes: int
    resident_bytes: int


def scan_agg_geometry(spec: ScanAggSpec, table, shards: int,
                      batch_rows: Optional[int] = None) -> ScanAggGeometry:
    n_rows = table.num_rows
    m = int(batch_rows or DEVICE_BATCH_ROWS)
    # round up to the shard count, but never pad past the table: a small
    # table gets one table-sized batch instead of a full default batch of
    # mostly padding (which would inflate the byte estimates the tier
    # routing runs on up to ~16x)
    cap = -(-max(1, n_rows) // shards) * shards
    rows = min(-(-m // shards) * shards, cap)
    n_batches = max(1, -(-n_rows // rows))
    row_bytes = 1                                   # valid mask
    for c in spec.columns:
        row_bytes += table.column(c).data.dtype.itemsize
    carry = spec.n_groups * len(partial_layout(spec).kinds) * 8
    return ScanAggGeometry(
        batch_rows=rows, n_batches=n_batches, row_bytes=row_bytes,
        carry_nbytes=carry,
        batch_bytes=rows * row_bytes + carry,
        resident_bytes=n_batches * rows * row_bytes + carry)


@dataclass
class JoinAggGeometry:
    """Batch decomposition + byte footprint of one device join-agg.  The
    probe fields quack like ``ScanAggGeometry``; ``state_bytes`` is the
    HBM-resident working state (build matrices, ranked keys, carry) that
    stays on device for the whole query, and ``working_bytes`` is the
    streamed admission unit: state plus a double-buffered copy of the
    largest single stream batch (build or probe)."""
    batch_rows: int              # probe batch rows
    n_batches: int               # probe batch count
    row_bytes: int               # probe bytes per row
    carry_nbytes: int            # probe partial-matrix bytes
    state_bytes: int             # carry + build matrices + ranked keys
    max_batch_bytes: int         # largest single batch across all streams
    working_bytes: int           # state + 2 * max batch (streamed unit)
    resident_bytes: int          # every stream fully resident + state
    build_geoms: list            # per-build ScanAggGeometry (stream shape)


def join_agg_geometry(spec: JoinAggSpec, catalog, shards: int,
                      batch_rows: Optional[int] = None) -> JoinAggGeometry:
    pg = scan_agg_geometry(spec.probe_spec(), catalog.table(spec.probe_table),
                           shards, batch_rows)
    state = pg.carry_nbytes + sum(b.state_bytes for b in spec.builds)
    max_batch = pg.batch_rows * pg.row_bytes
    resident = pg.n_batches * pg.batch_rows * pg.row_bytes
    build_geoms = []
    for b in spec.builds:
        bspec = ScanAggSpec(b.table, [], [], [], [], 1, list(b.columns))
        bg = scan_agg_geometry(bspec, catalog.table(b.table), shards,
                               batch_rows)
        build_geoms.append(bg)
        max_batch = max(max_batch, bg.batch_rows * bg.row_bytes)
        resident += bg.n_batches * bg.batch_rows * bg.row_bytes
    return JoinAggGeometry(
        batch_rows=pg.batch_rows, n_batches=pg.n_batches,
        row_bytes=pg.row_bytes, carry_nbytes=pg.carry_nbytes,
        state_bytes=state, max_batch_bytes=max_batch,
        working_bytes=state + 2 * max_batch,
        resident_bytes=resident + state, build_geoms=build_geoms)


def choose_device_join_tier(resident_bytes: float, working_bytes: float,
                            device_budget: Optional[int],
                            host_budget: Optional[int] = None) -> str:
    """Join-tier placement, mirroring ``choose_device_tier``'s semantics:
    ``"resident"`` when every stream fits the device budget at once,
    ``"streamed"`` when the HBM working state plus a double-buffered batch
    does, ``"host"`` otherwise.  The host-budget demotion carries the same
    caveat as the scan-agg tier: streaming only bounds residency through
    eviction, so it needs a real device budget to be a demotion target."""
    streamable = device_budget is not None \
        and working_bytes <= device_budget
    if device_budget is not None and resident_bytes > device_budget:
        return "streamed" if streamable else "host"
    if host_budget is not None and resident_bytes > host_budget:
        return "streamed" if streamable else "host"
    return "resident"


def default_mesh():
    """The device tier's mesh when the caller passes none: every device JAX
    reports, on one ``data`` axis."""
    from jax.sharding import Mesh

    from .device_cache import jax_runtime
    return Mesh(np.array(jax_runtime().devices()).reshape(-1), ("data",))


def mesh_shards(mesh) -> int:
    shards = 1
    for ax in mesh.axis_names:
        if ax in ("pod", "data"):
            shards *= mesh.shape[ax]
    return shards


# ---------------------------------------------------------------------------
# device placement (previously optimizer.choose_device_tier)
# ---------------------------------------------------------------------------


def choose_device_tier(resident_bytes: float, batch_bytes: float,
                       device_budget: Optional[int],
                       host_budget: Optional[int] = None,
                       host_bytes: Optional[float] = None,
                       hit_history: int = 0) -> str:
    """Device-tier placement decision (paper optimization level 3, one tier
    up): ``"resident"`` when every block of the input fits the device
    budget at once, ``"streamed"`` when only morsel batches do (double-
    buffered: two batch working sets in flight), ``"host"`` when not even
    one batch fits — the plan stays on the host tier, whose blocking
    operators spill.

    ``host_budget``/``host_bytes`` fold in the *host* memory budget: the
    resident path keeps full device-resident copies (host RAM on CPU
    backends), so an input over the host budget is demoted to streaming —
    but only under a real device budget, because streaming bounds
    residency through *eviction*: with ``device_budget=None`` nothing ever
    evicts, so the demotion would silently retain the whole table and the
    plan goes to the bounded host spill tier instead.

    ``hit_history`` biases admission the way the paper's optimizer uses
    runtime statistics: a *borderline* table — one that fits the budget but
    would occupy more than ``DEVICE_BORDERLINE_FRACTION`` of it, crowding
    out every other table's blocks — is admitted resident only once its
    cumulative device-cache hits (``DeviceBufferManager.hit_history``)
    reach ``DEVICE_PROMOTE_HITS``.  A first query on such a table streams;
    its blocks still land in the cache, so a repeat query observes hits and
    flips to resident."""
    streamable = device_budget is not None \
        and 2 * batch_bytes <= device_budget
    if device_budget is not None and resident_bytes > device_budget:
        return "streamed" if streamable else "host"
    if host_budget is not None and host_bytes is not None \
            and host_bytes > host_budget:
        return "streamed" if streamable else "host"
    if device_budget is not None and streamable \
            and resident_bytes > DEVICE_BORDERLINE_FRACTION * device_budget \
            and hit_history < DEVICE_PROMOTE_HITS:
        return "streamed"
    return "resident"


# ---------------------------------------------------------------------------
# imprint-driven data skipping: plan-time skip-sets (paper §3.1)
# ---------------------------------------------------------------------------


def _simple_range(expr: Expr):
    """Detect `col <cmp> literal` for the imprint fast path.

    Returns (col, lo, hi, lo_strict, hi_strict) with +-inf open ends."""
    if not isinstance(expr, BinOp) \
            or expr.op not in ("<", "<=", ">", ">=", "="):
        return None
    l, r = expr.left, expr.right
    op = expr.op
    if isinstance(r, Col) and isinstance(l, (Lit, DateLit)):
        l, r = r, l
        op = {"<": ">", ">": "<", "<=": ">=", ">=": "<=", "=": "="}[op]
    if not (isinstance(l, Col) and isinstance(r, (Lit, DateLit))):
        return None
    if isinstance(r, DateLit):
        from .types import date_from_string
        v = float(date_from_string(r.text))
    else:
        if isinstance(r.value, str) or r.value is None:
            return None
        v = float(r.value)
    lo, hi = -np.inf, np.inf
    lo_s = hi_s = False
    if op == "=":
        lo = hi = v
    elif op == "<":
        hi, hi_s = v, True
    elif op == "<=":
        hi = v
    elif op == ">":
        lo, lo_s = v, True
    elif op == ">=":
        lo = v
    return l.name, lo, hi, lo_s, hi_s


@dataclass
class SkipSet:
    """Per-scan block-qualification bitmap derived from imprints at plan
    time.

    ``cand[b]`` is True when imprint block ``b`` *may* contain rows
    satisfying every simple-range filter conjunct on the scan — the AND of
    each conjunct's zone-map candidate bitmap, so it is a sound superset of
    the qualifying blocks (a block is dropped only when some conjunct is
    provably unsatisfiable there).  The skip-set is advisory: every tier
    still evaluates the full predicate on the blocks it does read.

    Skip-sets are derived against one table version and re-validated with
    ``valid_for`` at execution time; cache keys carry table versions too,
    so a stale bitmap is never consumed."""
    table: str
    version: int
    block: int                    # rows per imprint block
    n_rows: int
    cand: np.ndarray              # (n_blocks,) bool candidate bitmap
    columns: tuple                # filter columns the bitmap derives from

    @property
    def n_blocks(self) -> int:
        return len(self.cand)

    @property
    def n_skipped(self) -> int:
        return int((~self.cand).sum())

    def valid_for(self, table) -> bool:
        return (getattr(table, "version", None) == self.version
                and table.num_rows == self.n_rows)

    def batch_qualifies(self, s: int, e: int) -> bool:
        """May the row range [s, e) contain a qualifying row?"""
        if e <= s:
            return False
        return bool(self.cand[s // self.block:
                              (e - 1) // self.block + 1].any())

    def candidate_ranges(self):
        """Merged (start_row, end_row) ranges of candidate blocks."""
        out: list[tuple[int, int]] = []
        for b in np.nonzero(self.cand)[0]:
            s = int(b) * self.block
            e = min(self.n_rows, s + self.block)
            if out and out[-1][1] == s:
                out[-1] = (out[-1][0], e)
            else:
                out.append((s, e))
        return out


def derive_skip_sets(plan: PlanNode, db) -> dict:
    """Walk ``Filter(Scan)`` shapes over base tables and intersect each
    simple-range conjunct's imprint candidate bitmap into one ``SkipSet``
    per scan, keyed by ``id(scan_node)`` (plan-cache copies are shallow, so
    the normalized plan objects — and hence the keys — are shared).

    Gated on ``db.data_skipping`` (the forced-off knob the differential
    harness flips) and on the database having an ``IndexManager``; scans
    with no applicable imprint simply get no entry."""
    out: dict[int, SkipSet] = {}
    im = getattr(db, "index_manager", None)
    if im is None or not getattr(db, "data_skipping", True):
        return out

    def visit(node: PlanNode) -> None:
        if isinstance(node, FilterNode) and isinstance(node.child, ScanNode):
            scan = node.child
            try:
                table = db.catalog.table(scan.table)
            except Exception:
                table = None
            if table is not None:
                cand = None
                block = 0
                cols: list[str] = []
                for conj in split_conjuncts(node.predicate):
                    rng = _simple_range(conj)
                    if rng is None:
                        continue
                    cname, lo, hi, lo_s, hi_s = rng
                    info = im.candidate_info(scan.table, cname, lo, hi,
                                             lo_s, hi_s)
                    if info is None:
                        continue
                    c, block, _ = info
                    cand = c.copy() if cand is None else (cand & c)
                    cols.append(cname)
                if cand is not None:
                    out[id(scan)] = SkipSet(
                        scan.table, table.version, block, table.num_rows,
                        cand, tuple(cols))
        for c in node.children:
            visit(c)

    visit(plan)
    return out


# ---------------------------------------------------------------------------
# normalization: SQL and builder plans converge to identical shapes
# ---------------------------------------------------------------------------


def _conjoin(preds: list[Expr]) -> Expr:
    from .expression import BinOp
    out = preds[0]
    for p in preds[1:]:
        out = BinOp("and", out, p)
    return out


def _push_renames_into_agg(proj: ProjectNode, agg: AggregateNode,
                           catalog) -> Optional[AggregateNode]:
    """Project(Aggregate) that only renames — group keys identity-mapped in
    key order, then every aggregate output referenced exactly once, in agg
    order — folds into the aggregate's own output names.  This is the SQL
    front-end's ``__aggN`` rename projection; eliding it is what lets the
    device-tier matcher see SQL aggregates."""
    keys = list(agg.group_by)
    exprs = list(proj.exprs)
    if len(exprs) != len(keys) + len(agg.aggs):
        return None
    if any(not isinstance(e, Col) for e, _ in exprs):
        return None
    for (e, n), k in zip(exprs[:len(keys)], keys):
        if e.name != k or n != k:
            return None
    new_aggs = []
    for (e, n), a in zip(exprs[len(keys):], agg.aggs):
        if e.name != a.name:
            return None
        new_aggs.append(AggSpec(a.fn, a.expr, n))
    names = keys + [a.name for a in new_aggs]
    if len(set(names)) != len(names):
        return None
    return AggregateNode(agg.child, agg.group_by, tuple(new_aggs))


def normalize(plan: PlanNode, catalog) -> PlanNode:
    """Semantics-preserving canonicalization applied after optimization:

    * adjacent FilterNodes merge into one whose conjuncts are sorted by
      their (deterministic, value-based) repr — entry points that emitted
      the same predicates in different order converge, and the compiled
      step caches key on one canonical conjunct sequence;
    * identity projections (bare-Col, same names, same order as the child's
      output) are elided;
    * pure-rename projections over an aggregate fold into the aggregate's
      output names (only when the output column order is preserved — a
      reordering projection stays, since result column order is
      observable through the embedding API)."""
    node = plan.with_children(
        tuple(normalize(c, catalog) for c in plan.children))
    if isinstance(node, FilterNode):
        conjs: list[Expr] = []
        inner: PlanNode = node
        while isinstance(inner, FilterNode):
            conjs.extend(split_conjuncts(inner.predicate))
            inner = inner.child
        conjs.sort(key=repr)
        return FilterNode(inner, _conjoin(conjs))
    if isinstance(node, ProjectNode):
        child = node.child
        if all(isinstance(e, Col) and e.name == n for e, n in node.exprs):
            try:
                if [n for _, n in node.exprs] == \
                        list(child.output_columns(catalog)):
                    return child
            except Exception:
                pass
        if isinstance(child, AggregateNode):
            pushed = _push_renames_into_agg(node, child, catalog)
            if pushed is not None:
                return pushed
    return node


# ---------------------------------------------------------------------------
# the costed tier policy — the ONE home of routing thresholds
# ---------------------------------------------------------------------------


@dataclass
class TierPolicy:
    """Every tier-routing threshold in the engine, as one object.

    Plan-time annotation and runtime refinement both go through these
    methods; the executors hold a policy but contain no routing logic of
    their own.  The byte models mirror what the operators actually pin:
    blocking state per row is the key bytes plus ~16 bytes of
    index/gid/bookkeeping overhead."""

    bufman: object = None                 # host BufferManager (or None)
    devman: object = None                 # DeviceBufferManager (or None)

    @classmethod
    def for_db(cls, db) -> "TierPolicy":
        return cls(bufman=getattr(db, "buffer_manager", None),
                   devman=getattr(db, "device_manager", None))

    # -- budgets --------------------------------------------------------------
    @property
    def host_budget(self) -> Optional[int]:
        return None if self.bufman is None else self.bufman.budget

    @property
    def device_budget(self) -> Optional[int]:
        return None if self.devman is None else self.devman.budget

    def over_budget(self, est_bytes: float) -> bool:
        b = self.host_budget
        return b is not None and est_bytes > b

    # -- blocking-operator state models (bytes the op would pin) --------------
    @staticmethod
    def join_state_bytes(n_left: int, n_right: int, key_bytes: int) -> int:
        return (n_left + n_right) * (key_bytes + 16)

    @staticmethod
    def group_state_bytes(n_rows: int, key_bytes: int) -> int:
        return n_rows * (key_bytes + 16)

    @staticmethod
    def sort_state_bytes(n_rows: int, n_keys: int) -> int:
        return n_rows * 8 * (n_keys + 1)

    # -- runtime tier decisions (actual cardinalities) ------------------------
    def blocking_tier(self, est_bytes: float) -> str:
        return TIER_SPILL if self.over_budget(est_bytes) else TIER_IN_MEMORY

    def spills(self, est_bytes: float) -> bool:
        return self.blocking_tier(est_bytes) == TIER_SPILL

    def group_spills(self, n_rows: int, key_bytes: int,
                     probe_groups: Callable[[], int]) -> bool:
        """Grace-hash only when the input AND the probed grouping state are
        both over budget: a low-cardinality grouping (few distinct keys)
        stays in memory — its blocking state is tiny no matter how large
        the input, and partitioning by key could never split the dominant
        groups.  ``probe_groups`` samples actual rows (level-3 runtime
        statistics) and is only paid when the cheap input test trips."""
        if not self.over_budget(self.group_state_bytes(n_rows, key_bytes)):
            return False
        return self.over_budget(
            self.group_state_bytes(probe_groups(), key_bytes))

    def result_spills(self, total_bytes: int) -> bool:
        """Budgeted result materialization: over-budget final tables stream
        to memmapped columns instead of a second RAM materialization."""
        return self.bufman is not None and self.over_budget(total_bytes)

    # -- volcano row-spool estimate (was volcano._spool_estimate) -------------
    def row_spool_estimate(self, node: AggregateNode,
                           catalog) -> Optional[int]:
        """Input-size estimate when a volcano aggregate should spool, else
        None (one plan walk decides *and* sizes the partition fan-out).
        Volcano rows hold *decoded* values: a VARCHAR cell is the full
        string, not an 8-byte code, so string columns carry their average
        decoded heap width on top of ``estimate_bytes``' flat rate."""
        if self.host_budget is None or not node.group_by:
            return None
        est = estimate_bytes(node.child, catalog) \
            + _varchar_row_surcharge(node.child, catalog)
        return int(est) if est > self.host_budget else None

    # -- device placement -----------------------------------------------------
    def device_tier(self, geom: ScanAggGeometry, table: str) -> str:
        hits = 0 if self.devman is None else self.devman.hit_history(table)
        return choose_device_tier(
            geom.resident_bytes, geom.batch_bytes, self.device_budget,
            host_budget=self.host_budget, host_bytes=geom.resident_bytes,
            hit_history=hits)

    def device_join_tier(self, geom: JoinAggGeometry) -> str:
        return choose_device_join_tier(
            geom.resident_bytes, geom.working_bytes,
            self.device_budget, self.host_budget)


def _varchar_row_surcharge(node: PlanNode, catalog) -> float:
    if isinstance(node, ScanNode):
        extra = 0.0
        t = catalog.table(node.table)
        for name in (node.columns or t.schema.names):
            col = t.columns[name]
            if col.dbtype == DBType.VARCHAR and len(col.heap):
                extra += len(col) * (col.heap.nbytes() / len(col.heap))
        return extra
    extra = sum(_varchar_row_surcharge(c, catalog) for c in node.children)
    if isinstance(node, FilterNode) and extra:
        # scale by the filter's estimated selectivity, mirroring how
        # estimate_bytes scales its flat per-column rate by estimate_rows
        rows_in = estimate_rows(node.child, catalog)
        rows_out = estimate_rows(node, catalog)
        extra *= rows_out / max(1.0, rows_in)
    return extra


# ---------------------------------------------------------------------------
# the physical plan
# ---------------------------------------------------------------------------


@dataclass
class PhysicalOp:
    """One operator's tier annotation: the decision, the byte estimate it
    was made from, and the budget reservation the tier implies (what the
    operator expects to pin — the whole state in memory, at most the
    budget when spilling, the double-buffered batch working set when
    streaming devices)."""
    node: PlanNode
    tier: str
    est_bytes: int = 0
    reservation: int = 0
    detail: str = ""
    children: tuple = ()

    def lines(self, indent: int = 0) -> list[str]:
        pad = "  " * indent
        extra = f" {self.detail}" if self.detail else ""
        out = [f"{pad}{node_line(self.node)}"
               f" :: {self.tier}"
               f" [est={self.est_bytes}B reserve={self.reservation}B]"
               f"{extra}"]
        for c in self.children:
            out.extend(c.lines(indent + 1))
        return out


@dataclass
class PhysicalPlan:
    """The lowering result every executor consumes."""
    plan: PlanNode                        # normalized logical plan
    policy: TierPolicy
    catalog: object
    scan_agg: Optional[ScanAggSpec] = None
    agg_core: Optional[AggregateNode] = None
    agg_tier: Optional[str] = None        # device-*/parallel-host when set
    suffix_plan: Optional[PlanNode] = None
    geometry: Optional[ScanAggGeometry] = None
    # device join tier: the matched join-agg core and its geometry.  The
    # join runs in one of two modes ("resident"/"streamed"); both annotate
    # as TIER_DEVICE_JOIN — the mode shows in the operator detail.
    join_agg: Optional[JoinAggSpec] = None
    join_geometry: Optional[JoinAggGeometry] = None
    join_mode: Optional[str] = None
    # device sort tier: the ORDER BY suffix node fused onto a device core
    # (sort keys computed + lexsorted in HBM; only the top rows fetched)
    sort_node: Optional[OrderByNode] = None
    sort_on_device: bool = False
    distributed: bool = False
    # observed group cardinality from a previous execution of this plan
    # shape (serving.PlanCache feedback) — refines the aggregate's
    # annotation with what the runtime actually saw instead of the
    # level-1 row estimate.  Only set when the plan has exactly one
    # aggregate (otherwise the observation is ambiguous).
    group_card_hint: Optional[int] = None
    # imprint-derived skip-sets keyed by id(scan node) — shared by shallow
    # plan-cache copies because the normalized plan objects are shared
    skip_sets: dict = field(default_factory=dict)
    _reservations: Optional[tuple] = None   # cached total_reservations()

    # -- queries --------------------------------------------------------------
    def device_tier(self) -> bool:
        return self.agg_tier in DEVICE_TIERS

    def demote_device(self, reason: str = "runtime fallback") -> None:
        """A device attempt failed at runtime (lowering gap, placement
        race): the core re-routes to the host program.  The annotation is
        updated so EXPLAIN output reflects what actually ran.  A fused
        device sort demotes with its core — the host suffix re-sorts."""
        self.agg_tier = TIER_PARALLEL_HOST
        self.sort_on_device = False
        self._demote_reason = reason

    def total_reservations(self) -> tuple[int, int]:
        """Summed per-operator budget reservations as ``(host_bytes,
        device_bytes)`` — what the admission gate reserves before this plan
        executes.  Each side is capped at its budget: a plan whose
        reservations sum past the budget is exactly what the spill/stream
        tiers bound at runtime, and it must be admissible when alone.
        Computed once and cached (shallow plan-cache copies share it)."""
        if self._reservations is None:
            host = device = 0

            def visit(op: PhysicalOp):
                nonlocal host, device
                if op.tier in DEVICE_TIERS:
                    device += op.reservation
                else:
                    host += op.reservation
                for c in op.children:
                    visit(c)

            visit(self.annotate())
            hb = self.policy.host_budget
            db = self.policy.device_budget
            if hb is not None:
                host = min(host, hb)
            if db is not None:
                device = min(device, db)
            self._reservations = (int(host), int(device))
        return self._reservations

    def skip_set_for(self, node: PlanNode) -> Optional[SkipSet]:
        return self.skip_sets.get(id(node))

    def core_skip_set(self) -> Optional[SkipSet]:
        """The skip-set attached to the scan-agg core's base scan, if any
        (what ``DistributedScanAgg`` intersects with its batch geometry)."""
        node: Optional[PlanNode] = self.agg_core
        while node is not None:
            if isinstance(node, ScanNode):
                return self.skip_sets.get(id(node))
            node = node.children[0] if node.children else None
        return None

    def skip_set_for_table(self, name: str) -> Optional[SkipSet]:
        """The skip-set attached to the (unique, by the join matcher's
        no-self-join rule) base scan of ``name`` — what the per-table
        streams of a device join consult on the probe and build sides."""
        for n in _walk_nodes(self.plan):
            if isinstance(n, ScanNode) and n.table == name:
                ss = self.skip_sets.get(id(n))
                if ss is not None:
                    return ss
        return None

    def _skip_note(self, node: PlanNode) -> str:
        ss = self.skip_sets.get(id(node))
        if ss is None:
            return ""
        return f"(skip: {ss.n_skipped}/{ss.n_blocks} blocks)"

    def _delta_note(self, node: PlanNode) -> str:
        """Merge-on-read visibility in EXPLAIN: a base-table scan whose
        table carries an uncompacted delta tail says how many rows it will
        merge on read."""
        if not isinstance(node, ScanNode):
            return ""
        t = self.catalog.tables.get(node.table) \
            if hasattr(self.catalog, "tables") else None
        if t is None or not t.delta_rows:
            return ""
        return f"(delta: {t.delta_rows} rows)"

    # -- annotation -----------------------------------------------------------
    def annotate(self) -> PhysicalOp:
        return self._annotate(self.plan)

    def _annotate(self, node: PlanNode) -> PhysicalOp:
        if node is self.agg_core and self.agg_tier in (
                TIER_DEVICE_RESIDENT, TIER_DEVICE_STREAMED,
                TIER_DEVICE_JOIN):
            return self._annotate_core(node)
        if node is self.sort_node and self.sort_on_device:
            children = tuple(self._annotate(c) for c in node.children)
            est = int(self.policy.sort_state_bytes(
                self._core_groups(), len(node.keys)))
            return PhysicalOp(node, TIER_DEVICE_SORT, est, est,
                              "(fused onto device core)", children)
        children = tuple(self._annotate(c) for c in node.children)
        policy = self.policy
        budget = policy.host_budget
        if isinstance(node, JoinNode):
            est = int(policy.join_state_bytes(
                estimate_rows(node.left, self.catalog),
                estimate_rows(node.right, self.catalog),
                8 * len(node.left_keys)))
            tier = policy.blocking_tier(est)
        elif isinstance(node, AggregateNode):
            kb = 8 * max(1, len(node.group_by))
            est = int(policy.group_state_bytes(
                estimate_rows(node.child, self.catalog), kb))
            tier = policy.blocking_tier(est)
            if self.group_card_hint is not None and node.group_by:
                # cardinality feedback (serving.PlanCache): a previous run
                # observed the actual group count, so mirror the runtime
                # rule — spill only when the input state AND the observed
                # grouping state are both over budget.  A low-cardinality
                # grouping annotates in-memory no matter how large the
                # input, exactly as it will execute.
                observed = int(policy.group_state_bytes(
                    self.group_card_hint, kb))
                tier = TIER_SPILL if (policy.over_budget(est)
                                      and policy.over_budget(observed)) \
                    else TIER_IN_MEMORY
                est = observed if tier == TIER_IN_MEMORY else est
        elif isinstance(node, OrderByNode):
            est = int(policy.sort_state_bytes(
                estimate_rows(node.child, self.catalog), len(node.keys)))
            tier = policy.blocking_tier(est)
        else:
            est = int(estimate_rows(node, self.catalog) * 8)
            tier = TIER_IN_MEMORY
        reserve = est if tier == TIER_IN_MEMORY \
            else min(est, budget if budget is not None else est)
        detail = "(runtime-refined)" if tier == TIER_SPILL or (
            isinstance(node, (JoinNode, AggregateNode, OrderByNode))
            and budget is not None) else ""
        if isinstance(node, AggregateNode) and node.group_by \
                and self.group_card_hint is not None:
            detail = f"{detail} (observed groups=" \
                     f"{self.group_card_hint})".strip()
        if node is self.agg_core and self.agg_tier == TIER_PARALLEL_HOST:
            # the core matched a device pattern but runs as an ordinary
            # host program (device declined, or a runtime fallback) —
            # annotate with the HOST byte model like any other aggregate,
            # and record why the device tier was not used
            kind = "join-agg" if self.join_agg is not None else "scan-agg"
            extra = f"{kind} core kept on host"
            if getattr(self, "_demote_reason", None):
                extra += f" ({self._demote_reason})"
            detail = f"{detail} {extra}".strip()
        note = self._skip_note(node)
        if note:
            detail = f"{detail} {note}".strip()
        dnote = self._delta_note(node)
        if dnote:
            detail = f"{detail} {dnote}".strip()
        return PhysicalOp(node, tier, est, reserve, detail, children)

    def _core_groups(self) -> int:
        if self.join_agg is not None:
            return self.join_agg.n_groups
        if self.scan_agg is not None:
            return self.scan_agg.n_groups
        return 1

    def _annotate_core(self, node: PlanNode) -> PhysicalOp:
        """A device-routed scan-agg or join-agg core: one tier decision
        covers the whole fused subtree (filters, scans and — for the join
        tier — the build/probe joins execute inside the jitted steps)."""
        if self.agg_tier == TIER_DEVICE_JOIN:
            g = self.join_geometry
            if self.join_mode == "resident":
                est, reserve = g.resident_bytes, g.resident_bytes
            else:
                est, reserve = g.resident_bytes, g.working_bytes
            detail = f"groups={self.join_agg.n_groups}"
            detail += f" builds={len(self.join_agg.builds)}"
            detail += f" mode={self.join_mode}"
            detail += f" batches={g.n_batches}x{g.batch_rows}rows"
        else:
            g = self.geometry
            if self.agg_tier == TIER_DEVICE_RESIDENT:
                est, reserve = g.resident_bytes, g.resident_bytes
            else:
                est, reserve = g.resident_bytes, 2 * g.batch_bytes
            detail = f"groups={self.scan_agg.n_groups}"
            detail += f" batches={g.n_batches}x{g.batch_rows}rows"

        ranked = {} if self.join_agg is None else {
            b.key: len(b.ranked.keys) for b in self.join_agg.builds
            if b.ranked is not None}

        def fused(n: PlanNode) -> PhysicalOp:
            d = "(fused)"
            if isinstance(n, JoinNode):
                for k in (*n.left_keys, *n.right_keys):
                    if k in ranked:
                        d = f"{d} ranked keys: {ranked[k]}"
            note = self._skip_note(n)
            if note:
                d = f"{d} {note}"
            dnote = self._delta_note(n)
            if dnote:
                d = f"{d} {dnote}"
            return PhysicalOp(
                n, self.agg_tier, 0, 0, d,
                tuple(fused(c) for c in n.children))

        return PhysicalOp(node, self.agg_tier, int(est), int(reserve),
                          detail, tuple(fused(c) for c in node.children))

    # -- rendering ------------------------------------------------------------
    def render(self) -> str:
        head = "physical plan"
        if self.distributed:
            head += " [distributed]"
        b = self.policy.host_budget
        d = self.policy.device_budget
        head += f" memory_budget={b if b is not None else 'unlimited'}"
        head += f" device_budget={d if d is not None else 'unlimited'}"
        return "\n".join([head] + self.annotate().lines())

    def tier_summary(self) -> list[tuple[str, str]]:
        """(operator kind, tier) pairs in pre-order, skipping projections —
        the shape two entry points must agree on even when one carries a
        residual (trivial, reordering) projection the other lacks."""
        out: list[tuple[str, str]] = []

        def walk(op: PhysicalOp):
            if not isinstance(op.node, ProjectNode):
                out.append((type(op.node).__name__, op.tier))
            for c in op.children:
                walk(c)

        walk(self.annotate())
        return out


# ---------------------------------------------------------------------------
# the lowering pass
# ---------------------------------------------------------------------------


def _walk_nodes(node: PlanNode):
    yield node
    for c in node.children:
        yield from _walk_nodes(c)


def plan_physical(plan: PlanNode, db, *, do_optimize: bool = True,
                  distributed: bool = False, mesh=None,
                  group_card_hint: Optional[int] = None) -> PhysicalPlan:
    """Lower one logical plan to its physical plan: optimize (level 1),
    normalize (entry-point convergence), find the scan-agg core + suffix,
    and annotate tiers.  ``distributed`` enables the device tiers and — if
    no ``mesh`` is given — derives the default mesh from ``jax.devices()``
    (the only path that touches the accelerator runtime; plain host
    planning never imports jax).  ``group_card_hint`` is an observed group
    cardinality from a previous run of the same plan shape
    (``serving.PlanCache`` feedback); it refines the aggregate annotation
    and only applies when the plan has exactly one aggregate."""
    catalog = db.catalog
    if do_optimize:
        plan = optimize(plan, catalog)
    plan = normalize(plan, catalog)
    policy = TierPolicy.for_db(db)
    phys = PhysicalPlan(plan, policy, catalog, distributed=distributed)
    if group_card_hint is not None:
        n_aggs = sum(isinstance(n, AggregateNode)
                     for n in _walk_nodes(plan))
        if n_aggs == 1:
            phys.group_card_hint = int(group_card_hint)
    # imprint-driven data skipping (paper §3.1): every tier — device batch
    # streams, host morsels, volcano rows — consumes the same plan-time
    # skip-sets, so derivation happens before the host-only early return
    phys.skip_sets = derive_skip_sets(plan, db)
    if not distributed:
        # the sequential host path never consumes the scan-agg spec, and
        # matching is not free (dense-domain detection scans each group
        # key's min/max) — only the distributed lowering pays for it
        return phys

    core, suffix = find_scan_agg_core(plan, catalog)
    if core is None:
        return phys
    spec = match_scan_agg(core, catalog)
    jspec = match_join_agg(core, catalog) if spec is None else None
    if spec is None and jspec is None:
        return phys
    phys.agg_core = core
    phys.suffix_plan = suffix
    shard_table = catalog.table(spec.table if spec is not None
                                else jspec.probe_table)
    if spec is not None:
        phys.scan_agg = spec
    else:
        phys.join_agg = jspec
    if shard_table.num_rows < MIN_ROWS_TO_SHARD:
        return phys
    if mesh is None:
        mesh = default_mesh()
    shards = mesh_shards(mesh)
    batch_rows = getattr(db, "device_batch_rows", None)
    if spec is not None:
        geom = scan_agg_geometry(spec, shard_table, shards, batch_rows)
        phys.geometry = geom
        tier = policy.device_tier(geom, spec.table)
        phys.agg_tier = {"resident": TIER_DEVICE_RESIDENT,
                         "streamed": TIER_DEVICE_STREAMED,
                         "host": TIER_PARALLEL_HOST}[tier]
    else:
        jgeom = join_agg_geometry(jspec, catalog, shards, batch_rows)
        phys.join_geometry = jgeom
        mode = policy.device_join_tier(jgeom)
        phys.join_mode = None if mode == "host" else mode
        phys.agg_tier = TIER_PARALLEL_HOST if mode == "host" \
            else TIER_DEVICE_JOIN
    # ORDER BY directly over a device-routed core fuses onto the device:
    # sort keys are computed and lexsorted in HBM, only the surviving rows
    # come back.  Any deeper suffix (projection, HAVING) keeps the host
    # suffix path — the assembled aggregate is tiny there anyway.
    if phys.agg_tier in DEVICE_TIERS and isinstance(plan, OrderByNode) \
            and plan.children[0] is core:
        try:
            outputs = set(core.output_columns(catalog))
        except Exception:
            outputs = set()
        if outputs and all(col in outputs for col, _ in plan.keys):
            phys.sort_node = plan
            phys.sort_on_device = True
    return phys
