"""Row-at-a-time volcano baseline engine.

The paper benchmarks MonetDBLite against row-store, tuple-at-a-time systems
(SQLite/PostgreSQL/MariaDB §4) and attributes their poor analytical
performance to (a) row-wise storage forcing whole-table scans and (b)
per-tuple interpretation overhead.  Per the "implement the baseline too"
rule, this module is that comparator: the same logical plans interpreted
through Python-level row iterators with per-row expression evaluation.
Tests run identical queries through both engines and compare the answers.

It materializes rows as dicts — intentionally; the point of the baseline is
the processing *model*, not an optimized row engine.
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

from .expression import (BinOp, Case, Cast, Col, DateLit, Expr, Func, InList,
                         IsNull, Like, Lit, Not)
from .relalg import (AggregateNode, FilterNode, JoinNode, LimitNode,
                     OrderByNode, PlanNode, ProjectNode, ScanNode)
from .types import DBType

Row = dict


def _eval_row(e: Expr, row: Row):
    """Scalar (per-tuple) expression interpreter — the volcano way."""
    if isinstance(e, Col):
        return row[e.name]
    if isinstance(e, Lit):
        return e.value
    if isinstance(e, DateLit):
        from .types import date_from_string
        return int(date_from_string(e.text))
    if isinstance(e, BinOp):
        l = _eval_row(e.left, row)
        r = _eval_row(e.right, row)
        if e.op == "and":
            return bool(l) and bool(r) if l is not None and r is not None else False
        if e.op == "or":
            return bool(l) or bool(r)
        if l is None or r is None:
            return None if e.op in ("+", "-", "*", "/", "%") else False
        return {"+": lambda: l + r, "-": lambda: l - r, "*": lambda: l * r,
                "/": lambda: l / r if r != 0 else None,
                "%": lambda: l % r if r != 0 else None,
                "=": lambda: l == r, "<>": lambda: l != r,
                "<": lambda: l < r, "<=": lambda: l <= r,
                ">": lambda: l > r, ">=": lambda: l >= r}[e.op]()
    if isinstance(e, Not):
        v = _eval_row(e.child, row)
        return not bool(v)
    if isinstance(e, IsNull):
        v = _eval_row(e.child, row)
        isnull = v is None or (isinstance(v, float) and np.isnan(v))
        return (not isnull) if e.negate else isnull
    if isinstance(e, InList):
        v = _eval_row(e.child, row)
        return v in e.values
    if isinstance(e, Like):
        import fnmatch
        v = _eval_row(e.child, row)
        if v is None:
            return False
        pat = e.pattern.replace("%", "*").replace("_", "?")
        return fnmatch.fnmatchcase(str(v), pat)
    if isinstance(e, Func):
        a = _eval_row(e.args[0], row)
        if a is None:
            return None
        import math
        if e.name.lower() == "year":
            from .types import date_year
            return int(date_year(np.asarray([a]))[0])
        return {"sqrt": lambda: math.sqrt(max(a, 0.0)),
                "abs": lambda: abs(a), "floor": lambda: math.floor(a),
                "ceil": lambda: math.ceil(a), "log": lambda: math.log(a),
                "exp": lambda: math.exp(a),
                "round": lambda: round(a, int(e.args[1].value)
                                       if len(e.args) > 1 else 0)}[e.name.lower()]()
    if isinstance(e, Case):
        for c, v in e.branches:
            if _eval_row(c, row):
                return _eval_row(v, row)
        return _eval_row(e.default, row)
    if isinstance(e, Cast):
        v = _eval_row(e.child, row)
        if v is None:
            return None
        if e.to in (DBType.INT32, DBType.INT64):
            return int(v)
        return float(v)
    raise TypeError(f"volcano cannot evaluate {type(e).__name__}")


class VolcanoExecutor:
    """Pull-based iterator interpreter (open/next/close model)."""

    def __init__(self, database):
        self.db = database
        from .physplan import TierPolicy
        self.policy = TierPolicy.for_db(database)

    def execute(self, plan: PlanNode) -> list[Row]:
        return list(self._iter(plan))

    def _iter(self, node: PlanNode) -> Iterator[Row]:
        if isinstance(node, ScanNode):
            # row-store emulation: decode EVERY column per row (the paper's
            # point about row stores scanning entire tables)
            t = self.db.catalog.table(node.table)
            self._note_delta(t)
            decoded = {n: t.columns[n].to_numpy() for n in t.schema.names}
            names = list(t.schema.names)
            for i in range(t.num_rows):
                yield {n: _denull(decoded[n][i]) for n in names}
        elif isinstance(node, FilterNode):
            if isinstance(node.child, ScanNode):
                yield from self._iter_filtered_scan(node)
            else:
                for row in self._iter(node.child):
                    if _eval_row(node.predicate, row):
                        yield row
        elif isinstance(node, ProjectNode):
            for row in self._iter(node.child):
                yield {n: _eval_row(e, row) for e, n in node.exprs}
        elif isinstance(node, JoinNode):
            # per-tuple hash join: build dict, probe row by row
            build: dict = {}
            for rrow in self._iter(node.right):
                k = tuple(rrow[c] for c in node.right_keys)
                build.setdefault(k, []).append(rrow)
            for lrow in self._iter(node.left):
                k = tuple(lrow[c] for c in node.left_keys)
                matches = build.get(k, [])
                if node.how == "semi":
                    if matches:
                        yield lrow
                elif node.how == "anti":
                    if not matches:
                        yield lrow
                elif node.how == "left" and not matches:
                    out = dict(lrow)
                    rcols = node.right.output_columns(self.db.catalog)
                    for c in rcols:
                        out.setdefault(c, None)
                    yield out
                else:
                    for rrow in matches:
                        out = dict(lrow)
                        for c, v in rrow.items():
                            out.setdefault(c, v)
                        yield out
        elif isinstance(node, AggregateNode):
            yield from self._iter_aggregate(node)
        elif isinstance(node, OrderByNode):
            rows = list(self._iter(node.child))
            for name, desc in reversed(node.keys):
                rows.sort(key=lambda r: _sort_key(r[name]),
                          reverse=desc)
            if node.limit is not None:
                rows = rows[:node.limit]
            yield from rows
        elif isinstance(node, LimitNode):
            for i, row in enumerate(self._iter(node.child)):
                if i >= node.n:
                    break
                yield row
        else:
            raise TypeError(f"volcano cannot run {type(node).__name__}")


    def _note_delta(self, t) -> None:
        """Merge-on-read visibility: count delta-tail rows the scan had to
        merge (the row baseline pays the same concatenation the columnar
        engine does, so the counter is engine-agnostic)."""
        dr = t.delta_rows
        if dr:
            bm = getattr(self.db, "buffer_manager", None)
            if bm is not None:
                bm.bump(delta_rows=dr)

    def _iter_filtered_scan(self, node: FilterNode) -> Iterator[Row]:
        """Filter directly over a base-table scan: consult the imprints
        (physplan.derive_skip_sets, re-derived here at execution time so
        the bitmap is inherently fresh) and only materialize rows of
        candidate blocks.  Every materialized row still evaluates the full
        predicate, so skipping stays advisory — blocks are dropped only
        when the zone maps prove no row can qualify.  Even the row-store
        baseline honors the paper's §3.1 claim this way."""
        scan = node.child
        from .physplan import derive_skip_sets
        ss = derive_skip_sets(node, self.db).get(id(scan))
        t = self.db.catalog.table(scan.table)
        self._note_delta(t)
        decoded = {n: t.columns[n].to_numpy() for n in t.schema.names}
        names = list(t.schema.names)
        if ss is None or not ss.n_skipped:
            ranges = [(0, t.num_rows)]
        else:
            ranges = ss.candidate_ranges()
            bm = getattr(self.db, "buffer_manager", None)
            if bm is not None:
                skipped_rows = t.num_rows - sum(e - s for s, e in ranges)
                row_width = sum(decoded[n].dtype.itemsize for n in names)
                bm.bump(blocks_skipped=ss.n_skipped,
                        bytes_skipped_spill=skipped_rows * row_width)
        for s, e in ranges:
            for i in range(s, e):
                row = {n: _denull(decoded[n][i]) for n in names}
                if _eval_row(node.predicate, row):
                    yield row

    # -- aggregation (in-memory + spooled out-of-core variants) --------------
    def _iter_aggregate(self, node: AggregateNode) -> Iterator[Row]:
        keyf = lambda row: tuple(row[c] for c in node.group_by)
        est = self._spool_estimate(node)
        if est is not None:
            # grace-style row grouping: rows spool to hash partitions on
            # disk; each group aggregates and frees before the next loads.
            # The fan-out follows the input estimate + budget, so a huge
            # input gets enough partitions for each to fit the budget.
            from .spill import spooled_row_groups
            bm = self.db.buffer_manager
            results = [(k, _agg_group(node, k, rows)) for k, rows in
                       spooled_row_groups(self._iter(node.child), keyf, bm,
                                          est_bytes=est)]
            bm.bump(spilled_ops=1)
        else:
            groups: dict[tuple, list[Row]] = {}
            for row in self._iter(node.child):
                groups.setdefault(keyf(row), []).append(row)
            results = [(k, _agg_group(node, k, rows))
                       for k, rows in groups.items()]
        if not results and not node.group_by:
            results = [((), _agg_group(node, (), []))]
        for _, out in sorted(results, key=lambda kv: tuple(
                (v is None, v) for v in kv[0])):
            yield out

    def _spool_estimate(self, node: AggregateNode) -> Optional[int]:
        """Input-size estimate when the aggregate should spool, else None
        (one plan walk decides *and* sizes the partition fan-out).  The
        estimate — including the decoded-VARCHAR surcharge volcano rows
        incur — lives in the unified tier policy (physplan.TierPolicy);
        this interpreter just consumes the decision."""
        return self.policy.row_spool_estimate(node, self.db.catalog)


def _agg_group(node: AggregateNode, k: tuple, rows: list[Row]) -> Row:
    out = dict(zip(node.group_by, k))
    for spec in node.aggs:
        out[spec.name] = _agg_rows(spec, rows)
    return out


def _sort_key(v):
    return (v is None or (isinstance(v, float) and np.isnan(v)), v)


def _denull(v):
    if isinstance(v, float) and np.isnan(v):
        return None
    return v


def _agg_rows(spec, rows: list[Row]):
    if spec.fn == "count" and spec.expr is None:
        return len(rows)
    vals = [_eval_row(spec.expr, r) for r in rows]
    vals = [v for v in vals
            if v is not None and not (isinstance(v, float) and np.isnan(v))]
    if spec.fn == "count":
        return len(vals)
    if spec.fn == "count_distinct":
        return len(set(vals))
    if not vals:
        return None
    if spec.fn == "sum":
        return sum(vals)
    if spec.fn == "avg":
        return sum(vals) / len(vals)
    if spec.fn == "min":
        return min(vals)
    if spec.fn == "max":
        return max(vals)
    if spec.fn == "median":
        s = sorted(vals)
        m = len(s)
        return 0.5 * (s[(m - 1) // 2] + s[m // 2])
    if spec.fn == "first":
        return vals[0]
    if spec.fn in ("var", "std"):
        mu = sum(vals) / len(vals)
        var = sum((v - mu) ** 2 for v in vals) / len(vals)
        return var ** 0.5 if spec.fn == "std" else var
    raise ValueError(spec.fn)
