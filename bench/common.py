"""Arithmetic shared by the query references and their comparisons.

The references are plain numpy over the generated arrays; ``dtype`` is the
floating type they compute in (float64, as the configurations state, or a
lower one for the control that shows a comparison can fail)."""

from __future__ import annotations

import numpy as np

# relative error reported when two answers cannot be lined up at all
# (different row counts or keys): as far off as an answer can be
NOT_COMPARABLE = 1.0


def small_int(gid: np.ndarray) -> np.ndarray:
    """Group ids in the narrowest signed type, so a stable sort is a radix
    sort."""
    top = int(gid.max()) if gid.size else 0
    for t in (np.int8, np.int16, np.int32):
        if top <= np.iinfo(t).max:
            return gid.astype(t)
    return gid.astype(np.int64)


def group_sums(gid: np.ndarray, values, dtype):
    """``(keys, counts, [sums])``: rows grouped by ``gid``, each group's
    values summed pairwise in ``dtype``; keys ascending."""
    gid = small_int(gid)
    order = np.argsort(gid, kind="stable")
    g = gid[order]
    if g.size == 0:
        return g.astype(np.int64), np.zeros(0, np.int64), \
            [np.zeros(0, dtype) for _ in values]
    starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
    ends = np.r_[starts[1:], g.size]
    sums = []
    for v in values:
        vs = np.asarray(v, dtype=dtype)[order]
        sums.append(np.array([vs[s:e].sum(dtype=dtype)
                              for s, e in zip(starts, ends)], dtype=dtype))
    return g[starts].astype(np.int64), (ends - starts).astype(np.int64), sums


def decimal(col, dtype) -> np.ndarray:
    """A DECIMAL column's values in ``dtype``."""
    return col.data.astype(dtype) / dtype(10 ** col.scale)


def rel_err(got, want) -> float:
    """Largest relative gap of ``got`` from ``want`` (0 for empty)."""
    g = np.asarray(got, dtype=np.float64)
    w = np.asarray(want, dtype=np.float64)
    if g.shape != w.shape:
        return NOT_COMPARABLE
    if g.size == 0:
        return 0.0
    gap = np.abs(g - w) / np.maximum(np.abs(w), np.finfo(np.float64).tiny)
    gap = np.where(np.isnan(g) & np.isnan(w), 0.0, gap)
    gap = np.where(np.isnan(gap), NOT_COMPARABLE, gap)
    return float(gap.max())


def mismatches(got, want) -> int:
    """Cells of an exact column that differ, or every cell when the
    lengths differ."""
    g = np.asarray(got, dtype=object)
    w = np.asarray(want, dtype=object)
    if g.shape != w.shape:
        return max(g.size, w.size, 1)
    return int(sum(a != b for a, b in zip(g.tolist(), w.tolist())))


def compare_rows(got: dict, want: dict, exact, floats, name: str) -> dict:
    """The numbers one answer is judged by: exact columns must agree cell
    for cell, float columns by their largest relative gap."""
    missing = [c for c in list(exact) + list(floats) if c not in got]
    if missing:
        return {f"{name}_mismatch": len(missing),
                f"{name}_rel_err": NOT_COMPARABLE}
    bad = sum(mismatches(got[c], want[c]) for c in exact)
    err = max((rel_err(got[c], want[c]) for c in floats), default=0.0)
    return {f"{name}_mismatch": bad, f"{name}_rel_err": err}


def column_bytes(data: dict, columns: dict) -> int:
    """Logical bytes a query must read: rows x storage width of every
    column it references."""
    return int(sum(data[t][c].data.nbytes
                   for t, cols in columns.items() for c in cols))
