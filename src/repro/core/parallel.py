"""Chunked parallel / distributed query execution (paper Fig. 2 -> SPMD).

MonetDB parallelizes by splitting the largest table into chunks, running
"parallelizable" MAL operators per chunk, and merging before "blocking"
operators.  The TPU-native restatement (DESIGN.md §3): row-shard the base
columns over the mesh's ``data`` axis with ``shard_map``; the mappable span
(select masks, scalar expressions, partial aggregates) runs per shard; the
merge is a collective (psum / pmin / pmax) — exactly the chunk-merge tree of
Fig. 2 with the merge node lowered to an all-reduce.

Two execution tiers:

* ``DistributedScanAgg`` — the device tier for the hot OLAP pattern
  Aggregate(Filter*(Scan)) with dense group domains: it streams
  morsel-sized column batches through the HBM-budgeted block cache
  (``device_cache.DeviceBufferManager``) and merges per-batch raw partials
  with an order-fixed carry, so the query runs on devices whose memory is
  smaller than the table.  The batch decomposition is *independent of the
  device budget* — unbudgeted, generous and tight budgets all execute the
  identical sequence of jitted batch steps, so results are bit-identical
  across budgets and only the transfer/caching behaviour differs
  (resident: blocks stay cached across queries; streamed: LRU eviction
  recycles them, double-buffered prefetch overlaps the next batch's
  host→device copy with the current batch's compute).
* ``ParallelExecutor`` — Executor subclass that consumes the unified
  physical plan (``physplan.plan_physical``): a scan-agg core annotated
  device-resident/device-streamed runs through ``DistributedScanAgg``, a
  host-side suffix (ORDER BY / LIMIT / projection / HAVING) executes over
  the assembled aggregate, and everything else goes to the (host)
  sequential program.  ``physplan.choose_device_tier`` decides
  streamed-device vs resident-device vs host-spill from the byte
  estimates, biased by the device cache's hit history.

``build_query_step``/``make_fragment`` (the single-shot whole-table
fragment) remain for the multi-pod dry-run, which lowers the engine on the
production mesh.

Chunking heuristics follow the paper: the shard count comes from the mesh
("cores"), and small tables are not split at all (`MIN_ROWS_TO_SHARD`).
"""

from __future__ import annotations

import math
import threading
from typing import Optional

import numpy as np

from .device_cache import (DeviceBlockKeys, DeviceBudgetError,
                           DeviceBufferManager, jax_runtime)

# Analytical correctness needs 64-bit aggregation (the paper's engine sums
# DECIMALs exactly); jax_runtime also places the persistent compile cache.
jax = jax_runtime()

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from .executor import (ExecStats, Executor, _res_nulls,  # noqa: E402
                       compile_plan)
from .expression import EvalContext, Expr, ExprResult  # noqa: E402
from .physplan import (AGG_RESULT_NAME, DeviceBuild,  # noqa: E402
                       JoinAggSpec, PhysicalPlan, ScanAggSpec,
                       TIER_DEVICE_RESIDENT, choose_device_join_tier,
                       choose_device_tier, default_mesh, join_agg_geometry,
                       match_scan_agg,  # noqa: F401  (re-exported for tests)
                       mesh_shards, partial_layout, scan_agg_geometry)
from .relalg import PlanNode
from .tracing import span
from .types import DBType, NULL_SENTINEL

# The scan-agg pattern matcher, the partial-matrix layout, the batch
# geometry and the tier-placement policy all live in physplan.py (the
# unified physical planner); this module executes what the planner
# decided.  ``match_scan_agg`` / ``ScanAggSpec`` / ``partial_layout`` are
# re-exported above for existing importers.


# ---------------------------------------------------------------------------
# the shard_map fragment
# ---------------------------------------------------------------------------


def _eval_jnp(expr: Expr, arrays: dict, meta: dict) -> ExprResult:
    ctx = EvalContext(arrays, meta, xp=jnp)
    return expr.eval(ctx)


def _fragment_mask_gid(spec: ScanAggSpec, meta: dict, valid, arrays):
    """Shared SPMD prologue: the filter mask and the dense mixed-radix gid.
    One definition serves both the single-shot fragment and the batched
    raw-partial fragment — any fix to NULL masking or domain decoding
    lands in both, preserving their bit-identity."""
    mask = valid
    for conj in spec.conjuncts:
        r = _eval_jnp(conj, arrays, meta)
        m = r.values != 0
        if r.null is not None:
            m = m & ~r.null
        mask = mask & m
    if spec.group_keys:
        gid = jnp.zeros(valid.shape, dtype=jnp.int32)
        for k, (off, card) in zip(spec.group_keys, spec.key_domains):
            t, heap, scale = meta[k]
            kv = arrays[k]
            code = (kv.astype(jnp.float64) - off).astype(jnp.int32) \
                if t not in (DBType.VARCHAR,) else kv.astype(jnp.int32)
            code = jnp.clip(code, 0, card - 1)
            gid = gid * card + code
    else:
        gid = jnp.zeros(valid.shape, dtype=jnp.int32)
    return mask, gid


# A segment reduction into at most this many groups runs densely (see
# ``_segment_reduce``); above it, as XLA's scatter.  On one TPU v5e, per
# 65,536-row batch, the dense form takes about 0.38 us per group and lane
# and the scatter 4 to 7 ms whatever the groups: 15 lanes (Q1's) cross
# over near 880 groups (scripts/segment_reduce_crossover.py, PERF.md §6).
DENSE_REDUCE_MAX_GROUPS = 512

# op -> (dense reduction over rows, scatter fallback, empty-group identity)
_SEGMENT_OPS = {
    "sum": (jnp.sum, jax.ops.segment_sum, 0.0),
    "min": (jnp.min, jax.ops.segment_min, np.inf),
    "max": (jnp.max, jax.ops.segment_max, -np.inf),
}


def _dense_reduce(n_groups: int) -> bool:
    """Whether a segment reduction into ``n_groups`` groups runs densely."""
    return n_groups <= DENSE_REDUCE_MAX_GROUPS


def _segment_reduce(op: str, values, gid, n_groups: int):
    """Per-group ``op`` ("sum", "min" or "max") of ``values`` (rows,) or
    (rows, K) by ``gid`` in [0, n_groups): (n_groups,) or (n_groups, K),
    an empty group holding the identity (0, +inf, -inf).

    XLA runs a scatter whose indices collide as a serial loop over the
    rows.  For a small group domain the dense form is one fused reduce:
    every row is compared with every group id, the values masked with the
    identity where they differ, and the result reduced over the rows."""
    reduce, scatter, identity = _SEGMENT_OPS[op]
    if not _dense_reduce(n_groups):
        return scatter(values, gid, num_segments=n_groups)
    if n_groups == 1:
        return reduce(values, axis=0, keepdims=True)
    hit = gid[:, None] == jnp.arange(n_groups, dtype=gid.dtype)
    if values.ndim == 2:
        hit, values = hit[:, :, None], values[:, None, :]
    else:
        values = values[:, None]
    return reduce(jnp.where(hit, values, identity), axis=0)


def _fragment_partials(spec: ScanAggSpec, meta: dict, mask, gid, arrays,
                       data_axis):
    """Shared SPMD core: evaluate every aggregate expression once, stack
    the sum-like columns in ``partial_layout`` order into ONE segment sum
    + ONE psum (paper Fig. 2 per-chunk work, MAL-fused), and merge each
    min/max via its own segment reduction + collective.  Returns (seg,
    extras) — mergeable raw partials, not yet finalized."""
    layout = partial_layout(spec)
    sum_cols = [mask.astype(jnp.float64)]            # cnt_star
    evals = {}
    for i, a in enumerate(spec.aggs):
        if a.expr is None:
            continue
        r = _eval_jnp(a.expr, arrays, meta)
        ok = mask if r.null is None else (mask & ~r.null)
        f = r.as_float(jnp)
        evals[i] = (ok, f)
        sum_cols.append(ok.astype(jnp.float64))      # per-agg count
        if a.fn in ("sum", "avg"):
            sum_cols.append(jnp.where(ok, f, 0.0))
    stacked = jnp.stack(sum_cols, axis=1)            # (rows, n_sum)
    seg = _segment_reduce("sum", stacked, gid, spec.n_groups)
    seg = jax.lax.psum(seg, data_axis)               # one collective
    big = jnp.float64(np.inf)
    extras = {}
    for i, fn, _cnt, out_col in layout.minmax:
        ok, f = evals[i]
        if fn == "min":
            v = jnp.where(ok, f, big)
            s = jax.lax.pmin(_segment_reduce(
                "min", v, gid, spec.n_groups), data_axis)
        else:
            v = jnp.where(ok, f, -big)
            s = jax.lax.pmax(_segment_reduce(
                "max", v, gid, spec.n_groups), data_axis)
        extras[out_col] = s
    return seg, extras


def _join_edge_mask(arrays, meta: dict, mask, edge_cols, domains, btabs):
    """Shared probe-side join gating: for each equi-join edge, exclude rows
    whose local key is NULL, outside the build's dense domain, or absent
    from the build table (presence lane 0 == 0).  The domain comparison
    runs in float64 *before* the int32 narrowing — an out-of-domain key
    must never alias a clipped in-domain code."""
    for cname, (off, card), btab in zip(edge_cols, domains, btabs):
        kv = arrays[cname]
        sent = NULL_SENTINEL[meta[cname][0]]
        codef = kv.astype(jnp.float64) - off
        ok = (kv != sent) & (codef >= 0) & (codef < card)
        code = jnp.clip(codef, 0, card - 1).astype(jnp.int32)
        mask = mask & ok & (btab[code, 0] > 0)
    return mask


def make_fragment(spec: ScanAggSpec, meta: dict, data_axis: str = "data"):
    """Build the per-shard SPMD function (traced under shard_map).

    arrays: {col: (rows_local,)} storage-repr jnp arrays; ``valid``:
    (rows_local,) bool marking real (non-padding) rows.  Returns
    (n_groups, n_aggs+1) merged + finalized results: per agg, the ratio /
    NULL masking already applied (single-shot whole-input execution).
    """
    layout = partial_layout(spec)

    def fragment(valid, **arrays):
        mask, gid = _fragment_mask_gid(spec, meta, valid, arrays)
        seg, extras = _fragment_partials(spec, meta, mask, gid, arrays,
                                         data_axis)
        cnt_star = seg[:, 0]
        outs = {}
        for i, kind, cnt_idx, val_idx in layout.plans:
            if kind == "count_star":
                outs[i] = cnt_star
            elif kind == "count":
                outs[i] = seg[:, cnt_idx]
            else:
                cnt = seg[:, cnt_idx]
                v = seg[:, val_idx]
                outs[i] = jnp.where(
                    cnt > 0,
                    v if kind == "sum" else v / jnp.maximum(cnt, 1.0),
                    jnp.nan)
        for i, _fn, cnt_idx, out_col in layout.minmax:
            outs[i] = jnp.where(seg[:, cnt_idx] > 0, extras[out_col],
                                jnp.nan)
        cols = [outs[i] for i in range(len(spec.aggs))] + [cnt_star]
        return jnp.stack(cols, axis=1)          # (n_groups, n_aggs+1)

    return fragment


def build_query_step(spec: ScanAggSpec, meta: dict, mesh: Mesh,
                     data_axis: str = "data"):
    """jit(shard_map(fragment)) with row-sharded inputs; also used by the
    multi-pod dry-run to lower the engine on the production mesh."""
    axes = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    rowspec = P(axes if len(axes) > 1 else axes[0])

    def merged_axis_fragment(valid, **arrays):
        frag = make_fragment(spec, meta, data_axis=axes)
        return frag(valid, **arrays)

    in_specs = (rowspec,) + tuple(rowspec for _ in spec.columns)
    sm = jax.shard_map(
        lambda valid, *cols: merged_axis_fragment(
            valid, **dict(zip(spec.columns, cols))),
        mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)

    def scan_agg_query(valid, *cols):
        return sm(valid, *cols)

    return jax.jit(scan_agg_query)


_STEP_CACHE: dict = {}
# concurrent queries may race to build the same jitted step; the lock makes
# the check-then-build atomic so one trace is built and shared (tracing the
# same fragment twice is wasted work, and a dict insert during another
# thread's insert is not guaranteed safe across interpreters)
_STEP_CACHE_LOCK = threading.Lock()

# XLA's cross-device collectives rendezvous by (run_id, device set): two
# threads dispatching collective programs at once interleave their
# participants into each other's rendezvous and deadlock (observed on the
# forced-multi-device CPU backend; real accelerators serialize launches on
# a stream anyway).  ONE in-process device dispatch at a time — host-tier
# queries are unaffected and still run concurrently.
_DEVICE_DISPATCH_LOCK = threading.Lock()


def _meta_key(columns, meta: dict) -> tuple:
    """The trace-relevant identity of each referenced column: dtype, scale
    and — for VARCHAR — the heap content fingerprint.  String literal
    codes and heap bounds are baked into jitted traces at Python time
    (expression.py), and an append that introduces a novel string
    re-sorts/renumbers the whole heap, so a step compiled against the old
    heap must not be reused."""
    out = []
    for c in columns:
        t, heap, scale = meta[c]
        out.append((c, t, scale,
                    heap.fingerprint() if heap is not None else None))
    return tuple(out)


def _cached_program(key: tuple, build, stats):
    """The step cache's entry under ``key`` (one jitted program or a
    tuple of them), made by ``build()`` on a miss.  Repeated queries reuse
    the jitted programs instead of re-tracing per call.  A program built
    here compiles at its first call: the query that built it counts the
    miss (``ExecStats.programs_built``) and makes that call in a compile
    span."""
    with _STEP_CACHE_LOCK:
        progs = _STEP_CACHE.get(key)
        built = progs is None
        if built:
            progs = _STEP_CACHE[key] = build()
    if not built or stats is None:
        return progs
    stats.programs_built += 1
    if isinstance(progs, tuple):
        return tuple(_compile_span(p, stats) for p in progs)
    return _compile_span(progs, stats)


def _compile_span(fn, stats):
    """``fn`` whose first call, the one that traces and compiles it, runs
    in a compile span."""
    compiled = False

    def call(*args):
        nonlocal compiled
        if compiled:
            return fn(*args)
        compiled = True
        with span("compile", stats):
            return fn(*args)

    return call


# ---------------------------------------------------------------------------
# batched device-tier execution: raw partials + order-fixed carry
# (PartialLayout / partial_layout live in physplan.py — the layout of the
# partial matrix is physical-plan metadata the geometry estimates need)
# ---------------------------------------------------------------------------


def make_partial_fragment(spec: ScanAggSpec, meta: dict,
                          data_axis="data"):
    """Per-shard SPMD function returning *mergeable* raw partials
    (n_groups, K) in ``partial_layout`` order — the streaming analogue of
    ``make_fragment``: the identical shared prologue/core, minus the
    finalization (which ``finalize_partials`` applies once after the
    carry has merged every batch)."""
    layout = partial_layout(spec)

    def fragment(valid, **arrays):
        mask, gid = _fragment_mask_gid(spec, meta, valid, arrays)
        seg, extras = _fragment_partials(spec, meta, mask, gid, arrays,
                                         data_axis)
        if not extras:
            return seg
        cols = [extras[c][:, None] for c in sorted(extras)]
        return jnp.concatenate([seg] + cols, axis=1)

    return fragment


def finalize_partials(spec: ScanAggSpec, partial: np.ndarray) -> np.ndarray:
    """Merged raw partials -> the (n_groups, n_aggs + 1) matrix
    ``_assemble`` consumes (same formulas the single-shot fragment applies
    on device: avg ratios, NULL where a group saw no valid rows)."""
    layout = partial_layout(spec)
    cnt_star = partial[:, 0]
    outs = {}
    for i, kind, cnt_col, val_col in layout.plans:
        if kind == "count_star":
            outs[i] = cnt_star
        elif kind == "count":
            outs[i] = partial[:, cnt_col]
        else:
            cnt = partial[:, cnt_col]
            v = partial[:, val_col]
            outs[i] = np.where(
                cnt > 0,
                v if kind == "sum" else v / np.maximum(cnt, 1.0),
                np.nan)
    for i, _fn, cnt_col, out_col in layout.minmax:
        outs[i] = np.where(partial[:, cnt_col] > 0, partial[:, out_col],
                           np.nan)
    cols = [outs[i] for i in range(len(spec.aggs))] + [cnt_star]
    return np.stack(cols, axis=1)


def _mesh_axes(mesh: Mesh):
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def _gather_expand(gather, inv, valid, cols):
    """Reconstruct a shard's full batch rows from its gathered (compact)
    blocks.  ``inv`` maps each of the shard's ``L`` skip-slots to its
    position among the ``q`` uploaded candidate slots (-1 = not uploaded).
    Filler rows get ``valid = False``, which is exactly the state the full
    upload's rows would reach after masking: zone-map soundness guarantees
    a non-candidate slot's rows all fail some conjunct, and a masked row
    contributes the combine identity (+0.0 / +inf / -inf) no matter what
    its column values are — so the gathered and full paths produce
    bit-identical partials."""
    q, ublock, n_slots = gather

    def expand(comp, fill):
        cb = comp.reshape(q, ublock)
        idx = jnp.clip(inv, 0, q - 1)
        rows = jnp.where((inv >= 0)[:, None], cb[idx],
                         jnp.asarray(fill, dtype=comp.dtype))
        return rows.reshape(n_slots * ublock)

    return expand(valid, False), [expand(c, 0) for c in cols]


def build_batch_step(spec: ScanAggSpec, meta: dict, mesh: Mesh,
                     gather=None):
    """(init_fn, step_fn): ``step(carry, valid, *cols) -> carry'`` — one
    jitted fused unit per batch: the shard_map partial fragment plus the
    carry combine (add / min / max per column).  The carry is replicated
    over the mesh; ``init_fn`` materializes the combine identity on device
    (no host→device transfer beyond the compiled constant).  With
    ``gather`` (intra-batch skipping) the step instead takes
    ``step(carry, inv, valid_compact, *cols_compact)`` and reconstructs
    the full batch rows on device before the fragment runs."""
    axes = _mesh_axes(mesh)
    rowspec = P(axes if len(axes) > 1 else axes[0])
    layout = partial_layout(spec)
    frag = make_partial_fragment(spec, meta, data_axis=axes)
    if gather is None:
        def shard_fn(valid, *cols):
            return frag(valid, **dict(zip(spec.columns, cols)))
        n_in = 1 + len(spec.columns)
    else:
        def shard_fn(inv, valid, *cols):
            v, full = _gather_expand(gather, inv, valid, cols)
            return frag(v, **dict(zip(spec.columns, full)))
        n_in = 2 + len(spec.columns)
    sm = jax.shard_map(shard_fn, mesh=mesh, in_specs=(rowspec,) * n_in,
                       out_specs=P(), check_vma=False)
    kinds = layout.kinds

    def scan_agg_step(carry, *args):
        part = sm(*args)
        return jnp.where(kinds == 0, carry + part,
                         jnp.where(kinds == 1, jnp.minimum(carry, part),
                                   jnp.maximum(carry, part)))

    g, k = spec.n_groups, len(kinds)

    def scan_agg_init():
        return jnp.broadcast_to(jnp.asarray(layout.init), (g, k)) \
            + jnp.float64(0.0)

    rep_sh = NamedSharding(mesh, P())
    return (jax.jit(scan_agg_init, out_shardings=rep_sh),
            jax.jit(scan_agg_step, out_shardings=rep_sh))


def _cached_batch_step(spec: ScanAggSpec, meta: dict, mesh: Mesh,
                       batch_rows: int, gather=None, stats=None):
    key = ("batch", spec.table, repr(spec.conjuncts),
           tuple(spec.group_keys),
           tuple(spec.key_domains),     # baked into the trace as constants:
                                        # a shifted key domain (delete/append
                                        # moving min/max at equal cardinality)
                                        # must not reuse the stale step
           tuple((a.fn, repr(a.expr)) for a in spec.aggs),
           _meta_key(spec.columns, meta),
           spec.n_groups, batch_rows, gather,
           id(mesh.devices.flat[0]),
           tuple(mesh.shape.items()))
    return _cached_program(
        key, lambda: build_batch_step(spec, meta, mesh, gather=gather),
        stats)


# ---------------------------------------------------------------------------
# device join tier: radix build / probe / device-resident assembly steps
# ---------------------------------------------------------------------------


def build_join_build_step(build: DeviceBuild, meta: dict, mesh: Mesh,
                          child_domains, gather=None):
    """(init_fn, step_fn) for one join build table:
    ``step(btab, *child_btabs, valid, *cols) -> btab'``.

    One batch of the build table's stream is filtered (its own conjuncts +
    NULL/domain/presence gating against already-built child tables) and
    scatter-added into the (card, 1 + n_payload) build matrix: lane 0
    counts presence (the runtime uniqueness witness — any slot > 1 means
    duplicate build keys and the query falls back to the host join), the
    payload lanes hold the build's group-key columns as float64 (unique
    keys make the add a set; the integer-coded payload types decode
    exactly).  All-add combine: the same carry idiom as the scan-agg tier,
    so dirty-writeback/eviction compose unchanged."""
    axes = _mesh_axes(mesh)
    rowspec = P(axes if len(axes) > 1 else axes[0])
    off, card = build.domain
    width = 1 + len(build.payload)
    n_children = len(build.probe_edges)
    edge_cols = [c for _, c in build.probe_edges]

    def fragment(child_btabs, valid, *cols):
        arrays = dict(zip(build.columns, cols))
        mask = valid
        for conj in build.conjuncts:
            r = _eval_jnp(conj, arrays, meta)
            m = r.values != 0
            if r.null is not None:
                m = m & ~r.null
            mask = mask & m
        kv = arrays[build.key]
        sent = NULL_SENTINEL[meta[build.key][0]]
        codef = kv.astype(jnp.float64) - off
        mask = mask & (kv != sent) & (codef >= 0) & (codef < card)
        code = jnp.clip(codef, 0, card - 1).astype(jnp.int32)
        mask = _join_edge_mask(arrays, meta, mask, edge_cols,
                               child_domains, child_btabs)
        lanes = [mask.astype(jnp.float64)]
        for p in build.payload:
            lanes.append(jnp.where(mask, arrays[p].astype(jnp.float64),
                                   0.0))
        stacked = jnp.stack(lanes, axis=1)
        seg = jax.ops.segment_sum(stacked, code, num_segments=card)
        return jax.lax.psum(seg, axes)

    if gather is None:
        def shard_fn(*args):
            return fragment(args[:n_children], args[n_children],
                            *args[n_children + 1:])
        n_rows_in = 1 + len(build.columns)
    else:
        def shard_fn(*args):
            inv = args[n_children]
            v, full = _gather_expand(gather, inv, args[n_children + 1],
                                     args[n_children + 2:])
            return fragment(args[:n_children], v, *full)
        n_rows_in = 2 + len(build.columns)
    sm = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(),) * n_children + (rowspec,) * n_rows_in,
        out_specs=P(), check_vma=False)

    def join_build_step(btab, *args):
        return btab + sm(*args)

    def join_build_init():
        return jnp.zeros((card, width), dtype=jnp.float64) + jnp.float64(0.0)

    rep_sh = NamedSharding(mesh, P())
    return (jax.jit(join_build_init, out_shardings=rep_sh),
            jax.jit(join_build_step, out_shardings=rep_sh))


def _cached_join_build_step(build: DeviceBuild, meta: dict, mesh: Mesh,
                            batch_rows: int, child_domains, gather=None,
                            stats=None):
    key = ("jbuild", build.table, repr(build.conjuncts), build.key,
           build.domain, tuple(build.payload), tuple(build.probe_edges),
           tuple(child_domains), _meta_key(build.columns, meta),
           batch_rows, gather, id(mesh.devices.flat[0]),
           tuple(mesh.shape.items()))
    return _cached_program(
        key, lambda: build_join_build_step(build, meta, mesh, child_domains,
                                           gather=gather),
        stats)


def build_join_probe_step(spec: JoinAggSpec, meta: dict, mesh: Mesh,
                          gather=None):
    """(init_fn, step_fn) for the probe (fact) side of a device join:
    ``step(carry, *edge_btabs, valid, *cols) -> carry'``.

    The probe phase IS the scan-agg batch step over the probe table —
    identical prologue, partials and carry combine — plus presence gating
    through every probe-adjacent build matrix.  The gid is the group
    build's key code; rows with NULL / out-of-domain / unmatched keys are
    masked and contribute the combine identity."""
    pspec = spec.probe_spec()
    axes = _mesh_axes(mesh)
    rowspec = P(axes if len(axes) > 1 else axes[0])
    layout = partial_layout(pspec)
    domains = [spec.builds[bi].domain for bi, _ in spec.probe_edges]
    edge_cols = [c for _, c in spec.probe_edges]
    n_children = len(spec.probe_edges)

    def fragment(edge_btabs, valid, *cols):
        arrays = dict(zip(pspec.columns, cols))
        mask, gid = _fragment_mask_gid(pspec, meta, valid, arrays)
        mask = _join_edge_mask(arrays, meta, mask, edge_cols, domains,
                               edge_btabs)
        seg, extras = _fragment_partials(pspec, meta, mask, gid, arrays,
                                         axes)
        if not extras:
            return seg
        ecols = [extras[c][:, None] for c in sorted(extras)]
        return jnp.concatenate([seg] + ecols, axis=1)

    if gather is None:
        def shard_fn(*args):
            return fragment(args[:n_children], args[n_children],
                            *args[n_children + 1:])
        n_rows_in = 1 + len(pspec.columns)
    else:
        def shard_fn(*args):
            inv = args[n_children]
            v, full = _gather_expand(gather, inv, args[n_children + 1],
                                     args[n_children + 2:])
            return fragment(args[:n_children], v, *full)
        n_rows_in = 2 + len(pspec.columns)
    sm = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(),) * n_children + (rowspec,) * n_rows_in,
        out_specs=P(), check_vma=False)
    kinds = layout.kinds

    def join_probe_step(carry, *args):
        part = sm(*args)
        return jnp.where(kinds == 0, carry + part,
                         jnp.where(kinds == 1, jnp.minimum(carry, part),
                                   jnp.maximum(carry, part)))

    g, k = pspec.n_groups, len(kinds)

    def join_probe_init():
        return jnp.broadcast_to(jnp.asarray(layout.init), (g, k)) \
            + jnp.float64(0.0)

    rep_sh = NamedSharding(mesh, P())
    return (jax.jit(join_probe_init, out_shardings=rep_sh),
            jax.jit(join_probe_step, out_shardings=rep_sh))


def _cached_join_probe_step(spec: JoinAggSpec, meta: dict, mesh: Mesh,
                            batch_rows: int, gather=None, stats=None):
    pspec = spec.probe_spec()
    key = ("jprobe", spec.probe_table, repr(pspec.conjuncts),
           tuple(pspec.group_keys), tuple(pspec.key_domains),
           tuple((a.fn, repr(a.expr)) for a in pspec.aggs),
           tuple(spec.probe_edges),
           tuple(b.domain for b in spec.builds),
           _meta_key(pspec.columns, meta), pspec.n_groups,
           batch_rows, gather, id(mesh.devices.flat[0]),
           tuple(mesh.shape.items()))
    return _cached_program(
        key, lambda: build_join_probe_step(spec, meta, mesh, gather=gather),
        stats)


def build_scalar_step(kind: str):
    """Tiny jitted reducers dispatched on device-resident state:
    ``"present"`` counts non-empty groups of a carry (the dispatch key for
    the exact-size compaction trace); ``"dupmax"`` is the max presence
    count of a build matrix — the uniqueness verification the device join
    tier's soundness rests on."""
    def scalar_present(m):
        return jnp.sum(m[:, 0] > 0)

    def scalar_dupmax(m):
        return jnp.max(m[:, 0])

    return jax.jit(scalar_present if kind == "present" else scalar_dupmax)


def _cached_scalar_step(kind: str, stats=None):
    return _cached_program(("scalar", kind),
                           lambda: build_scalar_step(kind), stats)


def _finalize_rows_jnp(spec: ScanAggSpec, carry):
    """Traced mirror of ``finalize_partials`` — identical formulas, jnp
    ops — used by the device-resident assembly step so huge-group-domain
    partial matrices are finalized and compacted in HBM without ever
    materializing (n_groups, K) on the host."""
    layout = partial_layout(spec)
    cnt_star = carry[:, 0]
    outs = {}
    for i, kind, cnt_col, val_col in layout.plans:
        if kind == "count_star":
            outs[i] = cnt_star
        elif kind == "count":
            outs[i] = carry[:, cnt_col]
        else:
            cnt = carry[:, cnt_col]
            v = carry[:, val_col]
            outs[i] = jnp.where(
                cnt > 0,
                v if kind == "sum" else v / jnp.maximum(cnt, 1.0),
                jnp.nan)
    for i, _fn, cnt_col, out_col in layout.minmax:
        outs[i] = jnp.where(carry[:, cnt_col] > 0, carry[:, out_col],
                            jnp.nan)
    cols = [outs[i] for i in range(len(spec.aggs))] + [cnt_star]
    return jnp.stack(cols, axis=1)


def _device_sort_key(v, dbt, scale: int, desc: bool):
    """Traced mirror of ``executor._sort_key_float`` over a float64 copy
    of an assembled output column — identical arithmetic, so the lexsort
    permutation is identical to the host suffix sort's."""
    v = v.astype(jnp.float64)
    if dbt == DBType.VARCHAR:
        k, nulls = v, v == 0
    elif dbt == DBType.DECIMAL:
        k = v / (10 ** scale)
        nulls = v == NULL_SENTINEL[dbt]
    elif dbt in (DBType.FLOAT64, DBType.FLOAT32):
        k, nulls = v, jnp.isnan(v)
    else:
        k, nulls = v, v == NULL_SENTINEL[dbt]
    return jnp.where(nulls, jnp.inf, -k if desc else k)


def build_assemble_step(spec: ScanAggSpec, n_present: int, sort_cols,
                        limit, n_payload: int):
    """Device-resident assembly: finalize the carry, compact it to the
    ``n_present`` non-empty groups, gather the group build's payload lanes
    and — when an ORDER BY suffix was fused — compute the float sort keys
    and the (top-``limit``) lexsort permutation, all in HBM.  Only the
    compacted (and sorted) arrays are fetched to host.

    ``sort_cols`` is a tuple of ``(source, dbtype, scale, desc)`` where
    ``source`` is ``("digit", i)`` (mixed-radix group-key digit — for the
    join tier the single digit IS the build key code), ``("payload", j)``
    (a build payload lane) or ``("agg", i)``.  Returns
    ``(gids, finalized_rows, payload_rows)``."""
    doms = spec.key_domains

    def assemble(carry, btab=None):
        final = _finalize_rows_jnp(spec, carry)
        if spec.group_keys:
            gids = jnp.nonzero(carry[:, 0] > 0, size=n_present,
                               fill_value=0)[0]
        else:
            gids = jnp.zeros(1, dtype=jnp.int64)
        compact = final[gids]
        pay = btab[gids, 1:] if n_payload else \
            jnp.zeros((gids.shape[0], 0), dtype=jnp.float64)
        if sort_cols:
            rem = gids
            digits = []
            for off, card in reversed(doms):
                digits.append(rem % card)
                rem = rem // card
            digits.reverse()
            fkeys = []
            for (src, dbt, scale, desc) in sort_cols:
                if src[0] == "digit":
                    i = src[1]
                    v = digits[i].astype(jnp.float64)
                    if dbt != DBType.VARCHAR:
                        v = v + doms[i][0]
                elif src[0] == "payload":
                    v = pay[:, src[1]]
                else:
                    v = compact[:, src[1]]
                fkeys.append(_device_sort_key(v, dbt, scale, desc))
            perm = jnp.lexsort(tuple(reversed(fkeys)))
            if limit is not None:
                perm = perm[:limit]
            gids, compact, pay = gids[perm], compact[perm], pay[perm]
        return gids, compact, pay

    return jax.jit(assemble)


def _cached_assemble_step(spec: ScanAggSpec, n_present: int, sort_cols,
                          limit, n_payload: int, mesh: Mesh, stats=None):
    key = ("assemble", spec.table, tuple(spec.group_keys),
           tuple(spec.key_domains),
           tuple((a.fn, repr(a.expr)) for a in spec.aggs),
           spec.n_groups, n_present, sort_cols, limit, n_payload,
           id(mesh.devices.flat[0]), tuple(mesh.shape.items()))
    return _cached_program(
        key, lambda: build_assemble_step(spec, n_present, sort_cols, limit,
                                         n_payload),
        stats)


# requires-lock: _DEVICE_DISPATCH_LOCK
def _assemble_on_device(plan: tuple, mesh: Mesh, stats, carry, btab=None):
    """Device-resident assembly dispatch: count the present groups (the
    exact-size key of the compaction trace), run the finalize / compact /
    payload-gather / fused-sort step, fetch only the surviving rows.
    ``plan`` is ``(pspec, sort_cols, limit, n_payload)`` — data, not a
    closure, so the dispatch stays inside the lock-annotated call
    graph.  The count is the first value the host waits for after the
    batch loop: its fetch, like the rows', is a fence span."""
    pspec, sort_cols, limit, n_payload = plan
    with span("assemble", stats):
        present_fn = _cached_scalar_step("present", stats)
        present = present_fn(carry)
        with span("fence", stats):
            n_present = int(present)
        fn = _cached_assemble_step(pspec, n_present, tuple(sort_cols), limit,
                                   n_payload, mesh, stats)
        gids, vals, pay = fn(carry) if btab is None else fn(carry, btab)
        with span("fence", stats):
            return np.asarray(gids), np.asarray(vals), np.asarray(pay)


class _DeviceJoinFallback(Exception):
    """Raised when a runtime precondition of the device join fails
    (duplicate build keys); the executor falls back to the host join."""


class DistributedScanAgg:
    """Streamed device-tier execution of one Aggregate(Filter*(Scan)).

    The table's rows are cut into fixed-size batches (``batch_rows``,
    rounded up to a multiple of the shard count; NOT derived from the
    budget — identical batching across budgets is what makes the budget
    matrix bit-identical).  Each (column, batch) block flows through the
    ``DeviceBufferManager``:

    * resident tier: every block fits the budget at once; after the first
      query all blocks are cache hits and no host→device bytes move;
    * streamed tier: only batches fit; blocks of consumed batches are
      LRU-evicted to make room, and batch N+1's transfers are issued
      (non-blocking ``jax.device_put``) before batch N's compute so copy
      and compute overlap — ``jax`` orders them by data dependency, and
      the final host fetch of the carry is the ``block_until_ready``
      fence.

    The merge carry (a dirty intermediate block) may itself be evicted
    under a tight budget: it is copied back to host and transparently
    re-uploaded — the only writeback case, since base-column blocks are
    clean by definition."""

    def __init__(self, db, spec: ScanAggSpec, mesh: Mesh,
                 batch_rows: Optional[int] = None, skip_set=None,
                 stats: Optional[ExecStats] = None):
        self.db = db
        self.spec = spec
        self.mesh = mesh
        # the query's stats: its spans and counters land here
        self.stats = ExecStats() if stats is None else stats
        self.devman: DeviceBufferManager = getattr(
            db, "device_manager", None) or DeviceBufferManager(
                stats=getattr(db, "buffer_manager", None).stats
                if getattr(db, "buffer_manager", None) else None)
        self.table = db.catalog.table(spec.table)
        self.n_rows = self.table.num_rows
        # transaction snapshots run under a unique key namespace: their
        # tables reuse the version number the next committed write gets,
        # so bare versions would let rolled-back rows alias committed ones
        self._key_ns = getattr(db, "device_key_namespace", 0)
        # delta geometry (base tables report delta_rows == 0): batches that
        # lie fully inside the immutable base are keyed by base_version only
        # and so survive appends; tail-overlapping batches carry the delta
        # epoch and are the only entries an append invalidates
        self.base_rows = self.table.base_rows
        self.delta_rows = self.table.delta_rows
        self.base_version_key = (self._key_ns, "b", self.table.base_version)
        self.delta_version_key = (self._key_ns, "d", self.table.base_version,
                                  self.table.delta_epoch)
        # mesh identity (device ids + axis layout) joins the shard key:
        # blocks are sharded FOR a mesh, and serving a 4-device block to a
        # 2-device step raises inside jit — which the executor would
        # swallow as a host fallback, silently losing the device tier
        self.mesh_key = (tuple(mesh.shape.items()),
                         tuple(d.id for d in mesh.devices.flat))
        # batch decomposition + byte footprint come from the physical
        # planner's shared geometry model — identical numbers whether the
        # tier was chosen through plan_physical or a direct construction
        geom = scan_agg_geometry(spec, self.table, mesh_shards(mesh),
                                 batch_rows)
        self.batch_rows = geom.batch_rows
        self.n_batches = geom.n_batches
        self.row_bytes = geom.row_bytes
        self.carry_nbytes = geom.carry_nbytes
        self.batch_bytes = geom.batch_bytes
        self.resident_bytes = geom.resident_bytes
        # imprint-derived skip-set (physplan.SkipSet): intersected with the
        # batch geometry so non-qualifying batches are never built, never
        # prefetched and never device_put.  Execution-time re-validation:
        # a skip-set derived against another table version (an append or
        # DELETE raced the lowering) is discarded, not half-trusted.
        if skip_set is not None and not skip_set.valid_for(self.table):
            skip_set = None
        self.skip_set = skip_set
        m = self.batch_rows
        self.live_batches = [
            b for b in range(self.n_batches)
            if skip_set is None or skip_set.batch_qualifies(
                b * m, min(self.n_rows, b * m + m))]
        # intra-batch skipping (gather): a *boundary* batch — one the zone
        # maps could not skip whole — usually still contains non-candidate
        # imprint blocks.  Cut each shard's slice into L skip-aligned slots
        # and upload only the candidate slots (padded to q, one gather
        # trace for every gathered batch) plus a tiny (L,)-per-shard int32
        # inverse map; the step reconstructs full rows on device
        # (``_gather_expand``).  Per-batch layout choice: a batch whose
        # every slot qualifies keeps the plain full-batch trace — only
        # batches with actual gaps pay the gather indirection, and only
        # when q < L (the compact upload is strictly smaller).
        self.shards = mesh_shards(mesh)
        self.gather = None
        self._gather_sel: dict = {}
        if skip_set is not None and self.live_batches:
            local = self.batch_rows // self.shards
            ublock = math.gcd(skip_set.block, local)
            L = local // ublock
            if L > 1:
                sels = {}
                maxq = 0
                for b in self.live_batches:
                    s0 = b * self.batch_rows
                    e = min(self.n_rows, s0 + self.batch_rows)
                    per_shard = []
                    batch_max = 0
                    for sdx in range(self.shards):
                        sel = []
                        for slot in range(L):
                            ss = s0 + sdx * local + slot * ublock
                            if ss >= e:       # padding rows: never upload
                                continue
                            if skip_set.batch_qualifies(
                                    ss, min(ss + ublock, e)):
                                sel.append(slot)
                        per_shard.append(tuple(sel))
                        batch_max = max(batch_max, len(sel))
                    if batch_max < L:         # this batch has gaps: gather
                        sels[b] = tuple(per_shard)
                        maxq = max(maxq, batch_max)
                q = 1
                while q < maxq:
                    q *= 2
                if sels and q < L:
                    self.gather = (q, ublock, L)
                    self._gather_sel = sels
        self.meta = {}
        for c in spec.columns:
            col = self.table.column(c)
            self.meta[c] = (col.dbtype, col.heap, col.scale)

    # -- placement decision ---------------------------------------------------
    def choose_tier(self) -> str:
        return choose_device_tier(
            self.resident_bytes, self.batch_bytes, self.devman.budget,
            host_budget=getattr(self.db, "memory_budget", None),
            host_bytes=self.resident_bytes,
            hit_history=self.devman.hit_history(self.spec.table))

    # -- block builders -------------------------------------------------------
    def _builders(self, b: int):
        """Yield (cache key, host-build thunk) for batch ``b``'s blocks:
        the valid mask first, then every referenced column, each padded to
        exactly ``batch_rows`` rows (one trace serves all batches).  The
        shard component of the key is ``(mesh, batch_rows, b)``: a block
        is only reusable by a query slicing the same geometry onto the
        same devices — a different ``device_batch_rows`` cuts different
        row ranges (a bare batch index would serve the wrong rows as a
        cache hit), and a different mesh needs differently-sharded
        placements."""
        spec, table = self.spec, self.table
        m = self.batch_rows
        s = b * m
        e = min(self.n_rows, s + m)
        vkey = self._batch_version_key(b)
        if b in self._gather_sel:
            # gathered (compact) layout: per shard, q candidate slots of
            # ublock rows each, plus the (L,)-per-shard inverse map.  The
            # selection joins the shard key — two queries whose conjuncts
            # pick different candidate slots must not alias blocks.
            q, ublock, L = self.gather
            sel = self._gather_sel[b]
            local = m // self.shards
            shard = (self.mesh_key, m, b, "g", q, sel)

            def slot_span(sdx, slot):
                ss = s + sdx * local + slot * ublock
                return ss, max(0, min(ss + ublock, e) - ss)

            def binv():
                a = np.full(self.shards * L, -1, dtype=np.int32)
                for sdx, ssel in enumerate(sel):
                    for j, slot in enumerate(ssel):
                        a[sdx * L + slot] = j
                return a

            yield (DeviceBlockKeys.column(spec.table, "#ginv", vkey,
                                          shard), binv)

            def bvalid():
                a = np.zeros(self.shards * q * ublock, dtype=bool)
                for sdx, ssel in enumerate(sel):
                    base = sdx * q * ublock
                    for j, slot in enumerate(ssel):
                        _, nv = slot_span(sdx, slot)
                        a[base + j * ublock:base + j * ublock + nv] = True
                return a

            yield DeviceBlockKeys.valid(spec.table, vkey, shard), bvalid
            for c in spec.columns:
                col = table.column(c)

                def bcol(col=col):
                    a = np.zeros(self.shards * q * ublock,
                                 dtype=col.data.dtype)
                    for sdx, ssel in enumerate(sel):
                        base = sdx * q * ublock
                        for j, slot in enumerate(ssel):
                            ss, nv = slot_span(sdx, slot)
                            a[base + j * ublock:base + j * ublock + nv] \
                                = col.data[ss:ss + nv]
                    return a

                yield (DeviceBlockKeys.column(spec.table, c, vkey, shard),
                       bcol)
            return
        shard = (self.mesh_key, m, b)

        def bvalid():
            a = np.zeros(m, dtype=bool)
            a[:e - s] = True
            return a

        yield DeviceBlockKeys.valid(spec.table, vkey, shard), bvalid
        for c in spec.columns:
            col = table.column(c)

            def bcol(col=col):
                a = np.zeros(m, dtype=col.data.dtype)
                a[:e - s] = col.data[s:e]       # memmap: pages one morsel
                return a

            yield (DeviceBlockKeys.column(spec.table, c, vkey, shard),
                   bcol)

    def _batch_version_key(self, b: int):
        """Epoch-keyed caching (delta store): the version component of batch
        ``b``'s block keys.  A batch whose rows lie entirely within the
        immutable base is keyed ``(ns, "b", base_version)`` — stable across
        appends, so a repeat scan after an append re-uploads only the tail.
        A batch overlapping the delta tail is keyed
        ``(ns, "d", base_version, delta_epoch)``; the next append bumps the
        epoch, orphaning exactly those entries (reaped by
        ``DeviceBufferManager.invalidate_delta`` / LRU).  Soundness: a batch
        that ends at the base boundary *before* an append keeps the same
        rows after it (the base is immutable), so serving its "b" entry as a
        hit is correct; a batch that gains rows by an append necessarily
        overlaps the tail and flips to a fresh "d" key — never a stale hit."""
        if self.delta_rows == 0:
            return self.base_version_key
        e = min(self.n_rows, (b + 1) * self.batch_rows)
        if e <= self.base_rows:
            return self.base_version_key
        return self.delta_version_key

    # requires-lock: _DEVICE_DISPATCH_LOCK
    def _issue_prefetch(self, b: int, prefetched: set, query_keys: set,
                        sh) -> None:
        """Start batch ``b``'s host→device copies (non-blocking) so they
        overlap the current batch's compute.  ``put`` recycles the budget
        by evicting *unpinned* (already-consumed) blocks, and the loop
        stops issuing the moment room would require touching a pinned one
        — double-buffering never breaks ``device_bytes_peak <= budget``.
        The batch's builds and copies are one h2d span, opened at the
        first block that is not cached."""
        upload = span("h2d", self.stats)
        try:
            for key, build in self._builders(b):
                if key in self.devman or key in prefetched:
                    continue   # cached: will be a cache hit at consumption
                upload.open()
                try:
                    # single-flight even here: two streamed queries walking
                    # the same table prefetch the same next batch — one
                    # upload, the other attaches (and still takes its pin)
                    self.devman.get_or_put(key, build, sharding=sh,
                                           pin=True)
                except DeviceBudgetError:
                    return
                prefetched.add(key)
                query_keys.add(key)
        finally:
            upload.close()

    def _account_skipping(self) -> None:
        """Bump what the zone maps saved: every block of every whole
        skipped batch would have been padded to batch_rows and uploaded.
        A skipped batch contributes exactly the carry-combine identity
        (+0 / +inf / -inf): not running its step leaves the carry
        bit-identical to running it."""
        live = self.live_batches
        if len(live) >= self.n_batches:
            return
        blk = self.skip_set.block
        live_set = set(live)
        skipped_blocks = 0
        for b in range(self.n_batches):
            if b in live_set:
                continue
            s = b * self.batch_rows
            e = min(self.n_rows, s + self.batch_rows)
            skipped_blocks += -(-(e - s) // blk)
        self.devman.bump(
            blocks_skipped=skipped_blocks,
            bytes_skipped_h2d=(self.n_batches - len(live))
            * self.batch_rows * self.row_bytes)

    # requires-lock: _DEVICE_DISPATCH_LOCK
    def _stream_batches(self, sh, query_keys: set, pinned: set,
                        prefetched: set):
        """Generator driving the live batches through the block cache:
        yields ``(b, arrs, nxt)`` per batch — the batch index (the
        caller picks the gathered or full step trace by membership in
        ``_gather_sel``), the device block handles (pinned), and the NEXT
        live batch index (None on the last batch).  The caller pins its
        own carry state *before* calling ``_issue_prefetch(nxt, ...)``
        (so double-buffering can never evict it), dispatches its step,
        and resumes the generator, which unpins the consumed batch.
        Shared by the scan-agg carry loop and the join tier's
        build/probe streams.  Blocks neither prefetched nor cached are
        built and copied here, in one h2d span per batch."""
        devman = self.devman
        self._account_skipping()
        live = self.live_batches
        for i, b in enumerate(live):
            arrs = []
            batch_keys = []
            upload = span("h2d", self.stats)
            try:
                for key, build in self._builders(b):
                    if key in prefetched:
                        prefetched.discard(key)         # pinned at issue
                        arr = devman.peek(key)
                        devman.bump(device_prefetch_hits=1)
                    else:
                        arr = devman.get(key, pin=True)
                        if arr is None:
                            # single-flight: a concurrent query needing
                            # the same block attaches to one in-flight
                            # upload instead of issuing its own (shared
                            # morsel scans)
                            upload.open()
                            arr = devman.get_or_put(key, build, sharding=sh,
                                                    pin=True)
                    pinned.add(key)
                    query_keys.add(key)
                    batch_keys.append(key)
                    arrs.append(arr)
            finally:
                upload.close()
            if b in self._gather_sel:
                # intra-batch savings, counted at consumption: the full
                # upload would have moved L slots per shard, the gathered
                # one moves q — whether the blocks were cache hits or not
                q, ublock, L = self.gather
                devman.bump(bytes_skipped_h2d=(L - q) * ublock
                            * self.shards * self.row_bytes)

            yield b, arrs, (live[i + 1] if i + 1 < len(live) else None)
            for key in batch_keys:
                devman.unpin(key)
                pinned.discard(key)

    # -- execution ------------------------------------------------------------
    def run(self, tier: Optional[str] = None, assemble=None):
        tier = tier or self.choose_tier()
        if tier == "host":
            raise DeviceBudgetError("input does not fit the device tier")
        # serialize the whole batch loop: every step() carries a psum, and
        # concurrent collective dispatch deadlocks the XLA rendezvous (see
        # _DEVICE_DISPATCH_LOCK).  Cross-query sharing still happens — a
        # later query attaches to this one's cached blocks via get_or_put
        with span("device_lock", self.stats) as wait, _DEVICE_DISPATCH_LOCK:
            self.stats.device_lock_wait_ms += wait.close()
            return self._run_locked(tier, assemble=assemble)

    def _run_locked(self, tier: str, assemble=None):  # requires-lock: _DEVICE_DISPATCH_LOCK
        """Merge every live batch into the carry; then either fetch +
        finalize on host (default) or run the device-resident assembly
        described by the ``assemble`` plan tuple (the carry never reaches
        the host as a full (n_groups, K) matrix on that path)."""
        devman = self.devman
        spec = self.spec
        stats = self.stats
        init_fn, step = _cached_batch_step(spec, self.meta, self.mesh,
                                           self.batch_rows, stats=stats)
        dense = _dense_reduce(spec.n_groups)
        step_g = None
        if self.gather is not None:
            _, step_g = _cached_batch_step(spec, self.meta, self.mesh,
                                           self.batch_rows,
                                           gather=self.gather, stats=stats)
        axes = _mesh_axes(self.mesh)
        sh = NamedSharding(self.mesh, P(axes if len(axes) > 1 else axes[0]))
        rep_sh = NamedSharding(self.mesh, P())
        carry_key = DeviceBlockKeys.carry()
        query_keys: set = {carry_key}
        pinned: set = set()
        prefetched: set = set()
        try:
            with span("loop", stats):
                carry = devman.adopt(carry_key, init_fn(),
                                     nbytes=self.carry_nbytes, dirty=True)
                for b, arrs, nxt in self._stream_batches(
                        sh, query_keys, pinned, prefetched):
                    # the carry is unpinned between batches so a tight
                    # budget may have evicted it (writeback); re-upload
                    # before use
                    if carry_key not in devman:
                        host = devman.take_host(carry_key)
                        carry = devman.put(carry_key, host, sharding=rep_sh,
                                           pin=False, dirty=True)
                    devman.pin(carry_key)
                    if nxt is not None:
                        self._issue_prefetch(nxt, prefetched, query_keys, sh)
                    st = step_g if b in self._gather_sel else step
                    with span("step", stats):
                        carry = st(carry, *arrs)        # async dispatch
                    stats.dense_reduce_steps += dense
                    devman.unpin(carry_key)
                    devman.adopt(carry_key, carry, nbytes=self.carry_nbytes,
                                 dirty=True)
            if assemble is not None:
                return _assemble_on_device(assemble, self.mesh, stats, carry)
            with span("fence", stats):
                out = devman.take_host(carry_key)   # blocks until computed
            with span("assemble", stats):
                return finalize_partials(spec, out)
        finally:
            for key in pinned | prefetched:
                devman.unpin(key)
            devman.drop(carry_key)
            if devman.budget is None:
                # zero-config: no silent device-memory growth across
                # queries — cross-query caching is a budgeted feature
                for key in query_keys:
                    devman.drop(key)


class DistributedJoinAgg:
    """Streamed device-tier execution of one Aggregate(inner-join tree).

    Orchestrates per-table ``DistributedScanAgg`` block streams through the
    shared ``DeviceBufferManager``: build matrices are populated bottom-up
    (each build's batches probe the already-built child matrices, so
    semi-join filtering folds into the build itself), verified unique
    (``dupmax`` — a duplicate build key would double-count and falls back
    to the host join), then the probe table streams through the scan-agg
    carry loop with presence gating against every probe-adjacent matrix.
    Assembly is device-resident: the caller's ``assemble`` plan tuple
    drives ``_assemble_on_device`` — finalize/compact/sort happen in HBM
    and only the surviving rows are fetched; the (n_groups, K) carry and
    the (card, 1+P) group-build matrix never materialize on host."""

    def __init__(self, db, spec: JoinAggSpec, mesh: Mesh,
                 batch_rows: Optional[int] = None, skip_sets=None,
                 stats: Optional[ExecStats] = None):
        self.db = db
        self.spec = spec
        self.mesh = mesh
        self.stats = ExecStats() if stats is None else stats
        skip_sets = skip_sets or {}
        self.pspec = spec.probe_spec()
        self.probe = DistributedScanAgg(
            db, self.pspec, mesh, batch_rows=batch_rows,
            skip_set=skip_sets.get(spec.probe_table), stats=self.stats)
        self.devman = self.probe.devman
        # build-side streams: bare column streams (no grouping) — the
        # jitted build step applies the build's own conjuncts; a build
        # skip-set is sound because a masked row scatter-adds zero
        self.builds = [
            DistributedScanAgg(
                db, ScanAggSpec(b.table, [], [], [], [], 1,
                                list(b.columns)),
                mesh, batch_rows=batch_rows,
                skip_set=skip_sets.get(b.table), stats=self.stats)
            for b in spec.builds]
        geom = join_agg_geometry(spec, db.catalog, mesh_shards(mesh),
                                 batch_rows)
        self.resident_bytes = geom.resident_bytes
        self.working_bytes = geom.working_bytes
        self.delta_rows = self.probe.delta_rows \
            + sum(s.delta_rows for s in self.builds)

    def choose_mode(self) -> str:
        return choose_device_join_tier(
            self.resident_bytes, self.working_bytes, self.devman.budget,
            getattr(self.db, "memory_budget", None))

    def run(self, mode: Optional[str] = None, assemble=None):
        mode = mode or self.choose_mode()
        if mode == "host":
            raise DeviceBudgetError("join does not fit the device tier")
        with span("device_lock", self.stats) as wait, _DEVICE_DISPATCH_LOCK:
            self.stats.device_lock_wait_ms += wait.close()
            return self._run_locked(assemble)

    def _run_locked(self, assemble):  # requires-lock: _DEVICE_DISPATCH_LOCK
        devman = self.devman
        mesh = self.mesh
        axes = _mesh_axes(mesh)
        sh = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
        rep_sh = NamedSharding(mesh, P())
        stats = self.stats
        dup = _cached_scalar_step("dupmax", stats)
        query_keys: set = set()
        pinned: set = set()
        prefetched: set = set()
        btab_keys: list = []
        btabs: list = []
        carry_key = DeviceBlockKeys.carry()
        query_keys.add(carry_key)
        try:
            for b, stream in zip(self.spec.builds, self.builds):
                child_idx = [ci for ci, _ in b.probe_edges]
                child_domains = tuple(self.spec.builds[ci].domain
                                      for ci in child_idx)
                init_fn, step = _cached_join_build_step(
                    b, stream.meta, mesh, stream.batch_rows,
                    child_domains, stats=stats)
                step_g = None
                if stream.gather is not None:
                    _, step_g = _cached_join_build_step(
                        b, stream.meta, mesh, stream.batch_rows,
                        child_domains, gather=stream.gather, stats=stats)
                key = DeviceBlockKeys.carry()
                btab_keys.append(key)
                query_keys.add(key)
                children = [btabs[ci] for ci in child_idx]
                # build matrices stay pinned for the whole query: later
                # builds and every probe batch read them (the planner
                # reserved state_bytes for exactly this residency)
                with span("loop", stats):
                    btab = devman.adopt(key, init_fn(), nbytes=b.table_bytes,
                                        dirty=True, pin=True)
                    for bb, arrs, nxt in stream._stream_batches(
                            sh, query_keys, pinned, prefetched):
                        if nxt is not None:
                            stream._issue_prefetch(nxt, prefetched,
                                                   query_keys, sh)
                        st = step_g if bb in stream._gather_sel else step
                        with span("step", stats):
                            btab = st(btab, *children, *arrs)
                        devman.adopt(key, btab, nbytes=b.table_bytes,
                                     dirty=True, pin=True)
                # runtime uniqueness witness: the single-key gid is only
                # sound for unique build keys (one code, one group/row)
                most = dup(btab)
                with span("fence", stats):
                    most = float(most)
                if most > 1.0:
                    raise _DeviceJoinFallback(
                        f"duplicate join keys in build table {b.table}")
                btabs.append(btab)
            init_fn, pstep = _cached_join_probe_step(
                self.spec, self.probe.meta, mesh, self.probe.batch_rows,
                stats=stats)
            pstep_g = None
            if self.probe.gather is not None:
                _, pstep_g = _cached_join_probe_step(
                    self.spec, self.probe.meta, mesh,
                    self.probe.batch_rows, gather=self.probe.gather,
                    stats=stats)
            edge_btabs = [btabs[bi] for bi, _ in self.spec.probe_edges]
            dense = _dense_reduce(self.pspec.n_groups)
            with span("loop", stats):
                carry = devman.adopt(carry_key, init_fn(),
                                     nbytes=self.probe.carry_nbytes,
                                     dirty=True)
                for bb, arrs, nxt in self.probe._stream_batches(
                        sh, query_keys, pinned, prefetched):
                    if carry_key not in devman:
                        host = devman.take_host(carry_key)
                        carry = devman.put(carry_key, host, sharding=rep_sh,
                                           pin=False, dirty=True)
                    devman.pin(carry_key)
                    if nxt is not None:
                        self.probe._issue_prefetch(nxt, prefetched,
                                                   query_keys, sh)
                    st = pstep_g if bb in self.probe._gather_sel else pstep
                    with span("step", stats):
                        carry = st(carry, *edge_btabs, *arrs)
                    stats.dense_reduce_steps += dense
                    devman.unpin(carry_key)
                    devman.adopt(carry_key, carry,
                                 nbytes=self.probe.carry_nbytes, dirty=True)
            gb = self.spec.group_build
            return _assemble_on_device(
                assemble, mesh, stats, carry,
                btabs[gb] if gb is not None else None)
        finally:
            for key in pinned | prefetched:
                devman.unpin(key)
            for key in btab_keys + [carry_key]:
                devman.unpin(key)
                devman.drop(key)
            if devman.budget is None:
                for key in query_keys:
                    devman.drop(key)


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------


class _SuffixDatabase:
    """Minimal database view for suffix execution: one catalog entry — the
    assembled scan-agg core under ``AGG_RESULT_NAME`` — sharing the parent
    database's buffer manager (one budget accounting)."""

    class _Catalog:
        def __init__(self, table):
            self._table = table

        def table(self, name):
            if name != AGG_RESULT_NAME:
                raise KeyError(name)
            return self._table

    def __init__(self, table, buffer_manager):
        self.catalog = self._Catalog(table)
        self.buffer_manager = buffer_manager
        self.index_manager = None


class ParallelExecutor(Executor):
    """Routes qualifying plans to the shard_map tier (paper Fig. 2)."""

    def __init__(self, database, mesh: Optional[Mesh] = None):
        super().__init__(database)
        self.mesh = mesh

    def _default_mesh(self) -> Mesh:
        if self.mesh is None:
            self.mesh = default_mesh()
        return self.mesh

    def _fall_back(self, exc: BaseException) -> None:
        """Record why a device attempt is abandoned for a host recompute
        (``ExecStats.device_fallback``); returns None, the caller's
        fall-back signal."""
        self.stats.device_fallback = f"{type(exc).__name__}: {exc}"

    def execute(self, plan: PlanNode, do_optimize: bool = True):
        from .serving import lower_cached
        mesh = self._default_mesh()
        with span("plan", self.stats):
            phys, rendered, hit = lower_cached(self.db, plan,
                                               do_optimize=do_optimize,
                                               distributed=True, mesh=mesh)
        self.policy = phys.policy
        self.stats.plan_repr = rendered
        self.stats.plan_cache_hit = hit
        with self._admitted(phys):
            if phys.device_tier():
                result = self._try_distributed(phys)
                if result is not None:
                    return result
                # the planner chose the device tier but runtime lowering
                # failed; the host program is the fallback — re-render so
                # EXPLAIN/stats reflect what actually ran, and why
                phys.demote_device(
                    self.stats.device_fallback.splitlines()[0])
                self.stats.plan_repr = phys.render()
            prog = compile_plan(phys.plan, self.db.catalog)
            result = self.run_program(prog)
        self._plan_feedback(plan, True)
        return result

    @staticmethod
    def _stats_window():
        from .executor import (DEVICE_DELTA_FIELDS, INGEST_DELTA_FIELDS,
                               SKIP_DELTA_FIELDS, stats_base)
        fields = DEVICE_DELTA_FIELDS + SKIP_DELTA_FIELDS \
            + INGEST_DELTA_FIELDS
        return fields, stats_base

    def _claim_device(self, tier: str, fields, base, end, dm,
                      device_sorted: bool) -> None:
        # claim the device tier only once the WHOLE query succeeded: a
        # suffix failure falls back to a full host recompute, and
        # device_tier must describe the result returned
        self.stats.device_tier = tier
        self.stats.device_sorted = device_sorted
        for f, b, e in zip(fields, base, end):
            setattr(self.stats, f, getattr(self.stats, f) + e - b)
        # lifetime gauge, reported only by queries that ran on the device
        # tier (host-tier queries keep 0 alongside device_tier == "")
        self.stats.device_bytes_peak = dm.device_bytes_peak

    # -- distributed scan-agg -------------------------------------------------
    def _try_distributed(self, phys: PhysicalPlan):
        """Run the physical plan's core through the device tier (the tier
        the planner annotated), then the host-side suffix (ORDER BY /
        LIMIT / projection / HAVING) over the assembled aggregate — unless
        the sort was fused onto the device (``sort_on_device``), in which
        case assembly returns already-ordered rows and the suffix is
        skipped entirely; None means a runtime lowering gap — the caller
        falls back to the host program."""
        if phys.join_agg is not None:
            return self._try_join(phys)
        spec = phys.scan_agg
        table = self.db.catalog.table(spec.table)
        try:
            # the batch stream's set-up (batch geometry, zone-map live
            # batches, gather layout) runs on the host before any step
            with span("prepare", self.stats):
                agg = DistributedScanAgg(
                    self.db, spec, self._default_mesh(),
                    batch_rows=getattr(self.db, "device_batch_rows", None),
                    skip_set=phys.core_skip_set(), stats=self.stats)
        except Exception as e:
            return self._fall_back(e)
        tier = "resident" if phys.agg_tier == TIER_DEVICE_RESIDENT \
            else "streamed"
        fields, stats_base = self._stats_window()
        dm = agg.devman.stats
        base = stats_base(dm, fields)
        assemble = None
        if phys.sort_on_device:
            sort_cols = self._sort_cols_scan(spec, table,
                                             phys.sort_node.keys)
            if sort_cols is not None:
                assemble = self._device_assemble(
                    spec, sort_cols, phys.sort_node.limit, 0)
        try:
            out = agg.run(tier, assemble=assemble)
        except Exception as e:
            return self._fall_back(e)   # any lowering gap: host tier
        if agg.delta_rows:
            # merge-on-read visibility: the scan consumed a delta tail
            agg.devman.bump(delta_rows=agg.delta_rows)
        with span("assemble", self.stats):
            if assemble is not None:
                gids, vals, _pay = out
                result = self._assemble(spec, vals, table, gids=gids)
            else:
                result = self._assemble(spec, out, table)
        # close the device-counter window BEFORE the suffix runs (its host
        # program threads the same delta fields through run_program)
        end = stats_base(dm, fields)
        if phys.suffix_plan is not None and assemble is None:
            try:
                result = self._run_suffix(phys.suffix_plan, result)
            except Exception as e:
                # suffix gap: the host program recomputes everything
                return self._fall_back(e)
        self._claim_device(tier, fields, base, end, dm,
                           device_sorted=assemble is not None)
        return result

    # -- distributed join-agg -------------------------------------------------
    def _try_join(self, phys: PhysicalPlan):
        """Run the physical plan's join-agg core through the device join
        tier: builds bottom-up, probe stream, device-resident assembly
        (finalize + compact + fused ORDER BY all in HBM)."""
        jspec = phys.join_agg
        tables = [jspec.probe_table] + [b.table for b in jspec.builds]
        try:
            with span("prepare", self.stats):
                agg = DistributedJoinAgg(
                    self.db, jspec, self._default_mesh(),
                    batch_rows=getattr(self.db, "device_batch_rows", None),
                    skip_sets={t: phys.skip_set_for_table(t)
                               for t in tables},
                    stats=self.stats)
        except Exception as e:
            return self._fall_back(e)
        mode = phys.join_mode or "streamed"
        fields, stats_base = self._stats_window()
        dm = agg.devman.stats
        base = stats_base(dm, fields)
        gb = jspec.group_build
        n_payload = len(jspec.builds[gb].payload) if gb is not None else 0
        sort_cols, limit = (), None
        if phys.sort_on_device:
            sort_cols = self._sort_cols_join(jspec, phys.sort_node.keys)
            if sort_cols is None:
                sort_cols = ()
            else:
                limit = phys.sort_node.limit
        device_sorted = bool(sort_cols)
        assemble = self._device_assemble(agg.pspec, sort_cols, limit,
                                         n_payload)
        try:
            gids, vals, pay = agg.run(mode, assemble=assemble)
        except Exception as e:
            # _DeviceJoinFallback (duplicate build keys: the host join is
            # the truth) or any lowering gap
            return self._fall_back(e)
        if agg.delta_rows:
            agg.devman.bump(delta_rows=agg.delta_rows)
        with span("assemble", self.stats):
            result = self._assemble_join(jspec, gids, vals, pay)
        end = stats_base(dm, fields)
        if phys.suffix_plan is not None and not device_sorted:
            try:
                result = self._run_suffix(phys.suffix_plan, result)
            except Exception as e:
                return self._fall_back(e)
        self._claim_device("join-" + mode, fields, base, end, dm,
                           device_sorted=device_sorted)
        return result

    # -- device-resident assembly ---------------------------------------------
    def _device_assemble(self, pspec: ScanAggSpec, sort_cols, limit,
                         n_payload: int):
        """Assembly plan handed to the stream's ``run``: plain data (spec,
        sort sources, limit, payload width) that ``_assemble_on_device``
        turns into the finalize/compact/fused-sort dispatch under the
        stream's dispatch lock; only the compacted result rows come to
        host."""
        return (pspec, tuple(sort_cols), limit, n_payload)

    def _sort_cols_scan(self, spec: ScanAggSpec, table, keys):
        """Map ORDER BY keys of a scan-agg core onto assembly sort sources
        (group-key digit or agg column); None when a key is unmappable."""
        cols = []
        agg_names = [a.name for a in spec.aggs]
        for col, desc in keys:
            if col in spec.group_keys:
                c = table.column(col)
                cols.append((("digit", spec.group_keys.index(col)),
                             c.dbtype, c.scale, bool(desc)))
            elif col in agg_names:
                i = agg_names.index(col)
                dbt = DBType.INT64 if spec.aggs[i].fn == "count" \
                    else DBType.FLOAT64
                cols.append((("agg", i), dbt, 0, bool(desc)))
            else:
                return None
        return tuple(cols)

    def _sort_cols_join(self, jspec: JoinAggSpec, keys):
        """Join-core ORDER BY keys: group keys resolve through
        ``group_sources`` — the build key digit or a payload lane of the
        group build's matrix."""
        gb = jspec.builds[jspec.group_build] \
            if jspec.group_build is not None else None
        cols = []
        agg_names = [a.name for a in jspec.aggs]
        for col, desc in keys:
            if col in jspec.group_keys:
                src = jspec.group_sources[jspec.group_keys.index(col)]
                if src[0] == "key":
                    c = self.db.catalog.table(gb.table).column(gb.key)
                    cols.append((("digit", 0), c.dbtype, c.scale,
                                 bool(desc)))
                else:
                    c = self.db.catalog.table(gb.table).column(
                        gb.payload[src[1]])
                    cols.append((("payload", src[1]), c.dbtype, c.scale,
                                 bool(desc)))
            elif col in agg_names:
                i = agg_names.index(col)
                dbt = DBType.INT64 if jspec.aggs[i].fn == "count" \
                    else DBType.FLOAT64
                cols.append((("agg", i), dbt, 0, bool(desc)))
            else:
                return None
        return tuple(cols)

    def _run_suffix(self, suffix_plan: PlanNode, table):
        """Execute the suffix operators over the assembled aggregate: a
        host program against a one-table catalog holding the (tiny) core
        result.  Stats and policy are shared, so suffix sorts/limits that
        spill are counted against this query."""
        with span("suffix", self.stats):
            sdb = _SuffixDatabase(table, self.bufman)
            sub = Executor(sdb)
            sub.stats = self.stats
            sub.policy = self.policy
            prog = compile_plan(suffix_plan, sdb.catalog)
            return sub.run_program(prog)

    def _assemble(self, spec: ScanAggSpec, out: np.ndarray, table,
                  gids: Optional[np.ndarray] = None):
        from .column import Column
        from .table import Table
        from .types import ColumnSchema, TableSchema
        if gids is None:
            cnt_star = out[:, -1]
            present = cnt_star > 0 if spec.group_keys else np.ones(1, bool)
            gids = np.nonzero(present)[0]
            vals = out[gids]
        else:
            # device-resident assembly already compacted (and ordered)
            # the rows; ``out`` is (n_present, n_aggs + 1)
            vals = out
        cols = {}
        schemas = []
        # reconstruct key values from the mixed-radix gid
        rem = gids.copy()
        radices = [card for _, card in spec.key_domains]
        digits = []
        for off, card in reversed(spec.key_domains):
            digits.append(rem % card)
            rem = rem // card
        digits.reverse()
        for k, (off, card), d in zip(spec.group_keys, spec.key_domains,
                                     digits):
            col = table.column(k)
            if col.dbtype == DBType.VARCHAR:
                kv = d.astype(np.int32)
                cols[k] = Column(DBType.VARCHAR, kv, heap=col.heap)
            else:
                kv = (d + off).astype(col.data.dtype)
                cols[k] = Column(col.dbtype, kv, scale=col.scale)
            schemas.append(ColumnSchema(k, col.dbtype, scale=col.scale))
        for i, a in enumerate(spec.aggs):
            v = vals[:, i]
            if a.fn == "count":
                cols[a.name] = Column(DBType.INT64, v.astype(np.int64))
                schemas.append(ColumnSchema(a.name, DBType.INT64))
            else:
                cols[a.name] = Column(DBType.FLOAT64, v.astype(np.float64))
                schemas.append(ColumnSchema(a.name, DBType.FLOAT64))
        return Table(TableSchema("result", tuple(schemas)), cols)

    def _assemble_join(self, jspec: JoinAggSpec, gids: np.ndarray,
                       vals: np.ndarray, pay: np.ndarray):
        """Build the core result table of a device join from the
        device-assembled triple: group keys resolve through
        ``group_sources`` (build key code / payload lane), aggregates from
        the finalized rows — column order matches the host program's
        aggregate output (keys, then aggs)."""
        from .column import Column
        from .table import Table
        from .types import ColumnSchema, TableSchema
        catalog = self.db.catalog
        gb = jspec.builds[jspec.group_build] \
            if jspec.group_build is not None else None
        cols = {}
        schemas = []
        for k, src in zip(jspec.group_keys, jspec.group_sources):
            if src[0] == "key":
                col = catalog.table(gb.table).column(gb.key)
                v = (gids.astype(np.float64) + jspec.key_domain[0]) \
                    .astype(col.data.dtype)
            else:
                col = catalog.table(gb.table).column(gb.payload[src[1]])
                v = pay[:, src[1]].astype(col.data.dtype)
            if col.dbtype == DBType.VARCHAR:
                cols[k] = Column(DBType.VARCHAR, v, heap=col.heap)
            else:
                cols[k] = Column(col.dbtype, v, scale=col.scale)
            schemas.append(ColumnSchema(k, col.dbtype, scale=col.scale))
        for i, a in enumerate(jspec.aggs):
            v = vals[:, i]
            if a.fn == "count":
                cols[a.name] = Column(DBType.INT64, v.astype(np.int64))
                schemas.append(ColumnSchema(a.name, DBType.INT64))
            else:
                cols[a.name] = Column(DBType.FLOAT64,
                                      v.astype(np.float64))
                schemas.append(ColumnSchema(a.name, DBType.FLOAT64))
        return Table(TableSchema("result", tuple(schemas)), cols)

    # -- host-chunked fallback (Fig. 2 semantics without devices) -------------
    def run_chunked_host(self, spec: ScanAggSpec, n_chunks: int):
        """Reference chunked execution used by tests to validate that
        per-chunk partials + merge == sequential results."""
        db = self.db
        table = db.catalog.table(spec.table)
        n = table.num_rows
        bounds = np.linspace(0, n, n_chunks + 1).astype(int)
        partial_sums = None
        for ci in range(n_chunks):
            s, e = bounds[ci], bounds[ci + 1]
            arrays = {}
            meta = {}
            for c in spec.columns:
                col = table.column(c)
                arrays[c] = np.asarray(col.data)[s:e]
                meta[c] = (col.dbtype, col.heap, col.scale)
            ctx_mask = np.ones(e - s, dtype=bool)
            for conj in spec.conjuncts:
                r = conj.eval(EvalContext(arrays, meta, xp=np))
                m = np.asarray(r.values) != 0
                if r.null is not None:
                    m &= ~np.asarray(r.null)
                ctx_mask &= m
            gid = np.zeros(e - s, dtype=np.int64)
            for k, (off, card) in zip(spec.group_keys, spec.key_domains):
                t, heap, scale = meta[k]
                kv = arrays[k]
                code = kv.astype(np.int64) if t == DBType.VARCHAR \
                    else (kv.astype(np.float64) - off).astype(np.int64)
                code = np.clip(code, 0, card - 1)
                gid = gid * card + code
            chunk = np.zeros((spec.n_groups, 2 * len(spec.aggs) + 1))
            chunk[:, -1] = np.bincount(gid[ctx_mask],
                                       minlength=spec.n_groups)
            for i, a in enumerate(spec.aggs):
                if a.expr is None:
                    chunk[:, 2 * i] = chunk[:, -1]
                    chunk[:, 2 * i + 1] = chunk[:, -1]
                    continue
                r = a.expr.eval(EvalContext(arrays, meta, xp=np))
                ok = ctx_mask & ~_res_nulls(r)
                f = r.as_float(np)
                chunk[:, 2 * i] = np.bincount(
                    gid[ok], weights=f[ok], minlength=spec.n_groups)
                chunk[:, 2 * i + 1] = np.bincount(
                    gid[ok], minlength=spec.n_groups)
            partial_sums = chunk if partial_sums is None \
                else partial_sums + chunk
        return partial_sums
