"""Project-specific rule configuration: which attributes are guarded by
which locks, which call pairs must be exception-safe, and which names
produce jitted collective dispatch handles.

The guarded-by registry is seeded for the engine's five shared-state
classes; new fields can be declared either here or inline with a
``# guarded-by: <lock>`` comment on the assignment (see README.md).
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# guarded-by: class -> {attribute: lock attribute}
# ---------------------------------------------------------------------------
# Applies inside the defining module: `self.<attr>` in the owning class's
# methods and `<recv>.<attr>` anywhere (e.g. the admission ticket touching
# `gate._host_reserved`) must hold the named lock on the same receiver.
# `__init__` of the owning class (construction) is exempt.

GUARDED_REGISTRY: dict[str, dict[str, str]] = {
    "BufferManager": {
        "_files": "_lock",
        "_seq": "_lock",
        "_spill_dir": "_lock",
        "_dir_ready": "_lock",
        "_active_queries": "_query_cond",
        "_cleanup_deferred": "_query_cond",
    },
    "DeviceBufferManager": {
        "_blocks": "_lock",
        "_host": "_lock",
        "_resident": "_lock",
        "_table_hits": "_lock",
    },
    "DeltaTable": {
        # merge-on-read memoization: concurrent readers race to build the
        # merged column dict; the lock makes the merge happen once
        "_merged": "_merge_lock",
    },
    "AdmissionGate": {
        "_host_reserved": "_cond",
        "_device_reserved": "_cond",
    },
    "PlanCache": {
        "_entries": "_lock",
        "_cards": "_lock",
    },
    "SingleFlight": {
        "_calls": "_lock",
    },
}

# ---------------------------------------------------------------------------
# check-then-act: predicate names whose result must not gate a mutation
# outside a lock (the pre-PR-6 `would_exceed()` + `pin()` bug class), and
# the mutators they must not gate.  `try_pin` is the atomic replacement
# and is deliberately NOT a predicate.
# ---------------------------------------------------------------------------

TOCTOU_PREDICATES = {"would_exceed", "contains", "fits"}
TOCTOU_MUTATORS = {"pin", "put", "adopt", "add", "append", "reserve"}

# ---------------------------------------------------------------------------
# acquire-release pairing: acquire method -> acceptable releases.  A call
# to an acquire must be exception-safe: used as a `with` context, released
# in a `finally`/`except` within the same function, paired through
# `__enter__`/`__exit__`, or annotated `# transfers-ownership`.
# ---------------------------------------------------------------------------

ACQUIRE_PAIRS: dict[str, frozenset] = {
    "pin": frozenset({"unpin", "drop"}),          # byte pins + device keys
    "try_pin": frozenset({"unpin"}),
    "acquire_lock": frozenset({"release_lock"}),
    "new_spill_file": frozenset({"release_file", "abort"}),
    "begin_query": frozenset({"end_query"}),
    "admit": frozenset({"release"}),              # gate reserve -> release
}

# Methods returning an RAII object (safe when used as a `with` context).
CONTEXT_ACQUIRES = {"pinned", "query_scope", "admit"}

# ---------------------------------------------------------------------------
# device-dispatch: calling a handle returned by one of these factories
# lowers/executes a jitted collective step; a concurrent dispatch
# deadlocks the XLA rendezvous (PR 6), so every such call must hold
# _DEVICE_DISPATCH_LOCK (lexically or via `# requires-lock`).  AOT
# inspection (`handle.lower(...)`) does not execute and is not dispatch.
# ---------------------------------------------------------------------------

DISPATCH_PRODUCERS = {"_cached_batch_step",
                      "build_batch_step", "build_query_step",
                      "_cached_join_build_step", "_cached_join_probe_step",
                      "build_join_build_step", "build_join_probe_step",
                      "_cached_scalar_step", "build_scalar_step",
                      "_cached_assemble_step", "build_assemble_step"}
DISPATCH_LOCK = "_DEVICE_DISPATCH_LOCK"

# ---------------------------------------------------------------------------
# stats discipline: classes whose `self.stats` is the SHARED BufferStats /
# AdmissionStats object, and local-variable aliases that reach a shared
# stats object from operator code.  Direct `X.stats.field += n` on these is
# an unlocked read-modify-write (lost updates) — increments go through the
# manager's locked `bump(**deltas)` helper or the `stats_base` /
# `stats_apply_delta` delta window instead.  Per-query `ExecStats`
# (`self.stats` on Executor) is thread-local and exempt.
# ---------------------------------------------------------------------------

STATS_OWNER_CLASSES = {"BufferManager", "DeviceBufferManager",
                       "AdmissionGate"}
STATS_MANAGER_ALIASES = {"bm", "bufman", "devman", "dm",
                         "buffer_manager", "device_manager", "bstats"}

# module-level mutable containers that functions mutate must have a
# module-level lock whose name shares their leading token (e.g.
# _STEP_CACHE / _STEP_CACHE_LOCK, _open_dirs / _open_lock) or an explicit
# `# guarded-by:` comment; import-time (module-body) mutation is exempt.
MUTATING_METHODS = {"append", "add", "pop", "popitem", "setdefault",
                    "update", "clear", "extend", "insert", "discard",
                    "remove"}
