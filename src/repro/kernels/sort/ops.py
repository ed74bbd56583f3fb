"""Dispatch shim for the sort kernels: padding + multi-key dispatch.

Two entry points:

* ``sort_block`` — single-key block sort through the Pallas bitonic
  network (``use_pallas=False`` mirrors it with a stable numpy argsort,
  which the tie-broken network is exactly equivalent to);
* ``lexsort_indices`` — the multi-key permutation the engine's
  device-sort tier dispatches: a jitted ``jnp.lexsort`` over float sort
  keys (NULLs pushed to +inf, descending keys negated — the same key
  transform as the host executor's ``_sort_key_float``), optionally
  sliced to a fused top-N.  Jitted closures are memoized per
  (n_keys, limit) so repeated ORDER BY queries don't re-trace.
"""

from __future__ import annotations

import threading

import numpy as np

from .sort import bitonic_sort_call


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def sort_block(keys: np.ndarray, *, interpret: bool = False,
               use_pallas: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """keys: (n,) float.  Returns ``(sorted, perm)`` ascending with NaNs
    last; ``perm`` is the stable argsort permutation."""
    k = np.asarray(keys, dtype=np.float32)
    n = k.shape[0]
    if not use_pallas:
        kk = np.where(np.isnan(k), np.float32(np.inf), k)
        perm = np.argsort(kk, kind="stable")
        return kk[perm], perm
    n_pad = _next_pow2(max(n, 2))
    kp = np.full(n_pad, np.inf, dtype=np.float32)
    kp[:n] = np.where(np.isnan(k), np.float32(np.inf), k)
    ix = np.arange(n_pad, dtype=np.int32)
    import jax.numpy as jnp
    sk, si = bitonic_sort_call(jnp.asarray(kp[None, :]),
                               jnp.asarray(ix[None, :]),
                               interpret=interpret)
    return np.asarray(sk[0, :n]), np.asarray(si[0, :n])


# memoized jitted lexsort closures — shared across queries/threads
_PERM_CACHE: dict = {}
_PERM_CACHE_LOCK = threading.Lock()


def _lexsort_fn(n_keys: int, limit):
    with _PERM_CACHE_LOCK:
        fn = _PERM_CACHE.get((n_keys, limit))
        if fn is None:
            import jax
            import jax.numpy as jnp

            def perm_fn(*fkeys):
                # np.lexsort semantics: the LAST key is primary, so the
                # caller's primary-first order is reversed here
                idx = jnp.lexsort(tuple(reversed(fkeys)))
                return idx if limit is None else idx[:limit]

            fn = jax.jit(perm_fn)
            _PERM_CACHE[(n_keys, limit)] = fn
        return fn


def lexsort_indices(fkeys, limit=None, *, use_device: bool = True):
    """fkeys: primary-first list of (n,) float64 sort keys (already
    NULL-masked/negated).  Returns the (limit or n,) row permutation —
    ``np.lexsort``-identical (both paths are stable lexicographic)."""
    if not use_device:
        idx = np.lexsort(tuple(reversed([np.asarray(k) for k in fkeys])))
        return idx if limit is None else idx[:limit]
    fn = _lexsort_fn(len(fkeys), limit)
    return np.asarray(fn(*fkeys))
