"""Jit'd wrapper for hash_group: padding + multi-aggregate assembly.

``grouped_aggregate`` computes sum/count (and via sum-of-ones, mean) for V
value columns over dense group ids in one kernel launch.  min/max fall back
to the executor's segment path (they are not onehot-matmul shaped).
"""

from __future__ import annotations

import numpy as np

from .hash_group import hash_group_call

MAX_DENSE_GROUPS = 4096


def _pad8(x: int) -> int:
    return -(-x // 8) * 8


def grouped_aggregate(gid: np.ndarray, vals: np.ndarray, n_groups: int,
                      mask: np.ndarray | None = None,
                      block_rows: int = 2048, interpret: bool = False,
                      use_pallas: bool = True) -> np.ndarray:
    """gid: (n,) int; vals: (V, n) float; returns (n_groups, V+1) float64 —
    per-group sums for each value column plus the group count in the last
    column."""
    V, n = vals.shape
    g_pad = _pad8(max(n_groups + 1, 8))        # +1 trash group
    n_pad = -(-max(n, 1) // block_rows) * block_rows
    Vp = _pad8(V + 1)                           # +1 ones column for counts

    g = np.full(n_pad, g_pad - 1, dtype=np.int32)
    gg = gid.astype(np.int32)
    if mask is not None:
        gg = np.where(mask, gg, g_pad - 1)
    g[:n] = gg

    v = np.zeros((Vp, n_pad), dtype=np.float32)
    v[:V, :n] = vals.astype(np.float32)
    v[V, :n] = 1.0                              # count column

    if use_pallas:
        import jax.numpy as jnp
        acc = hash_group_call(jnp.asarray(g[None, :]), jnp.asarray(v),
                              g_pad, block_rows=block_rows,
                              interpret=interpret)
        acc = np.asarray(acc, dtype=np.float64)
    else:
        acc = np.zeros((g_pad, Vp), dtype=np.float64)
        np.add.at(acc, g, v.T.astype(np.float64))
    return acc[:n_groups, :V + 1]
