"""Jit'd wrapper + host-tier mirror for the imprint kernel.

``build_zone_maps`` is the engine entry point (indexes.py).  On the host
tier (CPU container) it uses the vectorized numpy mirror; the Pallas path
(`build_zone_maps_pallas`) is the TPU-target implementation, validated in
interpret mode by tests/test_kernels_imprint.py against ref.py.
"""

from __future__ import annotations

import numpy as np

from .imprint import G_BLOCKS, zone_maps_pallas


def _prepare(values: np.ndarray, nulls: np.ndarray, block: int):
    n = len(values)
    n_blocks = max(1, -(-n // block))
    pad_blocks = -(-n_blocks // G_BLOCKS) * G_BLOCKS
    total = pad_blocks * block
    v = np.zeros(total, dtype=np.float32)
    v[:n] = values.astype(np.float32)
    ok = np.zeros(total, dtype=np.float32)
    ok[:n] = (~nulls).astype(np.float32)
    return (v.reshape(pad_blocks, block), ok.reshape(pad_blocks, block),
            n_blocks)


def _range(values: np.ndarray, nulls: np.ndarray, nbins: int):
    ok = ~nulls
    if not ok.any():
        return 0.0, 0.0, 0.0
    lo = float(values[ok].min())
    hi = float(values[ok].max())
    inv = float(nbins / (hi - lo)) if hi > lo else 0.0
    return lo, hi, inv


def build_zone_maps(values: np.ndarray, nulls: np.ndarray,
                    block: int, nbins: int):
    """Host-tier zone maps (numpy mirror of the kernel; bit-identical
    semantics).  Returns (mins, maxs, bitmaps, lo, hi) trimmed to the real
    block count, in float64 for index precision."""
    lo, hi, inv = _range(values, nulls, nbins)
    n = len(values)
    n_blocks = max(1, -(-n // block))
    mins = np.full(n_blocks, np.inf)
    maxs = np.full(n_blocks, -np.inf)
    bitmaps = np.zeros(n_blocks, dtype=np.uint16)
    for b in range(n_blocks):
        s, e = b * block, min((b + 1) * block, n)
        v = values[s:e]
        ok = ~nulls[s:e]
        if ok.any():
            vv = v[ok]
            mins[b] = vv.min()
            maxs[b] = vv.max()
            if inv > 0:
                bins = np.clip(((vv - lo) * inv).astype(np.int64),
                               0, nbins - 1)
                bitmaps[b] = np.bitwise_or.reduce(
                    (1 << bins).astype(np.uint16))
            else:
                bitmaps[b] = 1
    return mins, maxs, bitmaps, lo, hi


def build_zone_maps_pallas(values: np.ndarray, nulls: np.ndarray,
                           block: int, nbins: int, interpret: bool = False):
    """Device-tier zone maps through the Pallas kernel.  Same contract as
    build_zone_maps (float32 bounds; callers widen conservatively)."""
    import jax.numpy as jnp
    lo, hi, inv = _range(values, nulls, nbins)
    v2d, ok2d, n_blocks = _prepare(values, nulls, block)
    rng = jnp.asarray([[lo, inv]], dtype=jnp.float32)
    mins, maxs, bm = zone_maps_pallas(
        jnp.asarray(v2d), jnp.asarray(ok2d), rng,
        block_rows=block, nbins=nbins, interpret=interpret)
    mins = np.asarray(mins)[:n_blocks].astype(np.float64)
    maxs = np.asarray(maxs)[:n_blocks].astype(np.float64)
    bm = np.asarray(bm)[:n_blocks].astype(np.uint16)
    empty = mins > maxs
    mins[empty], maxs[empty] = np.inf, -np.inf
    # float32 rounding could shrink the true bounds: widen by one ulp so the
    # zone test never mis-prunes.
    mins = np.nextafter(mins.astype(np.float32), -np.inf).astype(np.float64)
    maxs = np.nextafter(maxs.astype(np.float32), np.inf).astype(np.float64)
    mins[empty], maxs[empty] = np.inf, -np.inf
    return mins, maxs, bm, lo, hi
