"""Jit'd wrapper for scan_agg: padding, dispatch, partial merge.

``fused_filter_agg`` is what the query executor calls for qualifying
filter->aggregate plans (no GROUP BY or dense small groups handled by
hash_group): it pads the columns to tile shape, invokes the kernel, and
merges the per-step partials (the "merge" node of paper Fig. 2).
"""

from __future__ import annotations

import numpy as np

from .scan_agg import scan_agg_pallas

_NEG = np.float32(-3.0e38)
_WIDE = np.float32(3.0e38)


def fused_filter_agg(cols: np.ndarray, ranges: np.ndarray,
                     pairs: tuple[tuple[int, int], ...],
                     block_rows: int = 8192, interpret: bool = False,
                     use_pallas: bool = True) -> np.ndarray:
    """cols: (C, n) float; ranges: (C, 2); returns (P+1,) float64 —
    one sum per pair plus the selected count.

    Layout sent to the kernel: columns padded to the f32 sublane multiple,
    with column C a synthetic *validity column* (1.0 for real rows, -3e38
    for padding rows, range [0, 2]) so row padding can never leak into the
    aggregates regardless of the user's filter ranges."""
    C, n = cols.shape
    Cp = -(-(C + 1) // 8) * 8
    npad = -(-max(n, 1) // block_rows) * block_rows

    cp = np.zeros((Cp, npad), dtype=np.float32)
    cp[:C, :n] = cols.astype(np.float32)
    cp[C, :n] = 1.0                      # validity column
    cp[C, n:] = _NEG

    rp = np.zeros((Cp, 2), dtype=np.float32)
    rp[:, 0], rp[:, 1] = -_WIDE, _WIDE   # pad columns: always in range
    rr = ranges.astype(np.float32)
    rp[:C, 0] = np.maximum(rr[:, 0], -_WIDE)
    rp[:C, 1] = np.minimum(rr[:, 1], _WIDE)
    rp[C] = (0.0, 2.0)                   # validity range
    if use_pallas:
        import jax.numpy as jnp
        parts = scan_agg_pallas(jnp.asarray(cp), jnp.asarray(rp),
                                pairs=tuple(pairs), block_rows=block_rows,
                                interpret=interpret)
        merged = np.asarray(parts, dtype=np.float64).sum(axis=0)
        return merged[:len(pairs) + 1]
    # host mirror (numpy, same math)
    ok = np.all((cp >= rp[:, 0:1]) & (cp <= rp[:, 1:2]), axis=0)
    okf = ok.astype(np.float64)
    outs = []
    for a, b in pairs:
        v = cp[a].astype(np.float64)
        if b >= 0:
            v = v * cp[b].astype(np.float64)
        outs.append(float((v * okf).sum()))
    outs.append(float(okf.sum()))
    return np.asarray(outs)
