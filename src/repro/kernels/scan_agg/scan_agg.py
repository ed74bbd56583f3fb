"""Pallas TPU kernel: fused selection + multi-aggregate scan.

This is the engine's hottest loop (paper Table 1: TPC-H Q1/Q6 are
filter->aggregate scans).  A naive column-at-a-time plan reads each column
from HBM once per operator; this kernel performs the *entire*
filter + k-aggregate pipeline in a single HBM pass, accumulating partials in
VMEM.

Contract (see ops.py for the user-facing wrapper):

  cols:   (C, n) f32 — C input columns, tightly packed (sublane-padded)
  ranges: (C, 2) f32 — per-column [lo, hi] selection range; non-filter
          columns get (-inf, +inf).  Mask = AND over all columns in range.
  pairs:  static tuple of (a, b) column-index pairs; aggregate p sums
          cols[a]*cols[b] over selected rows (b == -1 means cols[a] alone).
  out:    (n_steps, 128) f32 — per-grid-step partials; lane p holds
          aggregate p, lane P holds the selected-row count.  Final reduce is
          a tiny jnp sum in ops.py (the merge step of the paper's Fig. 2).

Tiling: each grid step loads a (C_pad, B) tile; B = 8·1024 rows keeps a
6-column tile at 6·32 KiB = 192 KiB of VMEM.  The multiply-accumulate runs
on the VPU; there is no MXU work, so the kernel is purely HBM-bound — which
is the roofline the fusion is attacking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _scan_agg_kernel(pairs, cols_ref, ranges_ref, out_ref):
    x = cols_ref[...]                                   # (C, B)
    lo = ranges_ref[:, 0:1]                             # (C, 1)
    hi = ranges_ref[:, 1:2]
    ok = jnp.all((x >= lo) & (x <= hi), axis=0)         # (B,)
    okf = ok.astype(jnp.float32)
    acc = []
    for a, b in pairs:
        v = x[a] if b < 0 else x[a] * x[b]
        acc.append(jnp.sum(v * okf))
    acc.append(jnp.sum(okf))                            # count
    vec = jnp.zeros((LANES,), jnp.float32)
    vec = vec.at[:len(acc)].set(jnp.stack(acc))
    out_ref[0, :] = vec


@functools.partial(jax.jit,
                   static_argnames=("pairs", "block_rows", "interpret"))
def scan_agg_pallas(cols: jax.Array, ranges: jax.Array, *,
                    pairs: tuple[tuple[int, int], ...],
                    block_rows: int = 8192, interpret: bool = False):
    """cols: (C, n) f32 with n % block_rows == 0 and C % 8 == 0 (pre-padded,
    padding rows carry values outside every range).  Returns (n_steps, 128)
    partials."""
    C, n = cols.shape
    assert n % block_rows == 0 and C % 8 == 0
    assert len(pairs) + 1 <= LANES
    steps = n // block_rows
    kern = functools.partial(_scan_agg_kernel, tuple(pairs))
    return pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((C, block_rows), lambda i: (0, i)),
            pl.BlockSpec((C, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, LANES), jnp.float32),
        interpret=interpret,
    )(cols, ranges)
