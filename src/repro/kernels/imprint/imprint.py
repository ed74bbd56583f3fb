"""Pallas TPU kernel: imprint (zone map) construction.

MonetDB's imprints are per-cache-line min/max bitmaps (Sidirourgos &
Kersten, SIGMOD'13; paper §3.1).  The TPU adaptation builds zone maps at
VMEM-block granularity: for every block of ``block_rows`` rows we emit

    min, max, and a 16-bin presence bitmap over the global value range.

Tiling: each grid step loads a ``(G, block_rows)`` tile of values (plus a
validity tile) into VMEM — G zone blocks per step, laid out so the reduction
runs along lanes.  With G=8 and block_rows=2048 a step works on a
(8, 2048) f32 tile = 64 KiB of VMEM per operand, well inside v5e VMEM, and
the per-step output is an (8,) vector per statistic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

G_BLOCKS = 8          # zone blocks per grid step (sublane dim, f32 tile = 8)


def _zone_kernel(nbins: int, vals_ref, valid_ref, rng_ref,
                 mins_ref, maxs_ref, bm_ref):
    v = vals_ref[...]                        # (G, B) f32
    ok = valid_ref[...] > 0                  # (G, B)
    big = jnp.float32(3.4e38)
    vmin = jnp.min(jnp.where(ok, v, big), axis=1)       # (G,)
    vmax = jnp.max(jnp.where(ok, v, -big), axis=1)
    mins_ref[...] = vmin
    maxs_ref[...] = vmax
    lo = rng_ref[0, 0]
    inv = rng_ref[0, 1]                       # nbins / (hi - lo), 0 if empty
    binned = jnp.clip((v - lo) * inv, 0, nbins - 1).astype(jnp.int32)
    bm = jnp.zeros((v.shape[0],), dtype=jnp.int32)
    for b in range(nbins):                    # static unroll (nbins = 16)
        present = jnp.any(ok & (binned == b), axis=1)
        bm = bm | (present.astype(jnp.int32) << b)
    bm_ref[...] = bm


@functools.partial(jax.jit, static_argnames=("block_rows", "nbins",
                                             "interpret"))
def zone_maps_pallas(vals: jax.Array, valid: jax.Array, rng: jax.Array,
                     *, block_rows: int = 2048, nbins: int = 16,
                     interpret: bool = False):
    """vals/valid: (n_blocks, block_rows) f32 (pre-padded); rng: (1, 2) f32
    holding (lo, nbins/(hi-lo)).  Returns (mins, maxs, bitmaps)."""
    n_blocks = vals.shape[0]
    assert n_blocks % G_BLOCKS == 0, "pad n_blocks to a multiple of G_BLOCKS"
    grid = (n_blocks // G_BLOCKS,)
    kern = functools.partial(_zone_kernel, nbins)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((G_BLOCKS, block_rows), lambda i: (i, 0)),
            pl.BlockSpec((G_BLOCKS, block_rows), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((G_BLOCKS,), lambda i: (i,)),
            pl.BlockSpec((G_BLOCKS,), lambda i: (i,)),
            pl.BlockSpec((G_BLOCKS,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks,), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks,), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks,), jnp.int32),
        ],
        interpret=interpret,
    )(vals, valid, rng)
