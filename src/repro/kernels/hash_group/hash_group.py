"""Pallas TPU kernel: dense-domain grouped aggregation.

MonetDB auto-builds hash tables for GROUP BY (paper §3.1).  Pointer-chasing
hash tables are hostile to the TPU's vector/matrix units, so the TPU-native
equivalent (DESIGN.md §3) turns grouped aggregation into a *one-hot matmul*:

    acc[g, v] += Σ_rows onehot(gid)[row, g] · vals[row, v]

which the MXU executes as a (G × B) @ (B × V) product per tile — grouped
aggregation at matmul throughput, no scatter.  Valid for dense group ids
with G ≤ ~4096 (beyond that the executor falls back to segment-sum).

Accumulation uses the standard Pallas revisiting-output pattern: every grid
step maps to the same (G, V) output block, initialized at step 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hash_group_kernel(gid_ref, vals_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[0, :]                                  # (B,) int32
    vals = vals_ref[...]                                 # (V, B) f32
    G = out_ref.shape[0]
    groups = jax.lax.broadcasted_iota(jnp.int32, (G, gid.shape[0]), 0)
    onehot = (groups == gid[None, :]).astype(jnp.float32)   # (G, B)
    out_ref[...] += jnp.dot(onehot, vals.T,
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("g_pad", "block_rows",
                                             "interpret"))
def hash_group_call(gid: jax.Array, vals: jax.Array, g_pad: int, *,
                    block_rows: int = 2048, interpret: bool = False):
    """gid: (1, n) int32 — masked-out rows carry a trash group id that lands
    in a padding row (callers use g_pad - 1); vals: (V, n) f32 with V padded
    to the f32 sublane multiple.  g_pad is the padded group-domain size.
    Returns the (g_pad, V) f32 accumulator."""
    _, n = gid.shape
    V, n2 = vals.shape
    assert n == n2 and n % block_rows == 0, (n, n2, block_rows)
    steps = n // block_rows
    return pl.pallas_call(
        _hash_group_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((V, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((g_pad, V), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((g_pad, V), jnp.float32),
        interpret=interpret,
    )(gid, vals)
