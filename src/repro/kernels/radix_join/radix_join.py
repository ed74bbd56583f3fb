"""Pallas TPU kernels: radix-partitioned hash join.

MonetDB's join auto-builds a hash table on the smaller input and probes it
with the larger one (paper §3.1).  Pointer-chasing hash tables are hostile
to the TPU's vector/matrix units, so the TPU-native restatement (DESIGN.md
§3) follows the same move as ``hash_group``: radix-partition both inputs on
the low key bits so each partition's *local* key domain is small enough to
tile in VMEM, then express the partition-local hash table as a dense
(D, V) matrix and lower both halves of the join to one-hot matmuls:

    build:  btab[d, v]  = Σ_rows onehot(code)[row, d] · payload[row, v]
    probe:  out[row, v] = Σ_d    onehot(code)[row, d] · btab[d, v]

The build is a scatter-by-matmul (identical shape to grouped aggregation —
the MXU executes a (D × B) @ (B × V) product per tile); the probe is a
gather-by-matmul ((B × D) @ (D × V)).  Slot 0 of the payload carries the
build-side presence count, so a probe row's gathered count > 0 *is* the
inner-join match bit and the remaining lanes are the joined build columns —
build + probe of one partition is a fused pair of matmul kernels with no
per-row control flow.

Valid for unique build keys (the engine's device join verifies uniqueness
and falls back otherwise); partitioning keeps D ≈ domain / n_partitions so
a few-thousand-row tile fits VMEM even for large key domains.

Accumulation uses the standard Pallas revisiting-output pattern on the
build side: every grid step maps to the same (D, V) output block,
initialized at step 0.  The probe side writes disjoint (B, V) blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _radix_build_kernel(code_ref, vals_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    code = code_ref[0, :]                                # (B,) int32
    vals = vals_ref[...]                                 # (V, B) f32
    D = out_ref.shape[0]
    doms = jax.lax.broadcasted_iota(jnp.int32, (D, code.shape[0]), 0)
    onehot = (doms == code[None, :]).astype(jnp.float32)    # (D, B)
    out_ref[...] += jnp.dot(onehot, vals.T,
                            preferred_element_type=jnp.float32)


def _radix_probe_kernel(code_ref, btab_ref, out_ref):
    code = code_ref[0, :]                                # (B,) int32
    btab = btab_ref[...]                                 # (D, V) f32
    D = btab.shape[0]
    doms = jax.lax.broadcasted_iota(jnp.int32, (code.shape[0], D), 1)
    onehot = (doms == code[:, None]).astype(jnp.float32)    # (B, D)
    out_ref[...] = jnp.dot(onehot, btab,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("d_pad", "block_rows",
                                             "interpret"))
def radix_build_call(code: jax.Array, vals: jax.Array, d_pad: int, *,
                     block_rows: int = 2048, interpret: bool = False):
    """code: (1, n) int32 partition-local key codes — masked-out rows carry
    a trash code that lands in a padding row (callers use d_pad - 1); vals:
    (V, n) f32 payload with V padded to the f32 sublane multiple and lane 0
    holding the presence indicator.  Returns the (d_pad, V) f32 dense
    partition-local hash table."""
    _, n = code.shape
    V, n2 = vals.shape
    assert n == n2 and n % block_rows == 0, (n, n2, block_rows)
    steps = n // block_rows
    return pl.pallas_call(
        _radix_build_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((V, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((d_pad, V), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad, V), jnp.float32),
        interpret=interpret,
    )(code, vals)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def radix_probe_call(code: jax.Array, btab: jax.Array, *,
                     block_rows: int = 2048, interpret: bool = False):
    """code: (1, n) int32 partition-local probe codes (trash code = the
    padding row, whose presence count is 0, so padded probes simply miss);
    btab: (D, V) f32 build table from ``radix_build_call``.  Returns the
    (n, V) f32 gathered payload; lane 0 > 0 marks an inner-join match."""
    _, n = code.shape
    D, V = btab.shape
    assert n % block_rows == 0, (n, block_rows)
    steps = n // block_rows
    return pl.pallas_call(
        _radix_probe_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((D, V), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, V), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, V), jnp.float32),
        interpret=interpret,
    )(code, btab)
