"""Pallas TPU kernel: bitonic sort over a column block.

MonetDB's ORDER BY sorts a column and applies the permutation to the
others.  Comparison sorts with data-dependent control flow don't map to
the TPU's vector units, so the TPU-native restatement (DESIGN.md §3) is
the classic *bitonic network*: a fixed, data-independent sequence of
compare-exchange stages — for a 2^k block, k·(k+1)/2 stages of purely
element-wise min/max/select over lane-aligned halves, every one of which
the VPU executes at full width.  The partner of lane ``i`` at substage
``j`` is ``i ^ j``; because ``j`` is a power of two that exchange is a
reshape + flip, not a gather.

The kernel sorts (key, index) pairs: ties break on the original index,
which makes the network's output *identical* to a stable sort of the keys
— so the host oracle is ``np.argsort(kind="stable")`` and the permutation
can re-order payload columns exactly like MonetDB's tail projection.

One grid step sorts one block; block-local sorts are merged by the ops
shim (or consumed directly for top-N, where only the block prefix
survives).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cswap(k, ix, j: int, ksz: int):
    """One bitonic compare-exchange substage over (key, index) lanes."""
    n = k.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    kp = k.reshape(n // (2 * j), 2, j)[:, ::-1, :].reshape(n)
    ip = ix.reshape(n // (2 * j), 2, j)[:, ::-1, :].reshape(n)
    # ascending run when bit ksz is clear; lane keeps the smaller pair
    # member when its side matches the run direction
    keep_min = ((i & ksz) == 0) == ((i & j) == 0)
    partner_lt = (kp < k) | ((kp == k) & (ip < ix))      # stable tie-break
    take_partner = keep_min == partner_lt
    return (jnp.where(take_partner, kp, k),
            jnp.where(take_partner, ip, ix))


def _bitonic_kernel(keys_ref, idx_ref, out_k_ref, out_i_ref):
    k = keys_ref[0, :]
    ix = idx_ref[0, :]
    n = k.shape[0]
    ksz = 2
    while ksz <= n:                      # static: unrolled at trace time
        j = ksz // 2
        while j >= 1:
            k, ix = _cswap(k, ix, j, ksz)
            j //= 2
        ksz *= 2
    out_k_ref[0, :] = k
    out_i_ref[0, :] = ix


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_call(keys: jax.Array, idx: jax.Array, *,
                      interpret: bool = False):
    """keys: (1, n) f32 with n a power of two (callers pad with +inf);
    idx: (1, n) int32 original positions.  Returns (sorted keys, perm),
    ascending, ties broken by original position (= stable)."""
    _, n = keys.shape
    assert n & (n - 1) == 0, n
    return pl.pallas_call(
        _bitonic_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(keys, idx)
