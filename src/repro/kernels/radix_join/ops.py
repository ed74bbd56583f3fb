"""Dispatch shim for radix_join: partitioning + padding + assembly.

``radix_join`` joins integer probe keys against *unique* integer build keys
and gathers the build payload for every matching probe row.  The radix
partitioning (bucket = low key bits) happens at this layer: each bucket's
local key domain is ``domain / n_buckets``-sized, so the dense partition
tables the Pallas kernels operate on stay VMEM-tileable no matter how large
the global key domain is.  ``use_pallas=False`` runs a numpy mirror of the
identical partition/build/probe plan — the differential tests pin the two
paths against each other and against ``ref.radix_join_ref``.
"""

from __future__ import annotations

import numpy as np

from .radix_join import radix_build_call, radix_probe_call


def _pad8(x: int) -> int:
    return -(-x // 8) * 8


def _pad_to(n: int, block: int) -> int:
    return -(-max(n, 1) // block) * block


def radix_join(build_keys: np.ndarray, build_vals: np.ndarray,
               probe_keys: np.ndarray, *, n_bits: int = 4,
               block_rows: int = 2048, interpret: bool = False,
               use_pallas: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """build_keys: (nb,) int, unique; build_vals: (V, nb) float;
    probe_keys: (np,) int.  Returns ``(matched, gathered)`` where
    ``matched`` is the (np,) bool inner-join bit and ``gathered`` the
    (np, V) float64 build payload (zeros on misses), in probe order."""
    build_keys = np.asarray(build_keys, dtype=np.int64)
    probe_keys = np.asarray(probe_keys, dtype=np.int64)
    V, nb = build_vals.shape
    assert nb == build_keys.shape[0]
    n_parts = 1 << n_bits
    mask = n_parts - 1
    lo = int(min(build_keys.min(initial=0), probe_keys.min(initial=0)))
    bk = build_keys - lo
    pk = probe_keys - lo
    hi = int(max(bk.max(initial=0), pk.max(initial=0)))
    # bucket on the low bits; the local code is the high bits, so every
    # partition's dense domain is domain >> n_bits
    d_local = (hi >> n_bits) + 1
    d_pad = _pad8(d_local + 1)                  # +1 trash row
    Vp = _pad8(V + 1)                           # +1 presence lane

    b_bucket = (bk & mask).astype(np.int64)
    p_bucket = (pk & mask).astype(np.int64)
    b_order = np.argsort(b_bucket, kind="stable")
    p_order = np.argsort(p_bucket, kind="stable")
    b_counts = np.bincount(b_bucket, minlength=n_parts)
    p_counts = np.bincount(p_bucket, minlength=n_parts)
    b_starts = np.concatenate([[0], np.cumsum(b_counts)])
    p_starts = np.concatenate([[0], np.cumsum(p_counts)])

    matched = np.zeros(probe_keys.shape[0], dtype=bool)
    gathered = np.zeros((probe_keys.shape[0], V), dtype=np.float64)
    if use_pallas:
        import jax.numpy as jnp
    for p in range(n_parts):
        bi = b_order[b_starts[p]:b_starts[p + 1]]
        pi = p_order[p_starts[p]:p_starts[p + 1]]
        if pi.size == 0 or bi.size == 0:
            continue
        b_code = (bk[bi] >> n_bits).astype(np.int32)
        p_code = (pk[pi] >> n_bits).astype(np.int32)
        nbp = _pad_to(bi.size, block_rows)
        npp = _pad_to(pi.size, block_rows)
        bc = np.full(nbp, d_pad - 1, dtype=np.int32)
        bc[:bi.size] = b_code
        bv = np.zeros((Vp, nbp), dtype=np.float32)
        bv[0, :bi.size] = 1.0                   # presence lane
        bv[1:V + 1, :bi.size] = build_vals[:, bi].astype(np.float32)
        pc = np.full(npp, d_pad - 1, dtype=np.int32)
        pc[:pi.size] = p_code
        if use_pallas:
            btab = radix_build_call(jnp.asarray(bc[None, :]),
                                    jnp.asarray(bv), d_pad,
                                    block_rows=block_rows,
                                    interpret=interpret)
            btab = np.array(btab)
            btab[d_pad - 1, :] = 0.0            # trash row never matches
            out = radix_probe_call(jnp.asarray(pc[None, :]),
                                   jnp.asarray(btab),
                                   block_rows=block_rows,
                                   interpret=interpret)
            out = np.asarray(out, dtype=np.float64)
        else:
            btab = np.zeros((d_pad, Vp), dtype=np.float64)
            np.add.at(btab, bc, bv.T.astype(np.float64))
            btab[d_pad - 1, :] = 0.0
            out = btab[pc]
        matched[pi] = out[:pi.size, 0] > 0
        gathered[pi] = out[:pi.size, 1:V + 1]
    return matched, gathered
