"""Gather-free bitonic sorting networks for TPU.

XLA's sort/argsort, lax.top_k, take_along_axis and scatter all serialise on
TPU for per-lane dynamic indices (measured 120-1160 ms for a (36864, 448)
compaction — the entire ML-KEM encaps budget).  A bitonic network expressed
as reshapes + min/max + where with *static* direction masks lowers to pure
vectorised VPU ops: the same compaction runs in ~13 ms.

Used for the rejection-sampling compactions in kem/mlkem.py (SampleNTT) and
sig/mldsa.py (RejNTT / SampleInBall), where spec order of accepted candidates
must be preserved: callers embed the candidate index in the sort key, making
the (unstable) bitonic network a deterministic stable partition.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def bitonic_sort(x: jax.Array) -> jax.Array:
    """Sort ascending along the last axis; length must be a power of two."""
    n = x.shape[-1]
    stages = int(np.log2(n))
    assert 1 << stages == n, f"bitonic length must be a power of 2, got {n}"
    for k in range(1, stages + 1):
        for j in range(k - 1, -1, -1):
            d = 1 << j
            xr = x.reshape(x.shape[:-1] + (n // (2 * d), 2, d))
            a, b = xr[..., 0, :], xr[..., 1, :]
            idx = np.arange(n // (2 * d))[:, None] * 2 * d + np.arange(d)[None, :]
            desc = jnp.asarray(((idx >> k) & 1).astype(bool))
            lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
            x = jnp.stack(
                [jnp.where(desc, hi, lo), jnp.where(desc, lo, hi)], axis=-2
            ).reshape(x.shape)
    return x


def bitonic_sort_regs(regs: list) -> list:
    """Bitonic-sort a Python list of same-shaped arrays, elementwise-ascending.

    The network from :func:`bitonic_sort` with the sorted axis unrolled into
    the *list* dimension: element ``i`` of the result holds, lane-for-lane,
    the i-th smallest value across the input list.  Every compare-exchange is
    a static ``minimum``/``maximum`` pair between two named arrays — no
    reshapes, rolls or gathers — which makes the helper usable inside Pallas
    TPU kernels where each list element is one resident vector tile
    (kem/mlkem_pallas.py keeps all 512 SampleNTT candidates in VMEM this way).
    ``len(regs)`` must be a power of two.  Written in lax primitives, not
    jnp operators: a 1024-element network is ~56k of them, and each jnp
    operator on a tracer goes through a jit of its own (same jaxpr).
    """
    n = len(regs)
    stages = int(np.log2(n))
    assert 1 << stages == n, f"bitonic length must be a power of 2, got {n}"
    regs = list(regs)
    for k in range(1, stages + 1):
        for j in range(k - 1, -1, -1):
            d = 1 << j
            for i in range(n):
                p = i | d
                if p == i:
                    continue
                lo = lax.min(regs[i], regs[p])
                hi = lax.max(regs[i], regs[p])
                if (i >> k) & 1:
                    regs[i], regs[p] = hi, lo
                else:
                    regs[i], regs[p] = lo, hi
    return regs


def bitonic_sort_pairs_regs(keys: list, vals: list) -> tuple[list, list]:
    """Register-list variant of :func:`bitonic_sort_pairs`.

    Sorts ``keys`` elementwise-ascending across the list dimension, carrying
    ``vals`` through the same exchanges — the pairs analog of
    :func:`bitonic_sort_regs`, for Pallas kernels whose candidate values
    don't fit in an int32 key beside the index (sig/mldsa_pallas.py's 23-bit
    RejNTT candidates).  Keys must be elementwise-unique across the list.
    """
    n = len(keys)
    stages = int(np.log2(n))
    assert 1 << stages == n, f"bitonic length must be a power of 2, got {n}"
    keys, vals = list(keys), list(vals)
    for k in range(1, stages + 1):
        for j in range(k - 1, -1, -1):
            d = 1 << j
            for i in range(n):
                p = i | d
                if p == i:
                    continue
                swap = (lax.gt(keys[i], keys[p]) if not ((i >> k) & 1)
                        else lax.lt(keys[i], keys[p]))
                ki = lax.select(swap, keys[p], keys[i])
                kp = lax.select(swap, keys[i], keys[p])
                vi = lax.select(swap, vals[p], vals[i])
                vp = lax.select(swap, vals[i], vals[p])
                keys[i], keys[p] = ki, kp
                vals[i], vals[p] = vi, vp
    return keys, vals


def bitonic_sort_pairs(key: jax.Array, val: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort ``key`` ascending along the last axis, carrying ``val`` along.

    Keys must be unique per lane (callers embed the element index), so the
    network's instability is unobservable.
    """
    n = key.shape[-1]
    stages = int(np.log2(n))
    assert 1 << stages == n, f"bitonic length must be a power of 2, got {n}"
    for k in range(1, stages + 1):
        for j in range(k - 1, -1, -1):
            d = 1 << j
            kr = key.reshape(key.shape[:-1] + (n // (2 * d), 2, d))
            vr = val.reshape(val.shape[:-1] + (n // (2 * d), 2, d))
            ka, kb = kr[..., 0, :], kr[..., 1, :]
            va, vb = vr[..., 0, :], vr[..., 1, :]
            idx = np.arange(n // (2 * d))[:, None] * 2 * d + np.arange(d)[None, :]
            desc = jnp.asarray(((idx >> k) & 1).astype(bool))
            swap = (ka > kb) ^ desc
            ka2 = jnp.where(swap, kb, ka)
            kb2 = jnp.where(swap, ka, kb)
            va2 = jnp.where(swap, vb, va)
            vb2 = jnp.where(swap, va, vb)
            key = jnp.stack([ka2, kb2], axis=-2).reshape(key.shape)
            val = jnp.stack([va2, vb2], axis=-2).reshape(val.shape)
    return key, val
