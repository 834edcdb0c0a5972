"""Fused Pallas TPU kernel for ML-DSA's RejNTTPoly (FIPS 204 Algorithm 30).

Same recipe as kem/mlkem_pallas.py, which took ML-KEM encaps off the HBM
roofline: ExpandA draws k*l uniform NTT-domain polynomials per op (30 for
ML-DSA-65), and the jnp path's pairs-bitonic compaction moves ~11 GB of
HBM per 1024-batch — measured 22.6k polys-batch/s, ~45% of the whole
verify budget.  This kernel runs SHAKE-128 absorb, all 7 squeeze
permutations, 3-byte candidate extraction, and the 512-wide key/value
bitonic compaction in VMEM; HBM sees only the 21 input lane-words and the
256 output coefficients per seed.

The 23-bit candidates do not fit an int32 sort key next to the index, so
the network carries (key = reject<<10 | idx, val = candidate) register
pairs — :func:`core.sortnet.bitonic_sort_pairs_regs`, bit-identical in
output order to sig/mldsa.py:rej_ntt_poly's array formulation (asserted by
tests/test_mldsa_pallas.py; the kernel body is tested eagerly on CPU, the
full pallas_call natively on the chip).

Replaces (reference): the rejection loop inside liboqs ML-DSA
(vendor/oqs.py:506-583 via crypto/signatures.py:58-188).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.keccak_pallas import _f1600, absorb_block, block_bytes, sampler_call
from ..core.sortnet import bitonic_sort_pairs_regs, bitonic_sort_regs

Q = 8380417
RATE_WORDS = 21  # SHAKE-128 rate: 168 bytes = 21 lanes
N_SQUEEZE = 7  # 7 * 168 = 1176 bytes -> 392 candidates for 256 slots
N_CAND = 392
N_SORT = 512
N_OUT = 256


def _rej_ntt_tiles(in_hi: list, in_lo: list) -> list:
    """The full RejNTTPoly pipeline over 21 input lane-word tiles.

    Pure function of same-shaped uint32 arrays -> 256 int32 arrays; the
    Pallas kernel calls it on VMEM tiles, tests call it eagerly on CPU.
    """
    sh, sl = absorb_block(in_hi, in_lo, RATE_WORDS)

    # Squeeze 1176 bytes; each byte triple is one 23-bit candidate
    # b0 | b1<<8 | (b2 & 0x7F)<<16.
    cand = []
    for blk in range(N_SQUEEZE):
        byts = block_bytes(sh, sl, RATE_WORDS)
        for t in range(len(byts) // 3):
            b0, b1, b2 = byts[3 * t], byts[3 * t + 1], byts[3 * t + 2]
            c = (b0 | (b1 << 8) | ((b2 & 0x7F) << 16)).astype(jnp.int32)  # 23-bit bound machine-proved by qrkernel's interval analysis
            cand.append(c)
        if blk + 1 < N_SQUEEZE:
            sh, sl = _f1600(sh, sl)
    assert len(cand) == N_CAND

    # key = reject<<10 | index: accepted candidates first, spec order —
    # identical packing to sig/mldsa.py:rej_ntt_poly.
    keys = [jnp.where(c < Q, 0, 1 << 10) | i for i, c in enumerate(cand)]
    val_sent = jnp.zeros_like(cand[0])
    # unique sentinel keys, all above every real key (pairs-sort contract)
    keys += [jnp.full_like(keys[0], (1 << 11) | s) for s in range(N_SORT - N_CAND)]
    cand += [val_sent] * (N_SORT - N_CAND)
    _, cand = bitonic_sort_pairs_regs(keys, cand)
    return cand[:N_OUT]


def _rej_ntt_kernel(in_hi_ref, in_lo_ref, out_ref):
    out = _rej_ntt_tiles(
        [in_hi_ref[w] for w in range(RATE_WORDS)],
        [in_lo_ref[w] for w in range(RATE_WORDS)],
    )
    for i in range(N_OUT):
        out_ref[i] = out[i]


# --------------------------------------------------------------------------
# RejBoundedPoly (FIPS 204 Algorithm 31): SHAKE-256 nibble rejection
# --------------------------------------------------------------------------

RB_RATE_WORDS = 17  # SHAKE-256 rate: 136 bytes = 17 lanes
RB_N_SQUEEZE = 4  # 544 bytes squeezed; the first 512 feed the compaction
RB_N_SORT = 1024  # nibble candidates (= mldsa._REJ_BOUNDED_SORT), a power of 2


def _rej_bounded_tiles(in_hi: list, in_lo: list, eta: int) -> list:
    """RejBoundedPoly pipeline over 17 input lane-word tiles -> 256 nibble tiles.

    Returns the RAW accepted nibbles (0..14 / 0..8); the caller applies the
    eta-map — keeping the kernel's output identical to the jnp path's
    pre-map compaction.
    """
    sh, sl = absorb_block(in_hi, in_lo, RB_RATE_WORDS)

    bound = 15 if eta == 2 else 9
    byts = []
    for blk in range(RB_N_SQUEEZE):
        byts += block_bytes(sh, sl, RB_RATE_WORDS)
        if blk + 1 < RB_N_SQUEEZE and 2 * len(byts) < RB_N_SORT:
            sh, sl = _f1600(sh, sl)
    byts = byts[: RB_N_SORT // 2]  # first 512 bytes -> 1024 nibble candidates
    keys = []
    for byte in byts:
        for z in (byte & 0xF, byte >> 4):
            i = len(keys)
            keys.append(
                jnp.where(z < bound, 0, 1 << 16) | (i << 4) | z.astype(jnp.int32)
            )
    assert len(keys) == RB_N_SORT
    keys = bitonic_sort_regs(keys)
    return [k & 0xF for k in keys[:N_OUT]]


def _rej_bounded_kernel(in_hi_ref, in_lo_ref, out_ref, *, eta: int):
    out = _rej_bounded_tiles(
        [in_hi_ref[w] for w in range(RB_RATE_WORDS)],
        [in_lo_ref[w] for w in range(RB_RATE_WORDS)],
        eta,
    )
    for i in range(N_OUT):
        out_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def rej_bounded_words(in_hi: jax.Array, in_lo: jax.Array, *, eta: int,
                      interpret: bool = False):
    """Batched RejBoundedPoly over word-transposed padded seed blocks.

    Args:
      in_hi/in_lo: (17, B) uint32 — the padded 136-byte XOF seed block
        (rhop || n || 0x1F pad || 0x80) as hi/lo lane words, batch minor.
      eta: 2 or 4 (static; sets the nibble acceptance bound).

    Returns:
      (256, B) int32 raw accepted nibbles (pre eta-map) in [0, bound).
    """
    return sampler_call(functools.partial(_rej_bounded_kernel, eta=eta),
                        RB_RATE_WORDS, N_OUT, in_hi, in_lo, interpret=interpret)


# --------------------------------------------------------------------------
# NTT / invNTT over Z_q[X]/(X^256+1) (FIPS 204 §7.5) — VMEM-resident
# --------------------------------------------------------------------------
#
# The jnp formulation (sig/mldsa.py ntt/ntt_inv) materialises the full
# batched coefficient array between each of the 8 butterfly stages — 16 HBM
# round-trips per transform, and a sign attempt runs ~29 poly transforms
# (ntt(y) x l, invntt(w) x k, ntt(c), invntt(cs1/cs2/ct0) x l+2k).  Here a
# poly's 256 coefficients live as 256 (8, 128) int32 register tiles across
# 1024 lanes; all 1024 butterflies run in VMEM and HBM sees one read + one
# write.  Same register-resident recipe as the sampler kernels above.

from ..pyref.mldsa_ref import ZETAS as _ZETAS_PY

_N = 256
_N_INV = pow(_N, -1, Q)
# the butterflies in lax primitives, not jnp operators, for trace time (see
# core/keccak_pallas.py); the jaxpr is the same
_Q = np.int32(Q)


def _mm_zeta(a, z: int):
    """(a * z) % Q for an int32 tile a in [0, q) and STATIC z in [0, q).

    Horner over 8-bit limbs of z keeps every intermediate under 2**31
    (identical arithmetic to sig/mldsa.py:_mm with b static).  The limb
    bounds are machine-checked: qrkernel's interval analysis proves every
    product/shift below from the two declared contracts."""
    # qrkernel: assume a in [0, Q) — FIPS 204 §7.5: NTT butterfly operands are mod-q residues (every caller reduces % Q first)
    # qrkernel: assume z in [0, Q) — zeta table entries are powers of the 512th root of unity mod q
    b2, b1, b0 = z >> 16, (z >> 8) & 0xFF, z & 0xFF
    r = lax.rem(lax.mul(a, np.int32(b2)), _Q)
    r = lax.rem(lax.add(lax.rem(lax.shift_left(r, np.int32(8)), _Q),
                        lax.rem(lax.mul(a, np.int32(b1)), _Q)), _Q)
    r = lax.rem(lax.add(lax.rem(lax.shift_left(r, np.int32(8)), _Q),
                        lax.rem(lax.mul(a, np.int32(b0)), _Q)), _Q)
    return r


def ntt_tiles(f: list) -> list:
    """256 int32 tiles in [0, q) -> NTT domain (bit-exact vs mldsa.ntt)."""
    f = list(f)
    k = 1
    length = 128
    while length >= 1:
        groups = _N // (2 * length)
        for g in range(groups):
            z = int(_ZETAS_PY[k + g])
            base = g * 2 * length
            for j in range(length):
                i0, i1 = base + j, base + length + j
                t = _mm_zeta(f[i1], z)
                f[i0], f[i1] = (lax.rem(lax.add(f[i0], t), _Q),
                                lax.rem(lax.add(lax.sub(f[i0], t), _Q), _Q))
        k += groups
        length //= 2
    return f


def ntt_inv_tiles(f: list) -> list:
    """Inverse transform; bit-exact vs mldsa.ntt_inv."""
    f = list(f)
    k = 255
    length = 1
    while length <= 128:
        groups = _N // (2 * length)
        zs = [int(_ZETAS_PY[k - groups + 1 + i]) for i in range(groups)][::-1]
        for g in range(groups):
            base = g * 2 * length
            for j in range(length):
                i0, i1 = base + j, base + length + j
                s = lax.rem(lax.add(f[i0], f[i1]), _Q)
                t = _mm_zeta(lax.rem(lax.add(lax.sub(f[i1], f[i0]), _Q), _Q), zs[g])
                f[i0], f[i1] = s, t
        k -= groups
        length *= 2
    return [_mm_zeta(x, _N_INV) for x in f]


def _ntt_kernel(in_ref, out_ref, *, inverse: bool):
    f = [in_ref[i] for i in range(_N)]
    out = ntt_inv_tiles(f) if inverse else ntt_tiles(f)
    for i in range(_N):
        out_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def ntt_words(x: jax.Array, *, inverse: bool = False, interpret: bool = False):
    """Batched (inv)NTT over words layout.

    Args:
      x: (256, L) int32 coefficients in [0, q), lanes batch-minor (L is
        padded to the 1024-lane tile internally).

    Returns:
      (256, L) int32 transformed coefficients.
    """
    from jax.experimental import pallas as pl

    from ..core.keccak_pallas import _TL, _TS, BT

    n, l = x.shape
    assert n == _N
    lp = -(-l // BT) * BT
    if lp != l:
        x = jnp.pad(x, ((0, 0), (0, lp - l)))
    x = x.reshape(_N, lp // _TL, _TL)
    out = pl.pallas_call(
        functools.partial(_ntt_kernel, inverse=inverse),
        grid=(lp // BT,),
        in_specs=[pl.BlockSpec((_N, _TS, _TL), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((_N, _TS, _TL), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((_N, lp // _TL, _TL), jnp.int32),
        interpret=interpret,
    )(x)
    return out.reshape(_N, lp)[:, :l]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rej_ntt_words(in_hi: jax.Array, in_lo: jax.Array, *, interpret: bool = False):
    """Batched RejNTTPoly over word-transposed padded seed blocks.

    Args:
      in_hi/in_lo: (21, B) uint32 — the padded 168-byte XOF seed block
        (rho || s || r || 0x1F pad || 0x80) as hi/lo lane words, batch minor.

    Returns:
      (256, B) int32 NTT-domain coefficients in [0, q).
    """
    return sampler_call(_rej_ntt_kernel, RATE_WORDS, N_OUT, in_hi, in_lo,
                        interpret=interpret)
