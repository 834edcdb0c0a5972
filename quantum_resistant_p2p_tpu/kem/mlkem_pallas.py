"""Fused Pallas TPU kernel for ML-KEM's SampleNTT (FIPS 203 Algorithm 7).

Why: XLA cost analysis attributes ~85% of a batched encaps program's HBM
traffic to SampleNTT — 3.52 of 4.14 GB per 512-batch (2.4 GB of it the
bitonic compaction, the rest candidate extraction) — and the op is purely
memory-bound (expected; the roofline share is not measured on this chip).
This kernel runs the ENTIRE
SampleNTT pipeline per seed — SHAKE-128 absorb, 4 squeeze permutations,
byte-triple candidate extraction, rejection-key packing, and the 512-wide
bitonic compaction network — inside one Pallas program with every
intermediate resident in VMEM.  HBM traffic per seed drops from ~7 MB to
~1.2 KB (21 input lane-words + 256 output coefficients).

Layout (same recipe as core/keccak_pallas.py): batch lives on the two minor
dimensions — each logical scalar (a Keccak lane word, one of the 512
candidate slots) is an ``(8, 128)`` uint32 tile spanning 1024 sponge
instances, so the whole pipeline is full-width VPU ops between named
registers.  The compaction uses :func:`core.sortnet.bitonic_sort_regs`,
whose compare-exchanges become static min/max pairs between resident tiles
(the array version's reshapes would cross the lane dimension, which Mosaic
penalises heavily).

Spec correspondence: identical output to kem/mlkem.py:sample_ntt (the
fixed-672-byte-squeeze formulation, P[shortfall] < 1e-38) — byte-for-byte
equality is asserted by tests/test_mlkem_pallas.py (kernel body, eagerly on
CPU) and was verified for the native pallas_call against the jnp path on
TPU v5e at B=1500.

Replaces (reference): the rejection-sampling loop inside liboqs ML-KEM
(vendor/oqs.py:310-390 reaches it via OQS_KEM_keypair/encaps/decaps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.keccak_pallas import _f1600, absorb_block, block_bytes, sampler_call
from ..core.sortnet import bitonic_sort_regs

Q = 3329
RATE_WORDS = 21  # SHAKE-128 rate: 168 bytes = 21 lanes
N_SQUEEZE = 4  # 4 * 168 = 672 bytes -> 448 candidates for 256 slots
N_CAND = 448
N_SORT = 512  # candidates padded to the next power of two
N_OUT = 256


def _sample_ntt_tiles(in_hi: list, in_lo: list) -> list:
    """The full SampleNTT pipeline over 21 input lane-word tiles.

    Pure function of same-shaped uint32 arrays -> 256 int32 arrays; the
    Pallas kernel calls it on VMEM-resident (8, 128) tiles, and the test
    suite calls it directly on plain arrays (interpret mode would execute
    the ~57k-op body orders of magnitude too slowly).
    """
    sh, sl = absorb_block(in_hi, in_lo, RATE_WORDS)

    # Squeeze 672 bytes; each byte triple (b0, b1, b2) yields two 12-bit
    # candidates d1 = b0 + 256*(b1 mod 16), d2 = (b1 // 16) + 16*b2.
    cand = []
    for blk in range(N_SQUEEZE):
        byts = block_bytes(sh, sl, RATE_WORDS)
        for t in range(len(byts) // 3):
            b0, b1, b2 = byts[3 * t], byts[3 * t + 1], byts[3 * t + 2]
            cand.append(b0 | ((b1 & 0xF) << 8))  # 12-bit bound machine-proved by qrkernel's interval analysis
            cand.append((b1 >> 4) | (b2 << 4))
        if blk + 1 < N_SQUEEZE:
            sh, sl = _f1600(sh, sl)
    assert len(cand) == N_CAND

    # Rejection keys: accepted (bit 21 clear) before rejected, candidate
    # order preserved via the index field, 12-bit value in the low bits —
    # bit-identical to kem/mlkem.py:sample_ntt's packing.  Keys fit in 23
    # bits, so the sort runs in int32 (Mosaic has no unsigned vector min).
    keys = [
        jnp.where(c < Q, 0, 1 << 21) | (i << 12) | c.astype(jnp.int32)
        for i, c in enumerate(cand)
    ]
    sentinel = jnp.full_like(keys[0], 1 << 22)
    keys += [sentinel] * (N_SORT - N_CAND)
    keys = bitonic_sort_regs(keys)
    return [keys[i] & 0xFFF for i in range(N_OUT)]


def _sample_ntt_kernel(in_hi_ref, in_lo_ref, out_ref):
    out = _sample_ntt_tiles(
        [in_hi_ref[w] for w in range(RATE_WORDS)],
        [in_lo_ref[w] for w in range(RATE_WORDS)],
    )
    for i in range(N_OUT):
        out_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sample_ntt_words(in_hi: jax.Array, in_lo: jax.Array, *, interpret: bool = False):
    """Batched SampleNTT over word-transposed padded seed blocks.

    Args:
      in_hi/in_lo: (21, B) uint32 — the padded 168-byte XOF seed block
        (rho || j || i || 0x1F pad || 0x80) as hi/lo lane words, batch minor.

    Returns:
      (256, B) int32 NTT-domain polynomial coefficients in [0, q).
    """
    return sampler_call(_sample_ntt_kernel, RATE_WORDS, N_OUT, in_hi, in_lo,
                        interpret=interpret)


# --------------------------------------------------------------------------
# PRF + SamplePolyCBD (FIPS 203 Algorithms 7/8): SHAKE-256 -> CBD_eta poly
# --------------------------------------------------------------------------

CBD_RATE_WORDS = 17  # SHAKE-256 rate: 136 bytes = 17 lanes


def _cbd_tiles(in_hi: list, in_lo: list, eta: int) -> list:
    """PRF_eta + CBD_eta over 17 input lane-word tiles -> 256 coeff tiles.

    Squeezes 64*eta bytes (one block for eta=2, two for eta=3) and forms
    coefficient i from bit run [2*eta*i, 2*eta*(i+1)): sum of the first
    eta bits minus the sum of the second eta, mod q — the same byte-major
    LSB-first bit order as kem/mlkem.py:sample_poly_cbd.
    """
    sh, sl = absorb_block(in_hi, in_lo, CBD_RATE_WORDS)
    byts = block_bytes(sh, sl, CBD_RATE_WORDS)
    if 64 * eta > 8 * CBD_RATE_WORDS:  # eta=3: 192 bytes needs a second block
        sh, sl = _f1600(sh, sl)
        byts += block_bytes(sh, sl, CBD_RATE_WORDS)

    def bit(p: int):
        # int32 from the start: the x - y below must not wrap in uint32
        return ((byts[p // 8] >> (p % 8)) & 1).astype(jnp.int32)

    out = []
    for i in range(N_OUT):
        base = 2 * eta * i
        x = bit(base)
        for j in range(1, eta):
            x = x + bit(base + j)
        for j in range(eta):
            x = x - bit(base + eta + j)
        out.append(jnp.where(x < 0, x + Q, x))
    return out


def _cbd_kernel(in_hi_ref, in_lo_ref, out_ref, *, eta: int):
    out = _cbd_tiles(
        [in_hi_ref[w] for w in range(CBD_RATE_WORDS)],
        [in_lo_ref[w] for w in range(CBD_RATE_WORDS)],
        eta,
    )
    for i in range(N_OUT):
        out_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def cbd_words(in_hi: jax.Array, in_lo: jax.Array, *, eta: int,
              interpret: bool = False):
    """Batched PRF+CBD over word-transposed padded seed blocks.

    Args:
      in_hi/in_lo: (17, B) uint32 — the padded 136-byte PRF seed block
        (s || n || 0x1F pad || 0x80) as hi/lo lane words, batch minor.
      eta: 2 or 3 (static).

    Returns:
      (256, B) int32 CBD_eta coefficients in [0, q).
    """
    return sampler_call(functools.partial(_cbd_kernel, eta=eta),
                        CBD_RATE_WORDS, N_OUT, in_hi, in_lo, interpret=interpret)


# --------------------------------------------------------------------------
# NTT over Z_q[X]/(X^256+1), q = 3329 (FIPS 203 §4.3) — VMEM-resident
# --------------------------------------------------------------------------
#
# Same register-resident recipe as sig/mldsa_pallas.py:ntt_tiles, but the
# small modulus makes the butterflies cheaper: q^2 = 11_082_241 < 2**31, so
# a zeta product is ONE int32 multiply + remainder — no limb split.  The
# jnp formulation (kem/mlkem.py ntt/ntt_inv) materialises the full batched
# coefficient array between each of the 7 butterfly layers — 14 HBM
# round-trips per transform, and an encaps runs k NTTs + k+1 invNTTs.
# Here a poly's 256 coefficients are 256 (8, 128) int32 register tiles
# spanning 1024 lanes; HBM sees one read + one write per transform, and the
# fused CBD->NTT kernel below sees NONE (the CBD output never leaves VMEM).

from ..pyref.mlkem_ref import ZETAS as _ZETAS_PY

_N = 256
_N_INV = pow(128, -1, Q)  # 3303: ML-KEM's NTT has 128 base pairs, not 256 slots
# the butterflies in lax primitives, not jnp operators, for trace time (see
# core/keccak_pallas.py); the jaxpr is the same
_Q = np.int32(Q)


def _mul_zeta(a, z: int):
    """(a * z) % Q for an int32 tile a in [0, q) and STATIC z in [0, q).

    q^2 < 2**31 so the product cannot overflow int32 (unlike ML-DSA's
    q = 8380417, which needs the Horner limb split) — the bound is
    machine-checked by qrkernel's interval analysis from the contracts."""
    # qrkernel: assume a in [0, Q) — FIPS 203 §4.3: butterfly operands are mod-q residues (every caller reduces % Q first)
    # qrkernel: assume z in [0, Q) — zeta table entries are powers of the 256th root of unity mod q
    return lax.rem(lax.mul(a, np.int32(z)), _Q)


def ntt_tiles(f: list) -> list:
    """256 int32 tiles in [0, q) -> NTT domain (bit-exact vs mlkem.ntt)."""
    f = list(f)
    k = 1
    length = 128
    while length >= 2:  # ML-KEM stops at length 2: 128 degree-1 residues
        groups = _N // (2 * length)
        for g in range(groups):
            z = int(_ZETAS_PY[k + g])
            base = g * 2 * length
            for j in range(length):
                i0, i1 = base + j, base + length + j
                t = _mul_zeta(f[i1], z)
                f[i0], f[i1] = (lax.rem(lax.add(f[i0], t), _Q),
                                lax.rem(lax.add(lax.sub(f[i0], t), _Q), _Q))
        k += groups
        length //= 2
    return f


def ntt_inv_tiles(f: list) -> list:
    """Inverse transform; bit-exact vs mlkem.ntt_inv."""
    f = list(f)
    k = 127
    length = 2
    while length <= 128:
        groups = _N // (2 * length)
        zs = [int(_ZETAS_PY[k - groups + 1 + i]) for i in range(groups)][::-1]
        for g in range(groups):
            base = g * 2 * length
            for j in range(length):
                i0, i1 = base + j, base + length + j
                s = lax.rem(lax.add(f[i0], f[i1]), _Q)
                t = _mul_zeta(lax.rem(lax.add(lax.sub(f[i1], f[i0]), _Q), _Q), zs[g])
                f[i0], f[i1] = s, t
        k -= groups
        length *= 2
    return [_mul_zeta(x, _N_INV) for x in f]


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


# --------------------------------------------------------------------------
# Fused PRF + SamplePolyCBD + NTT: SHAKE-256 -> CBD_eta -> NTT, one kernel
# --------------------------------------------------------------------------
#
# The noise polynomials that feed matrix products (s, e at keygen; y at
# encrypt) are consumed ONLY in the NTT domain, so the separate cbd_words
# -> HBM -> ntt jnp-layer pipeline pays a full (256, B) round-trip plus 14
# layer materialisations for data that never needed to exist outside VMEM.
# This kernel squeezes the sponge, forms the CBD sums, and runs all 7
# butterfly layers on the register tiles before anything is written back.


def _cbd_ntt_tiles(in_hi: list, in_lo: list, eta: int) -> list:
    """PRF_eta + CBD_eta + NTT over 17 input lane-word tiles.

    Composition of the two tile pipelines above — _cbd_tiles' outputs are
    already reduced to [0, q), the domain ntt_tiles' contracts require."""
    return ntt_tiles(_cbd_tiles(in_hi, in_lo, eta))


def _cbd_ntt_kernel(in_hi_ref, in_lo_ref, out_ref, *, eta: int):
    out = _cbd_ntt_tiles(
        [in_hi_ref[w] for w in range(CBD_RATE_WORDS)],
        [in_lo_ref[w] for w in range(CBD_RATE_WORDS)],
        eta,
    )
    for i in range(N_OUT):
        out_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def cbd_ntt_words(in_hi: jax.Array, in_lo: jax.Array, *, eta: int,
                  interpret: bool = False):
    """Batched PRF+CBD+NTT over word-transposed padded seed blocks.

    Same contract as cbd_words but the coefficients come back already in
    the NTT domain — the intermediate CBD polynomial never touches HBM.

    Returns:
      (256, B) int32 NTT-domain coefficients in [0, q).
    """
    return sampler_call(functools.partial(_cbd_ntt_kernel, eta=eta),
                        CBD_RATE_WORDS, N_OUT, in_hi, in_lo, interpret=interpret)
