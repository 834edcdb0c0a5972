"""Bitsliced AES-128-ECB — table-free boolean circuits on the TPU VPU.

The gather S-box (core/aes.py) is the canonical TPU anti-pattern: per-lane
dynamic ``jnp.take`` serialises, and FrodoKEM-AES runs 2.6M of them per
640x640 A-matrix (15 encaps/s on an earlier platform).  Bitslicing is the
canonical counter: the state is held as 128 bit-planes packed 32 blocks per
uint32 lane, SubBytes becomes a boolean circuit evaluated on whole planes
(pure AND/XOR — ideal VPU material), ShiftRows a static plane permutation,
MixColumns a handful of plane XORs.

Two S-box circuits ship.  The default is the hand-optimised
**Boyar-Peralta 113-gate circuit** (32 AND + 81 XOR/XNOR, the public
standard for bitsliced software AES) — ~6x fewer plane-ops per SubBytes
than the derived circuit below.  The DERIVED circuit stays as the
independent cross-check: squaring and the affine map are GF(2^8)-linear
(8x8 bit matrices computed from the field at import), multiplication is
schoolbook partial products + a computed reduction matrix, and inversion
is the 4-multiply/7-square addition chain for b^254 = b^-1.  The two
circuits and the table construction are asserted equal over all 256
inputs (tests/test_frodo.py); ``QRP2P_AES_DERIVED_SBOX=1`` selects the
derived circuit for A/B.

Layout: state planes (8 bits, 16 bytes, *lead, W) uint32, W = ceil(B/32)
blocks packed along the minor axis; round keys broadcast over W.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .aes import _SBOX, key_schedule  # noqa: F401 (key_schedule re-exported)

_POLY = 0x11B


def _gf_mul_int(a: int, b: int) -> int:
    r = 0
    while b:
        if b & 1:
            r ^= a
        b >>= 1
        a <<= 1
        if a & 0x100:
            a ^= _POLY
    return r


def _linear_matrix(fn) -> np.ndarray:
    """8x8 bit matrix M of a GF(2)-linear byte map: out_bit[i] spans M[i]."""
    m = np.zeros((8, 8), dtype=np.uint8)
    for j in range(8):
        out = fn(1 << j)
        for i in range(8):
            m[i, j] = (out >> i) & 1
    return m


_SQ = _linear_matrix(lambda x: _gf_mul_int(x, x))
# affine part of the S-box: y = A(x) ^ 0x63 with A(x) = x ^ rotl1..rotl4
_AFF = _linear_matrix(
    lambda x: x ^ (((x << 1) | (x >> 7)) & 0xFF) ^ (((x << 2) | (x >> 6)) & 0xFF)
    ^ (((x << 3) | (x >> 5)) & 0xFF) ^ (((x << 4) | (x >> 4)) & 0xFF)
)
# x^(8+k) mod poly, k = 0..6 — reduction rows for schoolbook products
_RED = np.zeros((7, 8), dtype=np.uint8)
for _k in range(7):
    _v = 1 << (8 + _k)
    # reduce by repeated xor of shifted modulus
    for _sh in range(6, -1, -1):
        if _v & (0x100 << _sh):
            _v ^= _POLY << _sh
    for _i in range(8):
        _RED[_k, _i] = (_v >> _i) & 1

# ShiftRows on column-major state bytes (same table as core/aes.py)
_SHIFT = np.array([0, 5, 10, 15, 4, 9, 14, 3, 8, 13, 2, 7, 12, 1, 6, 11])

_POW2 = (1 << np.arange(32, dtype=np.uint32)).astype(np.uint32)


def _apply_linear(m: np.ndarray, x: list) -> list:
    """Bit-matrix times bit-plane vector: out[i] = XOR_j m[i,j] & x[j]."""
    out = []
    for i in range(8):
        acc = None
        for j in range(8):
            if m[i, j]:
                acc = x[j] if acc is None else acc ^ x[j]
        out.append(acc if acc is not None else jnp.zeros_like(x[0]))
    return out


def _mul_planes(a: list, b: list) -> list:
    """GF(2^8) product of two bit-plane bytes (schoolbook + reduction)."""
    c = [None] * 15
    for i in range(8):
        for j in range(8):
            t = a[i] & b[j]
            k = i + j
            c[k] = t if c[k] is None else c[k] ^ t
    out = list(c[:8])
    for k in range(7):  # fold x^(8+k) back via the reduction matrix
        for i in range(8):
            if _RED[k, i]:
                out[i] = out[i] ^ c[8 + k]
    return out


def _sq_planes(x: list) -> list:
    return _apply_linear(_SQ, x)


def _sbox_planes_derived(x: list) -> list:
    """S(x) = Affine(x^254) ^ 0x63, all on bit planes (derived circuit)."""
    b2 = _sq_planes(x)                     # x^2
    b3 = _mul_planes(b2, x)                # x^3
    b12 = _sq_planes(_sq_planes(b3))       # x^12
    b15 = _mul_planes(b12, b3)             # x^15
    b240 = b15
    for _ in range(4):                     # x^240
        b240 = _sq_planes(b240)
    b252 = _mul_planes(b240, b12)          # x^252
    b254 = _mul_planes(b252, b2)           # x^254 = x^-1
    y = _apply_linear(_AFF, b254)
    # ^ 0x63: flip bits 0, 1, 5, 6
    for i in (0, 1, 5, 6):
        y[i] = ~y[i]
    return y


def _sbox_planes_bp(x: list) -> list:
    """Boyar-Peralta 113-gate forward S-box (32 AND + 81 XOR/XNOR).

    The public standard circuit for bitsliced AES software.  BP's U0 is
    the byte's MSB, so U_k = x[7-k]; outputs S0..S7 map back the same way
    (the four XNOR outputs realise the 0x63 constant).  Asserted equal to
    the derived circuit and the table S-box over all 256 byte values in
    tests/test_frodo.py.
    """
    U0, U1, U2, U3 = x[7], x[6], x[5], x[4]
    U4, U5, U6, U7 = x[3], x[2], x[1], x[0]
    T1 = U0 ^ U3
    T2 = U0 ^ U5
    T3 = U0 ^ U6
    T4 = U3 ^ U5
    T5 = U4 ^ U6
    T6 = T1 ^ T5
    T7 = U1 ^ U2
    T8 = U7 ^ T6
    T9 = U7 ^ T7
    T10 = T6 ^ T7
    T11 = U1 ^ U5
    T12 = U2 ^ U5
    T13 = T3 ^ T4
    T14 = T6 ^ T11
    T15 = T5 ^ T11
    T16 = T5 ^ T12
    T17 = T9 ^ T16
    T18 = U3 ^ U7
    T19 = T7 ^ T18
    T20 = T1 ^ T19
    T21 = U6 ^ U7
    T22 = T7 ^ T21
    T23 = T2 ^ T22
    T24 = T2 ^ T10
    T25 = T20 ^ T17
    T26 = T3 ^ T16
    T27 = T1 ^ T12
    D = U7
    M1 = T13 & T6
    M2 = T23 & T8
    M3 = T14 ^ M1
    M4 = T19 & D
    M5 = M4 ^ M1
    M6 = T3 & T16
    M7 = T22 & T9
    M8 = T26 ^ M6
    M9 = T20 & T17
    M10 = M9 ^ M6
    M11 = T1 & T15
    M12 = T4 & T27
    M13 = M12 ^ M11
    M14 = T2 & T10
    M15 = M14 ^ M11
    M16 = M3 ^ M2
    M17 = M5 ^ T24
    M18 = M8 ^ M7
    M19 = M10 ^ M15
    M20 = M16 ^ M13
    M21 = M17 ^ M15
    M22 = M18 ^ M13
    M23 = M19 ^ T25
    M24 = M22 ^ M23
    M25 = M22 & M20
    M26 = M21 ^ M25
    M27 = M20 ^ M21
    M28 = M23 ^ M25
    M29 = M28 & M27
    M30 = M26 & M24
    M31 = M20 & M23
    M32 = M27 & M31
    M33 = M27 ^ M25
    M34 = M21 & M22
    M35 = M24 & M34
    M36 = M24 ^ M25
    M37 = M21 ^ M29
    M38 = M32 ^ M33
    M39 = M23 ^ M30
    M40 = M35 ^ M36
    M41 = M38 ^ M40
    M42 = M37 ^ M39
    M43 = M37 ^ M38
    M44 = M39 ^ M40
    M45 = M42 ^ M41
    M46 = M44 & T6
    M47 = M40 & T8
    M48 = M39 & D
    M49 = M43 & T16
    M50 = M38 & T9
    M51 = M37 & T17
    M52 = M42 & T15
    M53 = M45 & T27
    M54 = M41 & T10
    M55 = M44 & T13
    M56 = M40 & T23
    M57 = M39 & T19
    M58 = M43 & T3
    M59 = M38 & T22
    M60 = M37 & T20
    M61 = M42 & T1
    M62 = M45 & T4
    M63 = M41 & T2
    L0 = M61 ^ M62
    L1 = M50 ^ M56
    L2 = M46 ^ M48
    L3 = M47 ^ M55
    L4 = M54 ^ M58
    L5 = M49 ^ M61
    L6 = M62 ^ L5
    L7 = M46 ^ L3
    L8 = M51 ^ M59
    L9 = M52 ^ M53
    L10 = M53 ^ L4
    L11 = M60 ^ L2
    L12 = M48 ^ M51
    L13 = M50 ^ L0
    L14 = M52 ^ M61
    L15 = M55 ^ L1
    L16 = M56 ^ L0
    L17 = M57 ^ L1
    L18 = M58 ^ L8
    L19 = M63 ^ L4
    L20 = L0 ^ L1
    L21 = L1 ^ L7
    L22 = L3 ^ L12
    L23 = L18 ^ L2
    L24 = L15 ^ L9
    L25 = L6 ^ L10
    L26 = L7 ^ L9
    L27 = L8 ^ L10
    L28 = L11 ^ L14
    L29 = L11 ^ L17
    S0 = L6 ^ L24
    S1 = ~(L16 ^ L26)
    S2 = ~(L19 ^ L28)
    S3 = L6 ^ L21
    S4 = L20 ^ L22
    S5 = L25 ^ L29
    S6 = ~(L13 ^ L27)
    S7 = ~(L6 ^ L23)
    return [S7, S6, S5, S4, S3, S2, S1, S0]


def _sbox_planes(x: list) -> list:
    if os.environ.get("QRP2P_AES_DERIVED_SBOX") == "1":
        return _sbox_planes_derived(x)
    return _sbox_planes_bp(x)


def _xtime_planes(a: list) -> list:
    """xtime on bit planes: shift up, fold 0x1B on the old high bit."""
    hi = a[7]
    out = [hi, a[0] ^ hi, a[1], a[2] ^ hi, a[3] ^ hi, a[4], a[5], a[6]]
    return out


def _mix_columns(s: jax.Array) -> jax.Array:
    """s (8, 16, ...) -> mixed; bytes are column-major (byte = row + 4*col)."""
    c = s.reshape((8, 4, 4) + s.shape[2:])  # (bit, col, row, ...)
    a = [[c[i, :, r] for i in range(8)] for r in range(4)]  # [row][bit]
    x = [_xtime_planes(a[r]) for r in range(4)]
    rows = []
    for r in range(4):
        r1, r2, r3 = (r + 1) % 4, (r + 2) % 4, (r + 3) % 4
        rows.append([
            x[r][i] ^ x[r1][i] ^ a[r1][i] ^ a[r2][i] ^ a[r3][i]
            for i in range(8)
        ])
    out = jnp.stack(
        [jnp.stack(rows[r], axis=0) for r in range(4)], axis=2
    )  # (bit, col, row, ...)
    return out.reshape(s.shape)


def pack_blocks(blocks: jax.Array) -> tuple[jax.Array, int]:
    """(*lead, B, 16) uint8 -> planes (8, 16, *lead, W) uint32, original B.

    Blocks pack 32-per-uint32 along the minor axis (padded with zeros).
    """
    lead = blocks.shape[:-2]
    b = blocks.shape[-2]
    w = -(-b // 32)
    if w * 32 != b:
        pad = [(0, 0)] * len(lead) + [(0, w * 32 - b), (0, 0)]
        blocks = jnp.pad(blocks, pad)
    x = blocks.astype(jnp.uint32)  # (*lead, W*32, 16)
    bits = (x[..., None] >> jnp.arange(8, dtype=jnp.uint32)) & 1  # (*l, B, 16, 8)
    bits = jnp.moveaxis(bits, (-1, -2), (0, 1))  # (8, 16, *lead, W*32)
    bits = bits.reshape(bits.shape[:-1] + (w, 32))
    planes = jnp.sum(bits * jnp.asarray(_POW2), axis=-1, dtype=jnp.uint32)
    return planes, b


def unpack_blocks(planes: jax.Array, b: int) -> jax.Array:
    """planes (8, 16, *lead, W) uint32 -> (*lead, B, 16) uint8."""
    w = planes.shape[-1]
    bits = (planes[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    bits = bits.reshape(planes.shape[:-1] + (w * 32,))  # (8, 16, *lead, B)
    bits = jnp.moveaxis(bits, (0, 1), (-1, -2))  # (*lead, B, 16, 8)
    vals = jnp.sum(bits << jnp.arange(8, dtype=jnp.uint32), axis=-1)
    return vals[..., :b, :].astype(jnp.uint8)


def _key_planes(round_keys: jax.Array) -> jax.Array:
    """(*lead, 11, 16) uint8 -> (11, 8, 16, *lead, 1) uint32 (0/~0 masks)."""
    x = round_keys.astype(jnp.uint32)
    bits = (x[..., None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    bits = jnp.moveaxis(bits, (-3, -1, -2), (0, 1, 2))  # (11, 8, 16, *lead)
    # 0 -> 0x00000000, 1 -> 0xFFFFFFFF so XOR applies the bit to all 32 lanes
    return (bits * jnp.uint32(0xFFFFFFFF))[..., None]


def encrypt_blocks(round_keys: jax.Array, blocks: jax.Array) -> jax.Array:
    """Drop-in for core.aes.encrypt_blocks, bitsliced.

    round_keys (*lead, 11, 16), blocks (*lead, B, 16) uint8 -> (*lead, B, 16).
    """
    rk = _key_planes(round_keys)
    s, b = pack_blocks(blocks)
    s = s ^ rk[0]
    for r in range(1, 10):
        bit_list = _sbox_planes([s[i] for i in range(8)])
        s = jnp.stack(bit_list, axis=0)
        s = s[:, _SHIFT]
        s = _mix_columns(s)
        s = s ^ rk[r]
    bit_list = _sbox_planes([s[i] for i in range(8)])
    s = jnp.stack(bit_list, axis=0)
    s = s[:, _SHIFT]
    s = s ^ rk[10]
    return unpack_blocks(s, b)
