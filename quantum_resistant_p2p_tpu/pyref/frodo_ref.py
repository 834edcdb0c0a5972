"""Pure-Python FrodoKEM (round-3 / ISO spec) — clean-room reference.

Written from the FrodoKEM specification (frodokem.org round-3 submission):
LWE with dense n x n matrices, nbar = mbar = 8, q = 2^D.  Matrix A comes from
AES-128-ECB (the -AES variants) or SHAKE-128 (the -SHAKE variants) expansion.
``cryptography`` supplies AES; ``hashlib`` supplies SHAKE.

Serves as the bit-exactness oracle for the batched JAX implementation in
``kem.frodo`` and as the CPU provider backend (the role liboqs FrodoKEM plays
for the reference app's crypto/key_exchange.py:312-449 FrodoKEMKeyExchange).

Determinism seam: keygen takes (s, seedSE, z); encaps takes mu — the exact
random inputs the spec draws, so KAT-style seeds drive both implementations.

Self-check: parameter sets reproduce the published sizes
  pk 9616/15632/21520, sk 19888/31296/43088, ct 9720/15744/21632.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

NBAR = 8


@dataclass(frozen=True)
class FrodoParams:
    name: str
    n: int
    d: int  # q = 2^d
    b: int  # extracted bits per coefficient
    len_sec: int  # bytes of s / seedSE / z / pkh / mu / ss
    cdf: tuple[int, ...]
    aes: bool  # True -> AES-128 matrix gen, False -> SHAKE-128

    @property
    def q(self) -> int:
        return 1 << self.d

    @property
    def pk_len(self) -> int:
        return 16 + self.n * NBAR * self.d // 8

    @property
    def sk_len(self) -> int:
        return self.len_sec + self.pk_len + 2 * self.n * NBAR + self.len_sec

    @property
    def ct_len(self) -> int:
        return (NBAR * self.n + NBAR * NBAR) * self.d // 8

    @property
    def shake(self):
        return hashlib.shake_128 if self.n == 640 else hashlib.shake_256


_CDF640 = (4643, 13363, 20579, 25843, 29227, 31145, 32103, 32525, 32689,
           32745, 32762, 32766, 32767)
_CDF976 = (5638, 15915, 23689, 28571, 31116, 32217, 32613, 32731, 32760,
           32766, 32767)
_CDF1344 = (9142, 23462, 30338, 32361, 32725, 32765, 32767)


def _mk(name, n, d, b, sec, cdf, aes):
    return FrodoParams(name, n, d, b, sec, cdf, aes)


FRODO640AES = _mk("FrodoKEM-640-AES", 640, 15, 2, 16, _CDF640, True)
FRODO640SHAKE = _mk("FrodoKEM-640-SHAKE", 640, 15, 2, 16, _CDF640, False)
FRODO976AES = _mk("FrodoKEM-976-AES", 976, 16, 3, 24, _CDF976, True)
FRODO976SHAKE = _mk("FrodoKEM-976-SHAKE", 976, 16, 3, 24, _CDF976, False)
FRODO1344AES = _mk("FrodoKEM-1344-AES", 1344, 16, 4, 32, _CDF1344, True)
FRODO1344SHAKE = _mk("FrodoKEM-1344-SHAKE", 1344, 16, 4, 32, _CDF1344, False)

PARAMS = {p.name: p for p in (
    FRODO640AES, FRODO640SHAKE, FRODO976AES, FRODO976SHAKE, FRODO1344AES, FRODO1344SHAKE
)}

assert FRODO640AES.pk_len == 9616 and FRODO640AES.sk_len == 19888 and FRODO640AES.ct_len == 9720
assert FRODO976AES.pk_len == 15632 and FRODO976AES.sk_len == 31296 and FRODO976AES.ct_len == 15744
assert FRODO1344AES.pk_len == 21520 and FRODO1344AES.sk_len == 43088 and FRODO1344AES.ct_len == 21632


def _shake(p: FrodoParams, data: bytes, out_len: int) -> bytes:
    return p.shake(data).digest(out_len)


# -- matrix A generation (spec Algorithms 7-8) -------------------------------


def gen_a(p: FrodoParams, seed_a: bytes) -> list[list[int]]:
    n = p.n
    mask = p.q - 1
    a = []
    if p.aes:
        enc = Cipher(algorithms.AES(seed_a), modes.ECB()).encryptor()
        for i in range(n):
            row = []
            blocks = b"".join(
                i.to_bytes(2, "little") + j.to_bytes(2, "little") + b"\0" * 12
                for j in range(0, n, 8)
            )
            ct = enc.update(blocks)
            for k in range(0, len(ct), 2):
                row.append(int.from_bytes(ct[k : k + 2], "little") & mask)
            a.append(row)
    else:
        for i in range(n):
            buf = hashlib.shake_128(i.to_bytes(2, "little") + seed_a).digest(2 * n)
            a.append(
                [int.from_bytes(buf[2 * j : 2 * j + 2], "little") & mask for j in range(n)]
            )
    return a


# -- error sampling (spec Algorithm 5: inversion sampling on the CDF) --------


def sample(p: FrodoParams, r16: int) -> int:
    t = r16 >> 1
    e = 0
    for z in p.cdf[:-1]:
        if t > z:
            e += 1
    if r16 & 1:
        e = -e
    return e % p.q


def sample_matrix(p: FrodoParams, rbytes: bytes, n1: int, n2: int) -> list[list[int]]:
    vals = [
        sample(p, int.from_bytes(rbytes[2 * k : 2 * k + 2], "little"))
        for k in range(n1 * n2)
    ]
    return [vals[i * n2 : (i + 1) * n2] for i in range(n1)]


# -- packing / encoding (spec Algorithms 3-4 and 1-2) ------------------------


def pack(p: FrodoParams, m: list[list[int]]) -> bytes:
    """D-bit big-endian bit packing of the matrix in row-major order."""
    bits = 0
    acc = 0
    out = bytearray()
    for row in m:
        for v in row:
            acc = (acc << p.d) | (v & (p.q - 1))
            bits += p.d
            while bits >= 8:
                bits -= 8
                out.append((acc >> bits) & 0xFF)
    return bytes(out)


def unpack(p: FrodoParams, data: bytes, n1: int, n2: int) -> list[list[int]]:
    acc = 0
    bits = 0
    vals = []
    pos = 0
    for _ in range(n1 * n2):
        while bits < p.d:
            acc = (acc << 8) | data[pos]
            pos += 1
            bits += 8
        bits -= p.d
        vals.append((acc >> bits) & (p.q - 1))
        acc &= (1 << bits) - 1
    return [vals[i * n2 : (i + 1) * n2] for i in range(n1)]


def encode(p: FrodoParams, mu: bytes) -> list[list[int]]:
    """mu (len_sec bytes = nbar*nbar*B bits) -> nbar x nbar matrix."""
    step = p.q >> p.b
    vals = []
    for k in range(NBAR * NBAR):
        v = 0
        for l in range(p.b):
            bit_idx = k * p.b + l
            v |= ((mu[bit_idx >> 3] >> (bit_idx & 7)) & 1) << l
        vals.append(v * step)
    return [vals[i * NBAR : (i + 1) * NBAR] for i in range(NBAR)]


def decode(p: FrodoParams, m: list[list[int]]) -> bytes:
    out = bytearray(NBAR * NBAR * p.b // 8)
    k = 0
    for row in m:
        for v in row:
            val = ((v << p.b) + (p.q >> 1)) >> p.d  # round(v * 2^B / q)
            val &= (1 << p.b) - 1
            for l in range(p.b):
                bit_idx = k * p.b + l
                out[bit_idx >> 3] |= ((val >> l) & 1) << (bit_idx & 7)
            k += 1
    return bytes(out)


# -- matrix helpers ----------------------------------------------------------


def _matmul_as(p, a, s):
    """A (n x n) @ S (n x nbar) mod q."""
    q = p.q
    n = p.n
    return [
        [sum(a[i][k] * s[k][j] for k in range(n)) % q for j in range(NBAR)]
        for i in range(n)
    ]


def _matmul_sa(p, s, a):
    """S' (nbar x n) @ A (n x n) mod q."""
    q = p.q
    n = p.n
    return [
        [sum(s[i][k] * a[k][j] for k in range(n)) % q for j in range(n)]
        for i in range(NBAR)
    ]


def _matmul_sb(p, s, b):
    """S' (nbar x n) @ B (n x nbar) mod q."""
    q = p.q
    return [
        [sum(s[i][k] * b[k][j] for k in range(p.n)) % q for j in range(NBAR)]
        for i in range(NBAR)
    ]


def _add(p, x, y):
    return [[(a + b) % p.q for a, b in zip(rx, ry)] for rx, ry in zip(x, y)]


def _sub(p, x, y):
    return [[(a - b) % p.q for a, b in zip(rx, ry)] for rx, ry in zip(x, y)]


# -- KEM (spec Algorithms 12-14) ---------------------------------------------


def keygen(p: FrodoParams, s: bytes, seed_se: bytes, z: bytes) -> tuple[bytes, bytes]:
    """Deterministic KeyGen from the spec's three random inputs."""
    seed_a = _shake(p, z, 16)
    a = gen_a(p, seed_a)
    r = _shake(p, b"\x5f" + seed_se, 4 * p.n * NBAR)
    st = sample_matrix(p, r[: 2 * p.n * NBAR], NBAR, p.n)  # S^T
    e = sample_matrix(p, r[2 * p.n * NBAR :], p.n, NBAR)
    s_mat = [[st[j][i] for j in range(NBAR)] for i in range(p.n)]  # n x nbar
    b_mat = _add(p, _matmul_as(p, a, s_mat), e)
    b_packed = pack(p, b_mat)
    pk = seed_a + b_packed
    pkh = _shake(p, pk, p.len_sec)
    st_bytes = b"".join(
        (v if v < p.q // 2 else v - p.q).to_bytes(2, "little", signed=True)
        for row in st for v in row
    )
    sk = s + pk + st_bytes + pkh
    return pk, sk


def encaps(p: FrodoParams, pk: bytes, mu: bytes) -> tuple[bytes, bytes]:
    """Deterministic Encaps from the spec's random mu -> (ct, ss)."""
    seed_a, b_packed = pk[:16], pk[16:]
    pkh = _shake(p, pk, p.len_sec)
    se_k = _shake(p, pkh + mu, p.len_sec + p.len_sec)
    seed_se, k = se_k[: p.len_sec], se_k[p.len_sec :]
    r = _shake(p, b"\x96" + seed_se, (2 * NBAR * p.n + NBAR * NBAR) * 2)
    sp = sample_matrix(p, r[: 2 * NBAR * p.n], NBAR, p.n)
    ep = sample_matrix(p, r[2 * NBAR * p.n : 4 * NBAR * p.n], NBAR, p.n)
    epp = sample_matrix(p, r[4 * NBAR * p.n :], NBAR, NBAR)
    a = gen_a(p, seed_a)
    bp = _add(p, _matmul_sa(p, sp, a), ep)
    b_mat = unpack(p, b_packed, p.n, NBAR)
    v = _add(p, _matmul_sb(p, sp, b_mat), epp)
    c = _add(p, v, encode(p, mu))
    ct = pack(p, bp) + pack(p, c)
    ss = _shake(p, ct + k, p.len_sec)
    return ct, ss


def decaps(p: FrodoParams, sk: bytes, ct: bytes) -> bytes:
    n, q = p.n, p.q
    s = sk[: p.len_sec]
    pk = sk[p.len_sec : p.len_sec + p.pk_len]
    seed_a = pk[:16]
    b_packed = pk[16:]
    st_off = p.len_sec + p.pk_len
    st = [
        [
            int.from_bytes(sk[st_off + 2 * (i * n + j) : st_off + 2 * (i * n + j) + 2],
                           "little", signed=True) % q
            for j in range(n)
        ]
        for i in range(NBAR)
    ]
    pkh = sk[st_off + 2 * NBAR * n :]
    c1_len = NBAR * n * p.d // 8
    bp = unpack(p, ct[:c1_len], NBAR, n)
    c = unpack(p, ct[c1_len:], NBAR, NBAR)
    # M = C - B' * S  (S is n x nbar = transpose of stored S^T)
    bps = [
        [sum(bp[i][k] * st[j][k] for k in range(n)) % q for j in range(NBAR)]
        for i in range(NBAR)
    ]
    m = _sub(p, c, bps)
    mu_p = decode(p, m)
    se_k = _shake(p, pkh + mu_p, 2 * p.len_sec)
    seed_se, kp = se_k[: p.len_sec], se_k[p.len_sec :]
    r = _shake(p, b"\x96" + seed_se, (2 * NBAR * p.n + NBAR * NBAR) * 2)
    sp = sample_matrix(p, r[: 2 * NBAR * p.n], NBAR, p.n)
    ep = sample_matrix(p, r[2 * NBAR * p.n : 4 * NBAR * p.n], NBAR, p.n)
    epp = sample_matrix(p, r[4 * NBAR * p.n :], NBAR, NBAR)
    a = gen_a(p, seed_a)
    bpp = _add(p, _matmul_sa(p, sp, a), ep)
    b_mat = unpack(p, b_packed, p.n, NBAR)
    v = _add(p, _matmul_sb(p, sp, b_mat), epp)
    cp = _add(p, v, encode(p, mu_p))
    if bp == bpp and c == cp:
        return _shake(p, ct + kp, p.len_sec)
    return _shake(p, ct + s, p.len_sec)
