"""Pure-Python ML-KEM (FIPS 203) — clean-room reference implementation.

Written directly from the FIPS 203 specification (Algorithms 13-21), with
``hashlib`` supplying SHA3-256/512 and SHAKE-128/256.  Used as the
bit-exactness oracle for the batched JAX implementation in
``quantum_resistant_p2p_tpu.kem.mlkem`` and as the CPU provider backend
(the role liboqs ML-KEM plays for the reference app's
``crypto/key_exchange.py:57-186`` MLKEMKeyExchange).

All functions are deterministic: randomness (d, z, m) is an explicit input,
which is exactly the seam FIPS 203 defines (and what liboqs's deterministic
KAT entry points expose), so the same seeds drive both implementations.

The benchmark's copy: its yardstick, which no change to the program can
move.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

Q = 3329
N = 256


@dataclass(frozen=True)
class MLKEMParams:
    name: str
    k: int
    eta1: int
    eta2: int
    du: int
    dv: int

    @property
    def ek_len(self) -> int:
        return 384 * self.k + 32

    @property
    def dk_len(self) -> int:
        return 768 * self.k + 96

    @property
    def ct_len(self) -> int:
        return 32 * (self.du * self.k + self.dv)


MLKEM512 = MLKEMParams("ML-KEM-512", k=2, eta1=3, eta2=2, du=10, dv=4)
MLKEM768 = MLKEMParams("ML-KEM-768", k=3, eta1=2, eta2=2, du=10, dv=4)
MLKEM1024 = MLKEMParams("ML-KEM-1024", k=4, eta1=2, eta2=2, du=11, dv=5)

PARAMS = {p.name: p for p in (MLKEM512, MLKEM768, MLKEM1024)}


# -- hashes (FIPS 203 §4.1) -------------------------------------------------

def G(data: bytes) -> bytes:
    return hashlib.sha3_512(data).digest()


def H(data: bytes) -> bytes:
    return hashlib.sha3_256(data).digest()


def J(data: bytes) -> bytes:
    return hashlib.shake_256(data).digest(32)


def prf(eta: int, s: bytes, b: int) -> bytes:
    return hashlib.shake_256(s + bytes([b])).digest(64 * eta)


# -- NTT (FIPS 203 §4.3) ----------------------------------------------------

def _bitrev7(i: int) -> int:
    return int(f"{i:07b}"[::-1], 2)


ZETAS = [pow(17, _bitrev7(i), Q) for i in range(128)]
GAMMAS = [pow(17, 2 * _bitrev7(i) + 1, Q) for i in range(128)]
_N_INV = pow(128, -1, Q)  # 3303


def ntt(f: list[int]) -> list[int]:
    f = list(f)
    k = 1
    length = 128
    while length >= 2:
        for start in range(0, N, 2 * length):
            zeta = ZETAS[k]
            k += 1
            for j in range(start, start + length):
                t = (zeta * f[j + length]) % Q
                f[j + length] = (f[j] - t) % Q
                f[j] = (f[j] + t) % Q
        length //= 2
    return f


def ntt_inv(fh: list[int]) -> list[int]:
    f = list(fh)
    k = 127
    length = 2
    while length <= 128:
        for start in range(0, N, 2 * length):
            zeta = ZETAS[k]
            k -= 1
            for j in range(start, start + length):
                t = f[j]
                f[j] = (t + f[j + length]) % Q
                f[j + length] = (zeta * (f[j + length] - t)) % Q
        length *= 2
    return [(x * _N_INV) % Q for x in f]


def multiply_ntts(f: list[int], g: list[int]) -> list[int]:
    h = [0] * N
    for i in range(128):
        a0, a1 = f[2 * i], f[2 * i + 1]
        b0, b1 = g[2 * i], g[2 * i + 1]
        h[2 * i] = (a0 * b0 + a1 * b1 % Q * GAMMAS[i]) % Q
        h[2 * i + 1] = (a0 * b1 + a1 * b0) % Q
    return h


def poly_add(f: list[int], g: list[int]) -> list[int]:
    return [(a + b) % Q for a, b in zip(f, g)]


def poly_sub(f: list[int], g: list[int]) -> list[int]:
    return [(a - b) % Q for a, b in zip(f, g)]


# -- sampling (FIPS 203 §4.2.2) ---------------------------------------------

def sample_ntt(seed34: bytes) -> list[int]:
    """Algorithm 7: rejection-sample a polynomial in NTT domain from XOF."""
    # hashlib's shake is one-shot; squeeze a buffer large enough that running
    # out has negligible probability (448+ candidates for 256 needed).
    buf = hashlib.shake_128(seed34).digest(168 * 6)
    out: list[int] = []
    pos = 0
    while len(out) < N:
        d1 = buf[pos] + 256 * (buf[pos + 1] % 16)
        d2 = (buf[pos + 1] // 16) + 16 * buf[pos + 2]
        pos += 3
        if d1 < Q:
            out.append(d1)
        if d2 < Q and len(out) < N:
            out.append(d2)
    return out


def sample_poly_cbd(eta: int, b: bytes) -> list[int]:
    """Algorithm 8: centered binomial distribution from 64*eta bytes."""
    bits = [(byte >> k) & 1 for byte in b for k in range(8)]
    f = []
    for i in range(N):
        x = sum(bits[2 * i * eta + j] for j in range(eta))
        y = sum(bits[2 * i * eta + eta + j] for j in range(eta))
        f.append((x - y) % Q)
    return f


# -- codecs (FIPS 203 §4.2.1) -----------------------------------------------

def byte_encode(d: int, f: list[int]) -> bytes:
    out = bytearray(32 * d)
    bit = 0
    for a in f:
        for j in range(d):
            out[bit >> 3] |= ((a >> j) & 1) << (bit & 7)
            bit += 1
    return bytes(out)


def byte_decode(d: int, b: bytes) -> list[int]:
    m = Q if d == 12 else (1 << d)
    f = []
    for i in range(N):
        a = 0
        for j in range(d):
            bit = i * d + j
            a |= ((b[bit >> 3] >> (bit & 7)) & 1) << j
        f.append(a % m)
    return f


def compress(d: int, x: int) -> int:
    return ((2 * (x << d) + Q) // (2 * Q)) % (1 << d)


def decompress(d: int, y: int) -> int:
    return (y * Q + (1 << (d - 1))) >> d


# -- K-PKE (FIPS 203 §5) ----------------------------------------------------

def kpke_keygen(p: MLKEMParams, d: bytes) -> tuple[bytes, bytes]:
    rho, sigma = G(d + bytes([p.k]))[:32], G(d + bytes([p.k]))[32:]
    a_hat = [[sample_ntt(rho + bytes([j, i])) for j in range(p.k)] for i in range(p.k)]
    n = 0
    s = []
    for _ in range(p.k):
        s.append(sample_poly_cbd(p.eta1, prf(p.eta1, sigma, n)))
        n += 1
    e = []
    for _ in range(p.k):
        e.append(sample_poly_cbd(p.eta1, prf(p.eta1, sigma, n)))
        n += 1
    s_hat = [ntt(x) for x in s]
    e_hat = [ntt(x) for x in e]
    t_hat = []
    for i in range(p.k):
        acc = e_hat[i]
        for j in range(p.k):
            acc = poly_add(acc, multiply_ntts(a_hat[i][j], s_hat[j]))
        t_hat.append(acc)
    ek = b"".join(byte_encode(12, t) for t in t_hat) + rho
    dk = b"".join(byte_encode(12, sh) for sh in s_hat)
    return ek, dk


def kpke_encrypt(p: MLKEMParams, ek: bytes, m: bytes, r: bytes) -> bytes:
    t_hat = [byte_decode(12, ek[384 * i : 384 * (i + 1)]) for i in range(p.k)]
    rho = ek[384 * p.k :]
    a_hat = [[sample_ntt(rho + bytes([j, i])) for j in range(p.k)] for i in range(p.k)]
    n = 0
    y = []
    for _ in range(p.k):
        y.append(sample_poly_cbd(p.eta1, prf(p.eta1, r, n)))
        n += 1
    e1 = []
    for _ in range(p.k):
        e1.append(sample_poly_cbd(p.eta2, prf(p.eta2, r, n)))
        n += 1
    e2 = sample_poly_cbd(p.eta2, prf(p.eta2, r, n))
    y_hat = [ntt(x) for x in y]
    u = []
    for i in range(p.k):
        acc = [0] * N
        for j in range(p.k):
            acc = poly_add(acc, multiply_ntts(a_hat[j][i], y_hat[j]))  # A^T
        u.append(poly_add(ntt_inv(acc), e1[i]))
    mu = [decompress(1, bit) for bit in byte_decode(1, m)]
    acc = [0] * N
    for j in range(p.k):
        acc = poly_add(acc, multiply_ntts(t_hat[j], y_hat[j]))
    v = poly_add(poly_add(ntt_inv(acc), e2), mu)
    c1 = b"".join(byte_encode(p.du, [compress(p.du, x) for x in ui]) for ui in u)
    c2 = byte_encode(p.dv, [compress(p.dv, x) for x in v])
    return c1 + c2


def kpke_decrypt(p: MLKEMParams, dk: bytes, c: bytes) -> bytes:
    du_bytes = 32 * p.du
    u = [
        [decompress(p.du, y) for y in byte_decode(p.du, c[du_bytes * i : du_bytes * (i + 1)])]
        for i in range(p.k)
    ]
    v = [decompress(p.dv, y) for y in byte_decode(p.dv, c[du_bytes * p.k :])]
    s_hat = [byte_decode(12, dk[384 * i : 384 * (i + 1)]) for i in range(p.k)]
    acc = [0] * N
    for i in range(p.k):
        acc = poly_add(acc, multiply_ntts(s_hat[i], ntt(u[i])))
    w = poly_sub(v, ntt_inv(acc))
    return byte_encode(1, [compress(1, x) for x in w])


# -- ML-KEM (FIPS 203 §6-7) -------------------------------------------------

def keygen(p: MLKEMParams, d: bytes, z: bytes) -> tuple[bytes, bytes]:
    """Algorithm 16 ML-KEM.KeyGen_internal: (ek, dk) from 32-byte seeds d, z."""
    ek, dk_pke = kpke_keygen(p, d)
    dk = dk_pke + ek + H(ek) + z
    return ek, dk


def encaps(p: MLKEMParams, ek: bytes, m: bytes) -> tuple[bytes, bytes]:
    """Algorithm 17 ML-KEM.Encaps_internal: (K, c) from ek and 32-byte m."""
    g = G(m + H(ek))
    key, r = g[:32], g[32:]
    c = kpke_encrypt(p, ek, m, r)
    return key, c


def decaps(p: MLKEMParams, dk: bytes, c: bytes) -> bytes:
    """Algorithm 18 ML-KEM.Decaps_internal with implicit rejection."""
    dk_pke = dk[: 384 * p.k]
    ek = dk[384 * p.k : 768 * p.k + 32]
    h = dk[768 * p.k + 32 : 768 * p.k + 64]
    z = dk[768 * p.k + 64 :]
    m2 = kpke_decrypt(p, dk_pke, c)
    g = G(m2 + h)
    key2, r2 = g[:32], g[32:]
    key_bar = J(z + c)
    c2 = kpke_encrypt(p, ek, m2, r2)
    return key2 if c == c2 else key_bar
