"""Pure-Python ML-DSA (FIPS 204) — clean-room reference implementation.

Written directly from the FIPS 204 specification with ``hashlib`` supplying
SHAKE-128/256.  Serves as the bit-exactness oracle for the batched JAX
implementation in ``quantum_resistant_p2p_tpu.sig.mldsa`` and as the CPU
provider backend (the role liboqs ML-DSA plays for the reference app's
crypto/signatures.py:58-188 MLDSASignature).

Determinism seam: keygen takes the 32-byte seed ``xi``; signing takes the
32-byte ``rnd`` (all-zeros = the deterministic variant), matching the spec's
internal functions so KAT-style seeds drive both implementations.

Self-check: parameter sets reproduce the published sizes
  pk 1312/1952/2592, sk 2560/4032/4896, sig 2420/3309/4627  (44/65/87).

The benchmark's copy: its yardstick, which no change to the program can
move.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

Q = 8380417
N = 256
D = 13  # dropped bits in Power2Round
ZETA = 1753


@dataclass(frozen=True)
class MLDSAParams:
    name: str
    k: int
    l: int
    eta: int
    tau: int
    gamma1: int
    gamma2: int
    omega: int
    lambda_: int  # collision strength in bits; ctilde = lambda/4 bytes

    @property
    def beta(self) -> int:
        return self.tau * self.eta

    @property
    def ctilde_len(self) -> int:
        return self.lambda_ // 4

    @property
    def z_bits(self) -> int:
        return 1 + (self.gamma1 - 1).bit_length()  # 18 or 20

    @property
    def w1_bits(self) -> int:
        return ((Q - 1) // (2 * self.gamma2) - 1).bit_length()  # 6 or 4

    @property
    def s_bits(self) -> int:
        return (2 * self.eta).bit_length()  # 3 (eta=2) or 4 (eta=4)

    @property
    def pk_len(self) -> int:
        return 32 + 32 * (23 - D) * self.k

    @property
    def sk_len(self) -> int:
        return 128 + 32 * self.s_bits * (self.k + self.l) + 32 * D * self.k

    @property
    def sig_len(self) -> int:
        return self.ctilde_len + 32 * self.z_bits * self.l + self.omega + self.k


MLDSA44 = MLDSAParams("ML-DSA-44", k=4, l=4, eta=2, tau=39, gamma1=1 << 17,
                      gamma2=(Q - 1) // 88, omega=80, lambda_=128)
MLDSA65 = MLDSAParams("ML-DSA-65", k=6, l=5, eta=4, tau=49, gamma1=1 << 19,
                      gamma2=(Q - 1) // 32, omega=55, lambda_=192)
MLDSA87 = MLDSAParams("ML-DSA-87", k=8, l=7, eta=2, tau=60, gamma1=1 << 19,
                      gamma2=(Q - 1) // 32, omega=75, lambda_=256)

PARAMS = {p.name: p for p in (MLDSA44, MLDSA65, MLDSA87)}

assert MLDSA44.pk_len == 1312 and MLDSA44.sk_len == 2560 and MLDSA44.sig_len == 2420
assert MLDSA65.pk_len == 1952 and MLDSA65.sk_len == 4032 and MLDSA65.sig_len == 3309
assert MLDSA87.pk_len == 2592 and MLDSA87.sk_len == 4896 and MLDSA87.sig_len == 4627


def shake256(data: bytes, n: int) -> bytes:
    return hashlib.shake_256(data).digest(n)


def shake128(data: bytes, n: int) -> bytes:
    return hashlib.shake_128(data).digest(n)


# -- NTT (complete 256-point, FIPS 204 §7.5) --------------------------------

def _bitrev8(i: int) -> int:
    return int(f"{i:08b}"[::-1], 2)


ZETAS = [pow(ZETA, _bitrev8(i), Q) for i in range(256)]
_N_INV = pow(256, -1, Q)


def ntt(f: list[int]) -> list[int]:
    f = list(f)
    k = 0
    length = 128
    while length >= 1:
        for start in range(0, N, 2 * length):
            k += 1
            zeta = ZETAS[k]
            for j in range(start, start + length):
                t = (zeta * f[j + length]) % Q
                f[j + length] = (f[j] - t) % Q
                f[j] = (f[j] + t) % Q
        length //= 2
    return f


def ntt_inv(fh: list[int]) -> list[int]:
    f = list(fh)
    k = 256
    length = 1
    while length <= 128:
        for start in range(0, N, 2 * length):
            k -= 1
            zeta = ZETAS[k]
            for j in range(start, start + length):
                t = f[j]
                f[j] = (t + f[j + length]) % Q
                f[j + length] = (zeta * (f[j + length] - t)) % Q
        length *= 2
    return [(x * _N_INV) % Q for x in f]


def pw_mul(a: list[int], b: list[int]) -> list[int]:
    return [(x * y) % Q for x, y in zip(a, b)]


def poly_add(a, b):
    return [(x + y) % Q for x, y in zip(a, b)]


def poly_sub(a, b):
    return [(x - y) % Q for x, y in zip(a, b)]


def _center(x: int, m: int = Q) -> int:
    """mod± : representative in (-m/2, m/2]."""
    x %= m
    return x - m if x > m // 2 else x


def inf_norm(poly: list[int]) -> int:
    return max(abs(_center(c)) for c in poly)


# -- rounding (FIPS 204 §7.4) ----------------------------------------------

def power2round(r: int) -> tuple[int, int]:
    r %= Q
    r0 = _center(r, 1 << D)
    return (r - r0) >> D, r0


def decompose(p: MLDSAParams, r: int) -> tuple[int, int]:
    alpha = 2 * p.gamma2
    r %= Q
    r0 = _center(r, alpha)
    if r - r0 == Q - 1:
        return 0, r0 - 1
    return (r - r0) // alpha, r0


def high_bits(p: MLDSAParams, r: int) -> int:
    return decompose(p, r)[0]


def low_bits(p: MLDSAParams, r: int) -> int:
    return decompose(p, r)[1]


def make_hint(p: MLDSAParams, z: int, r: int) -> int:
    return int(high_bits(p, r + z) != high_bits(p, r))


def use_hint(p: MLDSAParams, h: int, r: int) -> int:
    m = (Q - 1) // (2 * p.gamma2)
    r1, r0 = decompose(p, r)
    if h:
        return (r1 + 1) % m if r0 > 0 else (r1 - 1) % m
    return r1


# -- bit packing (FIPS 204 §7.1) --------------------------------------------

def simple_bit_pack(coeffs: list[int], bits: int) -> bytes:
    out = bytearray(32 * bits)
    pos = 0
    for c in coeffs:
        for j in range(bits):
            out[pos >> 3] |= ((c >> j) & 1) << (pos & 7)
            pos += 1
    return bytes(out)


def simple_bit_unpack(b: bytes, bits: int) -> list[int]:
    coeffs = []
    for i in range(N):
        a = 0
        for j in range(bits):
            pos = i * bits + j
            a |= ((b[pos >> 3] >> (pos & 7)) & 1) << j
        coeffs.append(a)
    return coeffs


def bit_pack(coeffs: list[int], up: int, bits: int) -> bytes:
    """Pack coeffs in [-(2^bits - 1 - up)... ] as ``up - c`` in ``bits`` bits."""
    return simple_bit_pack([(up - _center(c)) for c in coeffs], bits)


def bit_unpack(b: bytes, up: int, bits: int) -> list[int]:
    return [(up - v) % Q for v in simple_bit_unpack(b, bits)]


def hint_bit_pack(p: MLDSAParams, h: list[list[int]]) -> bytes:
    out = bytearray(p.omega + p.k)
    idx = 0
    for i in range(p.k):
        for j in range(N):
            if h[i][j]:
                out[idx] = j
                idx += 1
        out[p.omega + i] = idx
    return bytes(out)


def hint_bit_unpack(p: MLDSAParams, b: bytes) -> list[list[int]] | None:
    h = [[0] * N for _ in range(p.k)]
    idx = 0
    for i in range(p.k):
        end = b[p.omega + i]
        if end < idx or end > p.omega:
            return None
        first = True
        prev = -1
        while idx < end:
            j = b[idx]
            if not first and j <= prev:
                return None  # positions must be strictly increasing
            h[i][j] = 1
            prev = j
            first = False
            idx += 1
    if any(b[i] != 0 for i in range(idx, p.omega)):
        return None
    return h


# -- samplers (FIPS 204 §7.3) -----------------------------------------------

def rej_ntt_poly(seed: bytes) -> list[int]:
    buf = shake128(seed, 168 * 7)
    out = []
    pos = 0
    while len(out) < N:
        t = buf[pos] | (buf[pos + 1] << 8) | ((buf[pos + 2] & 0x7F) << 16)
        pos += 3
        if t < Q:
            out.append(t)
    return out


def rej_bounded_poly(eta: int, seed: bytes) -> list[int]:
    buf = shake256(seed, 136 * 4)
    out = []
    for byte in buf:
        for z in (byte & 0xF, byte >> 4):
            if len(out) == N:
                return out
            if eta == 2 and z < 15:
                out.append((2 - z % 5) % Q)
            elif eta == 4 and z < 9:
                out.append((4 - z) % Q)
    raise RuntimeError("rej_bounded_poly buffer exhausted")


def expand_a(p: MLDSAParams, rho: bytes) -> list[list[list[int]]]:
    return [
        [rej_ntt_poly(rho + bytes([s, r])) for s in range(p.l)]
        for r in range(p.k)
    ]


def expand_s(p: MLDSAParams, rhop: bytes) -> tuple[list, list]:
    s1 = [rej_bounded_poly(p.eta, rhop + n.to_bytes(2, "little")) for n in range(p.l)]
    s2 = [
        rej_bounded_poly(p.eta, rhop + (p.l + n).to_bytes(2, "little"))
        for n in range(p.k)
    ]
    return s1, s2


def expand_mask(p: MLDSAParams, rhop: bytes, kappa: int) -> list[list[int]]:
    y = []
    for r in range(p.l):
        buf = shake256(rhop + (kappa + r).to_bytes(2, "little"), 32 * p.z_bits)
        y.append(bit_unpack(buf, p.gamma1, p.z_bits))
    return y


def sample_in_ball(p: MLDSAParams, ctilde: bytes) -> list[int]:
    buf = hashlib.shake_256(ctilde).digest(8 + 1024)
    signs = int.from_bytes(buf[:8], "little")
    c = [0] * N
    pos = 8
    for i in range(N - p.tau, N):
        while True:
            j = buf[pos]
            pos += 1
            if j <= i:
                break
        c[i] = c[j]
        c[j] = (1 if (signs & 1) == 0 else Q - 1)
        signs >>= 1
    return c


# -- vector/matrix helpers ---------------------------------------------------

def _matvec(a_hat, vec_hat, k, l):
    out = []
    for r in range(k):
        acc = [0] * N
        for s in range(l):
            acc = poly_add(acc, pw_mul(a_hat[r][s], vec_hat[s]))
        out.append(acc)
    return out


# -- keygen / sign / verify (FIPS 204 §6, internal forms) --------------------

def keygen(p: MLDSAParams, xi: bytes) -> tuple[bytes, bytes]:
    """Algorithm 6 ML-DSA.KeyGen_internal: 32-byte seed -> (pk, sk)."""
    seed = shake256(xi + bytes([p.k, p.l]), 128)
    rho, rhop, cap_k = seed[:32], seed[32:96], seed[96:]
    a_hat = expand_a(p, rho)
    s1, s2 = expand_s(p, rhop)
    s1_hat = [ntt(x) for x in s1]
    t = [
        poly_add(ntt_inv(poly), s2[r])
        for r, poly in enumerate(_matvec(a_hat, s1_hat, p.k, p.l))
    ]
    t1 = [[0] * N for _ in range(p.k)]
    t0 = [[0] * N for _ in range(p.k)]
    for r in range(p.k):
        for j in range(N):
            t1[r][j], t0[r][j] = power2round(t[r][j])
    pk = rho + b"".join(simple_bit_pack(t1[r], 23 - D) for r in range(p.k))
    tr = shake256(pk, 64)
    sk = (
        rho
        + cap_k
        + tr
        + b"".join(bit_pack(s, p.eta, p.s_bits) for s in s1)
        + b"".join(bit_pack(s, p.eta, p.s_bits) for s in s2)
        + b"".join(bit_pack(t, 1 << (D - 1), D) for t in t0)
    )
    return pk, sk


def _unpack_sk(p: MLDSAParams, sk: bytes):
    rho, cap_k, tr = sk[:32], sk[32:64], sk[64:128]
    off = 128
    sb = 32 * p.s_bits
    s1 = [bit_unpack(sk[off + i * sb : off + (i + 1) * sb], p.eta, p.s_bits) for i in range(p.l)]
    off += p.l * sb
    s2 = [bit_unpack(sk[off + i * sb : off + (i + 1) * sb], p.eta, p.s_bits) for i in range(p.k)]
    off += p.k * sb
    tb = 32 * D
    t0 = [
        bit_unpack(sk[off + i * tb : off + (i + 1) * tb], 1 << (D - 1), D)
        for i in range(p.k)
    ]
    return rho, cap_k, tr, s1, s2, t0


def sign_internal(p: MLDSAParams, sk: bytes, m_prime: bytes, rnd: bytes = b"\0" * 32) -> bytes:
    """Algorithm 7 ML-DSA.Sign_internal (rnd = zeros -> deterministic variant)."""
    rho, cap_k, tr, s1, s2, t0 = _unpack_sk(p, sk)
    a_hat = expand_a(p, rho)
    s1_hat = [ntt(x) for x in s1]
    s2_hat = [ntt(x) for x in s2]
    t0_hat = [ntt(x) for x in t0]
    mu = shake256(tr + m_prime, 64)
    rhopp = shake256(cap_k + rnd + mu, 64)
    kappa = 0
    while True:
        y = expand_mask(p, rhopp, kappa)
        kappa += p.l
        y_hat = [ntt(x) for x in y]
        w = [ntt_inv(poly) for poly in _matvec(a_hat, y_hat, p.k, p.l)]
        w1 = [[high_bits(p, c) for c in poly] for poly in w]
        w1_enc = b"".join(simple_bit_pack(poly, p.w1_bits) for poly in w1)
        ctilde = shake256(mu + w1_enc, p.ctilde_len)
        c = sample_in_ball(p, ctilde)
        c_hat = ntt(c)
        z = [
            poly_add(y[s], ntt_inv(pw_mul(c_hat, s1_hat[s])))
            for s in range(p.l)
        ]
        if max(inf_norm(poly) for poly in z) >= p.gamma1 - p.beta:
            continue
        cs2 = [ntt_inv(pw_mul(c_hat, s2_hat[r])) for r in range(p.k)]
        r_minus = [poly_sub(w[r], cs2[r]) for r in range(p.k)]
        r0_norm = max(
            max(abs(_center(low_bits(p, cc))) for cc in poly) for poly in r_minus
        )
        if r0_norm >= p.gamma2 - p.beta:
            continue
        ct0 = [ntt_inv(pw_mul(c_hat, t0_hat[r])) for r in range(p.k)]
        if max(inf_norm(poly) for poly in ct0) >= p.gamma2:
            continue
        h = [
            [
                make_hint(p, -_center(ct0[r][j]), _center(r_minus[r][j]) + _center(ct0[r][j]))
                for j in range(N)
            ]
            for r in range(p.k)
        ]
        if sum(sum(poly) for poly in h) > p.omega:
            continue
        return (
            ctilde
            + b"".join(bit_pack(poly, p.gamma1, p.z_bits) for poly in z)
            + hint_bit_pack(p, h)
        )


def verify_internal(p: MLDSAParams, pk: bytes, m_prime: bytes, sigma: bytes) -> bool:
    """Algorithm 8 ML-DSA.Verify_internal."""
    if len(sigma) != p.sig_len or len(pk) != p.pk_len:
        return False
    rho = pk[:32]
    t1 = [
        simple_bit_unpack(pk[32 + r * 320 : 32 + (r + 1) * 320], 23 - D)
        for r in range(p.k)
    ]
    ctilde = sigma[: p.ctilde_len]
    zb = 32 * p.z_bits
    off = p.ctilde_len
    z = [bit_unpack(sigma[off + s * zb : off + (s + 1) * zb], p.gamma1, p.z_bits) for s in range(p.l)]
    h = hint_bit_unpack(p, sigma[off + p.l * zb :])
    if h is None:
        return False
    if max(inf_norm(poly) for poly in z) >= p.gamma1 - p.beta:
        return False
    a_hat = expand_a(p, rho)
    tr = shake256(pk, 64)
    mu = shake256(tr + m_prime, 64)
    c = sample_in_ball(p, ctilde)
    c_hat = ntt(c)
    z_hat = [ntt(x) for x in z]
    az = _matvec(a_hat, z_hat, p.k, p.l)
    w_approx = []
    for r in range(p.k):
        t1_shift = [(coef << D) % Q for coef in t1[r]]
        ct1 = pw_mul(c_hat, ntt(t1_shift))
        w_approx.append(ntt_inv(poly_sub(az[r], ct1)))
    w1 = [
        b"".join(
            simple_bit_pack([use_hint(p, h[r][j], w_approx[r][j]) for j in range(N)], p.w1_bits)
            for r in range(p.k)
        )
    ][0]
    return ctilde == shake256(mu + w1, p.ctilde_len)


# -- external API (ctx-string form, FIPS 204 Algorithms 2-3) -----------------

def sign(p: MLDSAParams, sk: bytes, message: bytes, ctx: bytes = b"",
         rnd: bytes = b"\0" * 32) -> bytes:
    if len(ctx) > 255:
        raise ValueError("context too long")
    m_prime = bytes([0, len(ctx)]) + ctx + message
    return sign_internal(p, sk, m_prime, rnd)


def verify(p: MLDSAParams, pk: bytes, message: bytes, sigma: bytes, ctx: bytes = b"") -> bool:
    if len(ctx) > 255:
        return False
    m_prime = bytes([0, len(ctx)]) + ctx + message
    try:
        return verify_internal(p, pk, m_prime, sigma)
    except Exception:  # qrlint: disable=broad-except  — FIPS 204 verify contract: any malformed signature/key decodes to False, never an exception
        return False
