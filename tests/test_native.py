"""Native C++ core: bit-exact vs hashlib and the pure-Python ML-KEM oracle."""

import hashlib

import numpy as np
import pytest

from quantum_resistant_p2p_tpu import native
from quantum_resistant_p2p_tpu.pyref import mlkem_ref

RNG = np.random.default_rng(3329)


def test_shake256_matches_hashlib():
    for ln in (0, 1, 135, 136, 137, 500):
        data = bytes(RNG.integers(0, 256, size=ln, dtype=np.uint8))
        assert native.shake256(data, 64) == hashlib.shake_256(data).digest(64)


@pytest.mark.parametrize("name", ["ML-KEM-512", "ML-KEM-768", "ML-KEM-1024"])
def test_mlkem_matches_pyref(name):
    p = mlkem_ref.PARAMS[name]
    nk = native.NativeMLKEM(name)
    d = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    z = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    m = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    ek, dk = nk.keygen(d, z)
    rek, rdk = mlkem_ref.keygen(p, d, z)
    assert ek == rek and dk == rdk
    key, ct = nk.encaps(ek, m)
    rkey, rct = mlkem_ref.encaps(p, ek, m)
    assert key == rkey and ct == rct
    assert nk.decaps(dk, ct) == key
    # implicit rejection path agrees with the oracle too
    bad = bytearray(ct)
    bad[0] ^= 1
    assert nk.decaps(dk, bytes(bad)) == mlkem_ref.decaps(p, dk, bytes(bad))


@pytest.mark.parametrize("name", ["ML-DSA-44", "ML-DSA-65", "ML-DSA-87"])
def test_mldsa_matches_pyref(name):
    from quantum_resistant_p2p_tpu.pyref import mldsa_ref

    p = mldsa_ref.PARAMS[name]
    nd = native.NativeMLDSA(name)
    xi = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    rnd = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    pk, sk = nd.keygen(xi)
    rpk, rsk = mldsa_ref.keygen(p, xi)
    assert pk == rpk and sk == rsk
    m_prime = bytes([0, 0]) + b"native vs pyref"
    sig = nd.sign_internal(sk, m_prime, rnd)
    assert sig == mldsa_ref.sign_internal(p, sk, m_prime, rnd)
    assert nd.verify_internal(pk, m_prime, sig)
    assert mldsa_ref.verify_internal(p, pk, m_prime, sig)
    bad = bytearray(sig)
    bad[17] ^= 1
    assert not nd.verify_internal(pk, m_prime, bytes(bad))
    assert not nd.verify_internal(pk, bytes([0, 0]) + b"other message", sig)


def test_mldsa_provider_native_cpu_interop():
    """cpu provider (native fast path) and pyref agree through the plugin API."""
    from quantum_resistant_p2p_tpu.provider.sig_providers import MLDSASignature

    alg = MLDSASignature(security_level=3, backend="cpu")
    assert alg._native is not None  # toolchain present (module-level skip)
    pk, sk = alg.generate_keypair()
    sig = alg.sign(sk, b"interop message")
    assert alg.verify(pk, b"interop message", sig)
    assert not alg.verify(pk, b"tampered message", sig)
    from quantum_resistant_p2p_tpu.pyref import mldsa_ref

    assert mldsa_ref.verify(mldsa_ref.MLDSA65, pk, b"interop message", sig)


def test_sha2_matches_hashlib():
    import hmac as hmac_mod

    lib = native.load()
    for ln in (0, 1, 55, 56, 63, 64, 65, 111, 112, 127, 128, 300):
        data = bytes(RNG.integers(0, 256, size=ln, dtype=np.uint8))
        out32 = (__import__("ctypes").c_uint8 * 32)()
        out64 = (__import__("ctypes").c_uint8 * 64)()
        lib.qrp_sha256(native._buf(data), ln, out32)
        lib.qrp_sha512(native._buf(data), ln, out64)
        assert bytes(out32) == hashlib.sha256(data).digest()
        assert bytes(out64) == hashlib.sha512(data).digest()
    key = bytes(RNG.integers(0, 256, size=32, dtype=np.uint8))
    msg = bytes(RNG.integers(0, 256, size=99, dtype=np.uint8))
    out32 = (__import__("ctypes").c_uint8 * 32)()
    lib.qrp_hmac_sha256(native._buf(key), 32, native._buf(msg), 99, out32)
    assert bytes(out32) == hmac_mod.new(key, msg, hashlib.sha256).digest()


@pytest.mark.parametrize(
    "name",
    [
        "SPHINCS+-SHA2-128s-simple",
        "SPHINCS+-SHA2-128f-simple",
        pytest.param("SPHINCS+-SHA2-192s-simple", marks=pytest.mark.slow),
        pytest.param("SPHINCS+-SHA2-192f-simple", marks=pytest.mark.slow),
        pytest.param("SPHINCS+-SHA2-256s-simple", marks=pytest.mark.slow),
        pytest.param("SPHINCS+-SHA2-256f-simple", marks=pytest.mark.slow),
    ],
)
def test_slhdsa_matches_pyref(name):
    from quantum_resistant_p2p_tpu.pyref import slhdsa_ref

    p = slhdsa_ref.PARAMS[name]
    ns = native.NativeSLHDSA(name)
    ss, sp, ps = (bytes(RNG.integers(0, 256, size=p.n, dtype=np.uint8)) for _ in range(3))
    pk, sk = ns.keygen(ss, sp, ps)
    rpk, rsk = slhdsa_ref.keygen(p, ss, sp, ps)
    assert pk == rpk and sk == rsk
    msg = b"native vs pyref slhdsa"
    sig = ns.sign_internal(msg, sk)
    assert sig == slhdsa_ref.sign_internal(p, msg, sk, None)
    assert ns.verify_internal(msg, sig, pk)
    bad = bytearray(sig)
    bad[40] ^= 1
    assert not ns.verify_internal(msg, bytes(bad), pk)
    assert not ns.verify_internal(b"other", sig, pk)
    # hedged variant agrees too
    ar = bytes(RNG.integers(0, 256, size=p.n, dtype=np.uint8))
    assert ns.sign_internal(msg, sk, ar) == slhdsa_ref.sign_internal(p, msg, sk, ar)


def test_slhdsa_provider_native_cpu_interop():
    from quantum_resistant_p2p_tpu.provider.sig_providers import SPHINCSSignature

    alg = SPHINCSSignature(security_level=1, backend="cpu", fast=True)
    assert alg._native is not None
    pk, sk = alg.generate_keypair()
    sig = alg.sign(sk, b"interop")
    assert alg.verify(pk, b"interop", sig)
    assert not alg.verify(pk, b"tampered", sig)
    from quantum_resistant_p2p_tpu.pyref import slhdsa_ref

    assert slhdsa_ref.verify(slhdsa_ref.SLH128F, pk, b"interop", sig)
    # small-signature variant through the registry
    from quantum_resistant_p2p_tpu.provider import get_signature

    s128 = get_signature("SPHINCS+-SHA2-128s-simple", backend="cpu")
    assert s128.signature_len == 7856
    pk, sk = s128.generate_keypair()
    sig = s128.sign(sk, b"small sig")
    assert s128.verify(pk, b"small sig", sig)


def test_aes128_matches_fips197_and_openssl():
    import ctypes

    from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

    lib = native.load()
    out = (ctypes.c_uint8 * 16)()
    # FIPS-197 Appendix C.1
    key = bytes.fromhex("000102030405060708090a0b0c0d0e0f")
    pt = bytes.fromhex("00112233445566778899aabbccddeeff")
    lib.qrp_aes128_ecb(native._buf(key), native._buf(pt), 1, out)
    assert bytes(out).hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"
    for _ in range(20):
        key = bytes(RNG.integers(0, 256, size=16, dtype=np.uint8))
        pt = bytes(RNG.integers(0, 256, size=16, dtype=np.uint8))
        ref = Cipher(algorithms.AES(key), modes.ECB()).encryptor().update(pt)
        lib.qrp_aes128_ecb(native._buf(key), native._buf(pt), 1, out)
        assert bytes(out) == ref


@pytest.mark.parametrize(
    "name",
    [
        "FrodoKEM-640-AES",
        "FrodoKEM-640-SHAKE",
        pytest.param("FrodoKEM-976-AES", marks=pytest.mark.slow),
        pytest.param("FrodoKEM-976-SHAKE", marks=pytest.mark.slow),
        pytest.param("FrodoKEM-1344-AES", marks=pytest.mark.slow),
        pytest.param("FrodoKEM-1344-SHAKE", marks=pytest.mark.slow),
    ],
)
def test_frodo_matches_pyref(name):
    from quantum_resistant_p2p_tpu.pyref import frodo_ref

    p = frodo_ref.PARAMS[name]
    nf = native.NativeFrodoKEM(name)
    s, se, z, mu = (
        bytes(RNG.integers(0, 256, size=p.len_sec, dtype=np.uint8)) for _ in range(4)
    )
    pk, sk = nf.keygen(s, se, z)
    rpk, rsk = frodo_ref.keygen(p, s, se, z)
    assert pk == rpk and sk == rsk
    ct, ss = nf.encaps(pk, mu)
    rct, rss = frodo_ref.encaps(p, pk, mu)
    assert ct == rct and ss == rss
    assert nf.decaps(sk, ct) == ss
    bad = bytearray(ct)
    bad[5] ^= 1
    assert nf.decaps(sk, bytes(bad)) == frodo_ref.decaps(p, sk, bytes(bad))


@pytest.mark.parametrize(
    "name",
    ["HQC-128",
     pytest.param("HQC-192", marks=pytest.mark.slow),
     pytest.param("HQC-256", marks=pytest.mark.slow)],
)
def test_hqc_matches_pyref(name):
    from quantum_resistant_p2p_tpu.pyref import hqc_ref

    p = hqc_ref.PARAMS[name]
    nh = native.NativeHQC(name)
    sk_seed = bytes(RNG.integers(0, 256, size=40, dtype=np.uint8))
    pk_seed = bytes(RNG.integers(0, 256, size=40, dtype=np.uint8))
    sigma = bytes(RNG.integers(0, 256, size=p.k, dtype=np.uint8))
    m = bytes(RNG.integers(0, 256, size=p.k, dtype=np.uint8))
    salt = bytes(RNG.integers(0, 256, size=16, dtype=np.uint8))
    pk, sk = nh.keygen(sk_seed, sigma, pk_seed)
    rpk, rsk = hqc_ref.keygen(p, sk_seed, sigma, pk_seed)
    assert pk == rpk and sk == rsk
    ct, ss = nh.encaps(pk, m, salt)
    rct, rss = hqc_ref.encaps(p, pk, m, salt)
    assert ct == rct and ss == rss
    assert nh.decaps(sk, ct) == ss
    # corrupted ciphertext follows the oracle through decode + implicit reject
    bad = bytearray(ct)
    bad[11] ^= 0xFF
    assert nh.decaps(sk, bytes(bad)) == hqc_ref.decaps(p, sk, bytes(bad))


def test_hqc_provider_native_cpu_interop():
    from quantum_resistant_p2p_tpu.provider.kem_providers import HQCKeyExchange

    alg = HQCKeyExchange(security_level=1, backend="cpu")
    assert alg._native is not None
    pk, sk = alg.generate_keypair()
    ct, ss = alg.encapsulate(pk)
    assert alg.decapsulate(sk, ct) == ss
    assert "native C++" in alg.description


def test_frodo_provider_native_cpu_interop():
    from quantum_resistant_p2p_tpu.provider.kem_providers import FrodoKEMKeyExchange

    alg = FrodoKEMKeyExchange(security_level=1, backend="cpu", use_aes=True)
    assert alg._native is not None
    pk, sk = alg.generate_keypair()
    ct, ss = alg.encapsulate(pk)
    assert alg.decapsulate(sk, ct) == ss
    assert "native C++" in alg.description


def test_zeroize():
    buf = bytearray(b"secret material")
    native.zeroize(buf)
    assert bytes(buf) == b"\0" * len(buf)


def test_wipe_polyglot_buffers():
    """native.wipe() is the shared end-of-life marker for secret buffers
    of whatever type a provider handed back: bytearrays go through the
    native cleanse, writable array-likes are zero-filled in place, and
    immutable operands (bytes, read-only/device arrays) are tolerated —
    the GC handoff is a documented limitation, not a crash."""
    buf = bytearray(b"secret material")
    arr = np.arange(8, dtype=np.float32) + 1.0
    frozen = b"immutable"
    native.wipe(buf, arr, frozen, None)
    assert bytes(buf) == b"\0" * len(buf)
    assert not arr.any()  # zero-filled for real, not just dereferenced
    assert frozen == b"immutable"
    ro = np.ones(4, dtype=np.float32)
    ro.setflags(write=False)
    native.wipe(ro)  # read-only: the immutable-operand path, no raise
    assert ro.any()
