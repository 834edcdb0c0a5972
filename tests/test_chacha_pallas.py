"""Batched ChaCha20-Poly1305 KATs: RFC 8439 vectors + scalar-twin parity.

The device data plane (core/chacha_pallas.py) must be bit-exact against
the pure-Python scalar twin (pyref/chacha_ref.py) — and against the
``cryptography`` package — at EVERY length
bucket, masked-tail edge (15/16/17-byte plaintexts), and AAD shape
(including empty AAD).  Fast tier runs the jnp twin; the Pallas kernel's
interpret-mode equality is slow-tier (interpret mode simulates every
vector op, like the keccak kernel tests).
"""

from __future__ import annotations


import numpy as np
import pytest

from quantum_resistant_p2p_tpu.core import chacha_pallas as cp
from quantum_resistant_p2p_tpu.pyref import chacha_ref as ref

# -- RFC 8439 vectors ---------------------------------------------------------

#: §2.8.2 AEAD vector
KEY = bytes(range(0x80, 0xA0))
NONCE = bytes([0x07, 0, 0, 0]) + bytes(range(0x40, 0x48))
AAD = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
PLAINTEXT = (b"Ladies and Gentlemen of the class of '99: If I could offer "
             b"you only one tip for the future, sunscreen would be it.")
CT_HEX = (
    "d31a8d34648e60db7b86afbc53ef7ec2a4aded51296e08fea9e2b5a736ee62d6"
    "3dbea45e8ca9671282fafb69da92728b1a71de0a9e060b2905d6a5b67ecd3b36"
    "92ddbd7f2d778b8c9803aee328091b58fab324e4fad675945585808b4831d7bc"
    "3ff4def08e4b7a9de576d26586cec64b6116"
)
TAG_HEX = "1ae10b594f09e26a7e902ecbd0600691"

#: §2.3.2 block function vector
BLOCK_KEY = bytes(range(32))
BLOCK_NONCE = bytes.fromhex("000000090000004a00000000")
BLOCK_OUT_HEX = (
    "10f1e7e4d13b5915500fdd1fa32071c4c7d1f4c733c068030422aa9ac3d46c4e"
    "d2826446079faa0914c2d705d98b02a2b5129cd1de164eb9cbd083e8a2503c4e"
)

#: §2.5.2 Poly1305 vector
POLY_KEY = bytes.fromhex(
    "85d6be7857556d337f4452fe42d506a80103808afb0db2fd4abff6af4149f51b")
POLY_MSG = b"Cryptographic Forum Research Group"
POLY_TAG = bytes.fromhex("a8061dc1305136c6c22b8baf0c0127a9")


def _seal_batch(keys, nonces, datas, aads, *, seal=True, use_pallas=False,
                interpret=False):
    """Pad a ragged batch to its pow2 buckets and run the jitted core."""
    from quantum_resistant_p2p_tpu.utils import next_pow2

    b = len(datas)
    l_bucket = 64 * next_pow2(max(1, max(-(-len(d) // 64) for d in datas)))
    a_bucket = 16 * next_pow2(max(1, max(-(-len(a) // 16) for a in aads)))
    data = np.zeros((b, l_bucket), np.uint8)
    aad = np.zeros((b, a_bucket), np.uint8)
    for i, (d, a) in enumerate(zip(datas, aads)):
        data[i, : len(d)] = np.frombuffer(d, np.uint8)
        aad[i, : len(a)] = np.frombuffer(a, np.uint8)
    out, tags = cp.aead_core(
        np.stack([np.frombuffer(k, np.uint8) for k in keys]),
        np.stack([np.frombuffer(n, np.uint8) for n in nonces]),
        data, np.array([len(d) for d in datas], np.int32),
        aad, np.array([len(a) for a in aads], np.int32),
        seal=seal, use_pallas=use_pallas, interpret=interpret,
    )
    return np.asarray(out), np.asarray(tags)


# -- pyref scalar twin vs the spec -------------------------------------------


def test_pyref_block_function_rfc_2_3_2():
    assert ref.chacha20_block(BLOCK_KEY, 1, BLOCK_NONCE).hex() == BLOCK_OUT_HEX


def test_pyref_poly1305_rfc_2_5_2():
    assert ref.poly1305_mac(POLY_KEY, POLY_MSG) == POLY_TAG


def test_pyref_aead_rfc_2_8_2():
    sealed = ref.seal(KEY, NONCE, PLAINTEXT, AAD)
    assert sealed[:-16].hex() == CT_HEX
    assert sealed[-16:].hex() == TAG_HEX
    assert ref.open_(KEY, NONCE, sealed, AAD) == PLAINTEXT
    bad = bytes([sealed[0] ^ 1]) + sealed[1:]
    with pytest.raises(ValueError):
        ref.open_(KEY, NONCE, bad, AAD)


# -- batched jnp core vs the spec and the twin --------------------------------


def test_device_core_rfc_2_8_2():
    out, tags = _seal_batch([KEY], [NONCE], [PLAINTEXT], [AAD])
    assert bytes(out[0][: len(PLAINTEXT)]).hex() == CT_HEX
    assert bytes(tags[0]).hex() == TAG_HEX
    # padded region stays zero (masked tail)
    assert not out[0][len(PLAINTEXT):].any()


#: every bucket edge the masking must get right: empty, sub-block,
#: one-byte-each-side of the 16-byte Poly1305 and 64-byte ChaCha blocks,
#: and across the pow2 length-bucket boundaries
TAIL_LENS = [0, 1, 15, 16, 17, 31, 32, 63, 64, 65, 127, 128, 129, 255, 256]


def test_device_core_masked_tails_match_pyref():
    rng = np.random.default_rng(7)
    keys = [bytes(rng.integers(0, 256, 32, dtype=np.uint8)) for _ in TAIL_LENS]
    nonces = [bytes(rng.integers(0, 256, 12, dtype=np.uint8)) for _ in TAIL_LENS]
    pts = [bytes(rng.integers(0, 256, n, dtype=np.uint8)) for n in TAIL_LENS]
    # every third item has EMPTY aad; the rest sweep aad block edges
    aads = [b"" if i % 3 == 0
            else bytes(rng.integers(0, 256, 5 * i + 1, dtype=np.uint8))
            for i in range(len(TAIL_LENS))]
    out, tags = _seal_batch(keys, nonces, pts, aads)
    for i, n in enumerate(TAIL_LENS):
        expect = ref.seal(keys[i], nonces[i], pts[i], aads[i])
        assert bytes(out[i][:n]) == expect[:-16], f"ct mismatch at len {n}"
        assert bytes(tags[i]) == expect[-16:], (
            f"tag mismatch at len {n}, aad {len(aads[i])}")


def test_device_core_open_path_and_tag_recompute():
    rng = np.random.default_rng(11)
    keys = [bytes(rng.integers(0, 256, 32, dtype=np.uint8)) for _ in range(4)]
    nonces = [bytes(rng.integers(0, 256, 12, dtype=np.uint8)) for _ in range(4)]
    pts = [bytes(rng.integers(0, 256, n, dtype=np.uint8))
           for n in (17, 64, 100, 200)]
    aads = [b"", b"a", b"ad" * 10, b"x" * 33]
    sealed = [ref.seal(k, n, p, a) for k, n, p, a in zip(keys, nonces, pts, aads)]
    out, tags = _seal_batch(keys, nonces, [s[:-16] for s in sealed], aads,
                            seal=False)
    for i, p in enumerate(pts):
        assert bytes(out[i][: len(p)]) == p
        assert bytes(tags[i]) == sealed[i][-16:]


def test_per_bucket_sizes_are_bit_exact():
    """One seal per bucket size (batch of 1 at each L bucket) — the shape
    the live queue compiles is exactly the shape the KAT pins.  The 64-,
    128- and 256-byte buckets are already covered batch-wise by the
    masked-tail sweep above; this pins the batch-1 programs at the
    buckets bracketing it (compile time is the suite's budget currency,
    so the sweep is minimal-but-bracketing)."""
    rng = np.random.default_rng(3)
    for n in (40, 700):
        key = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        nonce = bytes(rng.integers(0, 256, 12, dtype=np.uint8))
        pt = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        out, tags = _seal_batch([key], [nonce], [pt], [b"bucket-aad"])
        assert (bytes(out[0][:n]) + bytes(tags[0])
                == ref.seal(key, nonce, pt, b"bucket-aad"))


# -- cross-check vs the OpenSSL wheel -----------------------------------------


def test_device_core_matches_cryptography_wheel():
    from cryptography.hazmat.primitives.ciphers.aead import (
        ChaCha20Poly1305 as WheelChaCha)

    rng = np.random.default_rng(5)
    for n in (0, 16, 17, 64, 129):
        key = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        nonce = bytes(rng.integers(0, 256, 12, dtype=np.uint8))
        pt = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        out, tags = _seal_batch([key], [nonce], [pt], [b"wheel-aad"])
        assert (bytes(out[0][:n]) + bytes(tags[0])
                == WheelChaCha(key).encrypt(nonce, pt, b"wheel-aad"))


# -- device capability + scalar provider -------------------------------------


def test_chacha_device_capability_roundtrip():
    from quantum_resistant_p2p_tpu.provider.aead_device import ChaChaPolyDevice

    dev = ChaChaPolyDevice(use_pallas=False)
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 256, (3, 32), dtype=np.uint8)
    nonces = rng.integers(0, 256, (3, 12), dtype=np.uint8)
    pts = [b"", b"short", bytes(rng.integers(0, 256, 99, dtype=np.uint8))]
    aads = [b"", b"ad", b""]
    sealed = dev.seal_batch(keys, nonces, pts, aads)
    for i, s in enumerate(sealed):
        assert s == ref.seal(bytes(keys[i]), bytes(nonces[i]), pts[i], aads[i])
    opened = dev.open_batch(keys, nonces, sealed, aads)
    assert opened == pts
    # one tampered item fails alone — its batch mates still open
    bad = list(sealed)
    bad[1] = bytes([bad[1][0] ^ 0xFF]) + bad[1][1:]
    results = dev.open_batch(keys, nonces, bad, aads)
    assert results[0] == pts[0] and results[2] == pts[2]
    assert isinstance(results[1], ValueError)


def test_scalar_provider_wheel_less_fallback():
    """The registry's scalar ChaCha20-Poly1305 works without the OpenSSL
    wheel (pyref twin) and is KAT-exact + wire-compatible both ways."""
    from quantum_resistant_p2p_tpu.provider import get_symmetric

    algo = get_symmetric("ChaCha20-Poly1305")
    assert algo.seal(KEY, NONCE, PLAINTEXT, AAD).hex() == CT_HEX + TAG_HEX
    blob = algo.encrypt(KEY, b"scalar wire", b"ad")
    assert algo.decrypt(KEY, blob, b"ad") == b"scalar wire"
    # pyref opens what the provider sealed (format: nonce || ct || tag)
    assert ref.open_(KEY, blob[:12], blob[12:], b"ad") == b"scalar wire"
    with pytest.raises(ValueError):
        algo.decrypt(KEY, blob[:-1] + bytes([blob[-1] ^ 1]), b"ad")
    with pytest.raises(ValueError):
        algo.decrypt(b"short", blob, b"ad")


def test_aead_health_probe_passes_and_rejects_broken_device():
    from quantum_resistant_p2p_tpu.provider import get_symmetric
    from quantum_resistant_p2p_tpu.provider.aead_device import ChaChaPolyDevice
    from quantum_resistant_p2p_tpu.provider.health import _check_aead

    class _Facade:
        def __init__(self):
            self.device = ChaChaPolyDevice(use_pallas=False)
            self.scalar = get_symmetric("ChaCha20-Poly1305")
            self.name = self.device.name

    facade = _Facade()
    verdict = _check_aead(facade)
    assert verdict.ok, verdict.detail

    # a device computing wrong bytes must FAIL the gate (quarantine path)
    broken = _Facade()
    good_seal = broken.device.seal_batch

    def bad_seal(keys, nonces, pts, aads):
        out = good_seal(keys, nonces, pts, aads)
        return [bytes(len(s)) for s in out]

    broken.device.seal_batch = bad_seal
    assert not _check_aead(broken).ok


# -- Pallas kernel (interpret mode; slow tier like the keccak kernel) --------


@pytest.mark.slow
def test_pallas_kernel_matches_jnp_twin_and_spec():
    out, tags = _seal_batch([KEY], [NONCE], [PLAINTEXT], [AAD],
                            use_pallas=True, interpret=True)
    assert bytes(out[0][: len(PLAINTEXT)]).hex() == CT_HEX
    assert bytes(tags[0]).hex() == TAG_HEX


@pytest.mark.slow
def test_pallas_block_launcher_matches_jnp():
    rng = np.random.default_rng(13)
    states = rng.integers(0, 2 ** 32, (12, 7), dtype=np.uint32)
    a = np.asarray(cp.chacha_blocks(states, interpret=True))
    b = np.asarray(cp.chacha_blocks_jnp(states))
    assert (a == b).all()
