"""FrodoKEM batched JAX vs pure-Python oracle + AES kernel checks."""

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.pyref import frodo_ref as fr

RNG = np.random.default_rng(640)


def test_aes_kernel_matches_cryptography():
    from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

    from quantum_resistant_p2p_tpu.core import aes as jaes

    keys = RNG.integers(0, 256, size=(3, 16), dtype=np.uint8)
    blocks = RNG.integers(0, 256, size=(3, 5, 16), dtype=np.uint8)
    rk = jaes.key_schedule(keys)
    out = np.asarray(jaes.encrypt_blocks(rk, blocks))
    for i in range(3):
        enc = Cipher(algorithms.AES(keys[i].tobytes()), modes.ECB()).encryptor()
        ref = enc.update(blocks[i].tobytes())
        assert out[i].tobytes() == ref


@pytest.mark.parametrize("name", ["FrodoKEM-640-AES", "FrodoKEM-640-SHAKE"])
def test_matches_oracle(name):
    from quantum_resistant_p2p_tpu.kem import frodo as jfr

    p = fr.PARAMS[name]
    batch = 2
    kg, enc, dec = jfr.get(name)
    s = RNG.integers(0, 256, size=(batch, p.len_sec), dtype=np.uint8)
    se = RNG.integers(0, 256, size=(batch, p.len_sec), dtype=np.uint8)
    z = RNG.integers(0, 256, size=(batch, p.len_sec), dtype=np.uint8)
    mu = RNG.integers(0, 256, size=(batch, p.len_sec), dtype=np.uint8)
    pk, sk = np.asarray(kg(s, se, z)[0]), np.asarray(kg(s, se, z)[1])
    ct, ss = enc(pk, mu)
    ct, ss = np.asarray(ct), np.asarray(ss)
    ss_dec = np.asarray(dec(sk, ct))
    for i in range(batch):
        rpk, rsk = fr.keygen(p, s[i].tobytes(), se[i].tobytes(), z[i].tobytes())
        assert bytes(pk[i]) == rpk
        assert bytes(sk[i]) == rsk
        rct, rss = fr.encaps(p, rpk, mu[i].tobytes())
        assert bytes(ct[i]) == rct
        assert bytes(ss[i]) == rss
        assert bytes(ss_dec[i]) == rss
    # implicit rejection on tampered ct
    bad = ct.copy()
    bad[:, 3] ^= 0xFF
    ss_bad = np.asarray(dec(sk, bad))
    assert not (ss_bad == ss).all(axis=-1).any()


@pytest.mark.slow
@pytest.mark.parametrize("name", ["FrodoKEM-976-SHAKE", "FrodoKEM-1344-AES"])
def test_large_sets_roundtrip(name):
    """976/1344: JAX self-consistency (pyref too slow at these sizes)."""
    from quantum_resistant_p2p_tpu.kem import frodo as jfr

    p = fr.PARAMS[name]
    kg, enc, dec = jfr.get(name)
    s = RNG.integers(0, 256, size=(1, p.len_sec), dtype=np.uint8)
    se = RNG.integers(0, 256, size=(1, p.len_sec), dtype=np.uint8)
    z = RNG.integers(0, 256, size=(1, p.len_sec), dtype=np.uint8)
    mu = RNG.integers(0, 256, size=(1, p.len_sec), dtype=np.uint8)
    pk, sk = kg(s, se, z)
    assert pk.shape[-1] == p.pk_len and sk.shape[-1] == p.sk_len
    ct, ss = enc(np.asarray(pk), mu)
    assert ct.shape[-1] == p.ct_len
    assert (np.asarray(dec(np.asarray(sk), np.asarray(ct))) == np.asarray(ss)).all()


def test_provider_cross_backend():
    from quantum_resistant_p2p_tpu.provider import get_kem

    tpu = get_kem("FrodoKEM-640-AES", backend="tpu")
    cpu = get_kem("FrodoKEM-640-AES", backend="cpu")
    pk, sk = tpu.generate_keypair()
    ct, ss = cpu.encapsulate(pk)
    assert tpu.decapsulate(sk, ct) == ss


def test_bitsliced_aes_matches_gather_and_openssl():
    """The table-free bitsliced AES (core/aes_bitsliced.py) is bit-exact vs
    both the gather implementation and the OpenSSL oracle, including a
    non-multiple-of-32 block count (packing pad path)."""
    from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

    from quantum_resistant_p2p_tpu.core import aes, aes_bitsliced

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 256, (3, 16), dtype=np.uint8)
    blocks = rng.integers(0, 256, (3, 45, 16), dtype=np.uint8)
    rk = aes.key_schedule(keys)
    ref = np.asarray(aes.encrypt_blocks(rk, blocks))
    got = np.asarray(aes_bitsliced.encrypt_blocks(rk, blocks))
    assert np.array_equal(got, ref)
    enc = Cipher(algorithms.AES(bytes(keys[1])), modes.ECB()).encryptor()
    assert enc.update(bytes(blocks[1].reshape(-1))) == bytes(got[1].reshape(-1))


def test_sbox_circuits_exhaustive():
    """Both bitsliced S-box circuits (Boyar-Peralta default + the derived
    field circuit) equal the table S-box on all 256 byte values."""
    import jax.numpy as jnp

    from quantum_resistant_p2p_tpu.core import aes_bitsliced as bs

    vals = np.arange(256, dtype=np.uint64)
    # pack 256 values as bit planes, 4 uint32 words x 2 lanes shape (8,)
    planes = []
    for i in range(8):
        bits = ((vals >> i) & 1).astype(np.uint32)
        words = (bits.reshape(8, 32) << np.arange(32, dtype=np.uint32)).sum(
            axis=1, dtype=np.uint32
        )
        planes.append(jnp.asarray(words))
    for circuit in (bs._sbox_planes_bp, bs._sbox_planes_derived):
        out = [np.asarray(p) for p in circuit(planes)]
        res = np.zeros(256, dtype=np.uint8)
        for i in range(8):
            bits = (out[i][:, None] >> np.arange(32, dtype=np.uint32)) & 1
            res |= (bits.reshape(-1).astype(np.uint8) << i)
        assert np.array_equal(res, bs._SBOX), circuit.__name__
