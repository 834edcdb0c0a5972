"""SHA-256 / HMAC / HKDF kernels vs hashlib/hmac oracles."""

import hashlib
import hmac as hmac_mod

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.core import sha256 as jsha


@pytest.mark.parametrize("length", [0, 1, 3, 32, 55, 56, 63, 64, 65, 127, 128, 300])
def test_sha256_matches_hashlib(length):
    rng = np.random.default_rng(length)
    data = rng.integers(0, 256, size=(4, length), dtype=np.uint8)
    out = np.asarray(jsha.sha256(data))
    for i in range(4):
        assert bytes(out[i]) == hashlib.sha256(data[i].tobytes()).digest()


def test_midstate_equals_full_hash():
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 256, size=(3, 64), dtype=np.uint8)
    tail = rng.integers(0, 256, size=(3, 22 + 16), dtype=np.uint8)
    st = jsha.midstate(prefix)
    out = np.asarray(jsha.sha256_from_midstate(st, tail, prefix_blocks=1))
    for i in range(3):
        assert bytes(out[i]) == hashlib.sha256(prefix[i].tobytes() + tail[i].tobytes()).digest()


@pytest.mark.parametrize("key_len,msg_len", [(32, 13), (64, 100), (80, 64)])
def test_hmac_matches_stdlib(key_len, msg_len):
    rng = np.random.default_rng(key_len * 100 + msg_len)
    key = rng.integers(0, 256, size=(2, key_len), dtype=np.uint8)
    msg = rng.integers(0, 256, size=(2, msg_len), dtype=np.uint8)
    out = np.asarray(jsha.hmac_sha256(key, msg))
    for i in range(2):
        ref = hmac_mod.new(key[i].tobytes(), msg[i].tobytes(), hashlib.sha256).digest()
        assert bytes(out[i]) == ref


@pytest.mark.parametrize("length", [32, 42, 64, 100])
def test_hkdf_matches_cryptography(length):
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.kdf.hkdf import HKDF

    rng = np.random.default_rng(length)
    ikm = rng.integers(0, 256, size=(2, 32), dtype=np.uint8)
    salt = rng.integers(0, 256, size=(2, 16), dtype=np.uint8)
    info = rng.integers(0, 256, size=(2, 20), dtype=np.uint8)
    out = np.asarray(jsha.hkdf_sha256(ikm, salt, info, length))
    for i in range(2):
        ref = HKDF(
            algorithm=hashes.SHA256(), length=length,
            salt=salt[i].tobytes(), info=info[i].tobytes(),
        ).derive(ikm[i].tobytes())
        assert bytes(out[i]) == ref
