"""FrodoKEM pure-Python oracle: sizes + roundtrip + implicit rejection."""

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.pyref import frodo_ref as fr

RNG = np.random.default_rng(64)


def _rand(n):
    return bytes(RNG.integers(0, 256, size=n, dtype=np.uint8))


@pytest.mark.parametrize("name", ["FrodoKEM-640-AES", "FrodoKEM-640-SHAKE"])
def test_roundtrip(name):
    p = fr.PARAMS[name]
    pk, sk = fr.keygen(p, _rand(p.len_sec), _rand(p.len_sec), _rand(p.len_sec))
    assert len(pk) == p.pk_len and len(sk) == p.sk_len
    mu = _rand(p.len_sec)
    ct, ss = fr.encaps(p, pk, mu)
    assert len(ct) == p.ct_len and len(ss) == p.len_sec
    assert fr.decaps(p, sk, ct) == ss
    # implicit rejection: corrupt ciphertext -> pseudorandom, not an error
    bad = bytearray(ct)
    bad[5] ^= 0xFF
    ss_bad = fr.decaps(p, sk, bytes(bad))
    assert ss_bad != ss and len(ss_bad) == p.len_sec


def test_determinism():
    p = fr.PARAMS["FrodoKEM-640-AES"]
    seeds = (_rand(p.len_sec), _rand(p.len_sec), _rand(p.len_sec))
    assert fr.keygen(p, *seeds) == fr.keygen(p, *seeds)
