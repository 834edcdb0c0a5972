"""The plain reference that decides ``correct``: FIPS 203 ML-KEM and
FIPS 204 ML-DSA in pure Python on ``hashlib`` (``mlkem.py``, ``mldsa.py``,
copied from the clean-room ``pyref`` oracles so that no later change to the
program can change the yardstick), and the protocol's session-key rule.

It imports nothing of the program and takes nothing the program made
except the bytes that crossed the wire and the keys the program reports.
"""

from __future__ import annotations

import hashlib
import hmac

from . import mldsa, mlkem


def hkdf_sha256(ikm: bytes, salt: bytes, info: bytes, length: int = 32) -> bytes:
    """RFC 5869 HKDF-SHA256."""
    prk = hmac.new(salt or bytes(32), ikm, hashlib.sha256).digest()
    okm, t, i = b"", b"", 1
    while len(okm) < length:
        t = hmac.new(prk, t + info + bytes([i]), hashlib.sha256).digest()
        okm += t
        i += 1
    return okm[:length]


def message_key(secret: bytes, id_a: str, id_b: str, aead: str) -> bytes:
    """The session's message key: HKDF over the KEM secret, salted by the
    sorted peer ids, bound to the AEAD's name (docs/protocol.md)."""
    salt = "|".join(sorted([id_a, id_b])).encode()
    return hkdf_sha256(secret, salt, b"qrp2p-tpu/msgkey/" + aead.encode())


def verify(sig_name: str, pk: bytes, message: bytes, sig: bytes) -> bool:
    """FIPS 204 ML-DSA.Verify in pure mode with the empty context."""
    return mldsa.verify(mldsa.PARAMS[sig_name], pk, message, sig)


def decaps(kem_name: str, dk: bytes, ct: bytes) -> bytes:
    """FIPS 203 ML-KEM.Decaps."""
    return mlkem.decaps(mlkem.PARAMS[kem_name], dk, ct)
