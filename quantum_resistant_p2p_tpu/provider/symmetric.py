"""AEAD algorithms: AES-256-GCM and ChaCha20-Poly1305.

Scalar host-side path (OpenSSL via the ``cryptography`` package), as in the
reference (crypto/symmetric.py:66-258).  One addition over the reference:

deterministic-nonce ``seal``/``open_`` primitives (``encrypt`` is
``urandom nonce + seal``): the batched device AEAD's cpu fallback and its
cross-check tests need the nonce as an explicit operand.

Wire format parity: 12-byte random nonce prepended to the ciphertext
(crypto/symmetric.py:110-146); authentication failure raises ValueError
(crypto/symmetric.py:159-161).
"""

from __future__ import annotations

import os

from cryptography.exceptions import InvalidTag
from cryptography.hazmat.primitives.ciphers import aead as _aead

from .base import SymmetricAlgorithm


class _AEADBase(SymmetricAlgorithm):
    _impl = ""  # cryptography AEAD class name

    key_size = 32
    nonce_size = 12
    tag_size = 16

    def generate_key(self) -> bytes:
        return os.urandom(self.key_size)

    @property
    def _cipher(self):
        return getattr(_aead, self._impl)

    def _check_key(self, key: bytes) -> None:
        if len(key) != self.key_size:
            raise ValueError(f"{self.name} requires a {self.key_size}-byte key")

    def seal(self, key: bytes, nonce: bytes, plaintext: bytes,
             associated_data: bytes | None = None) -> bytes:
        self._check_key(key)
        if len(nonce) != self.nonce_size:
            raise ValueError(f"{self.name} requires a {self.nonce_size}-byte nonce")
        return self._cipher(key).encrypt(bytes(nonce), bytes(plaintext),
                                         associated_data)

    def open_(self, key: bytes, nonce: bytes, data: bytes,
              associated_data: bytes | None = None) -> bytes:
        self._check_key(key)
        if len(data) < self.tag_size:
            raise ValueError("ciphertext too short")
        try:
            return self._cipher(key).decrypt(bytes(nonce), bytes(data),
                                             associated_data)
        except InvalidTag as e:
            raise ValueError("authentication failed") from e

    def encrypt(self, key: bytes, plaintext: bytes, associated_data: bytes | None = None) -> bytes:
        nonce = os.urandom(self.nonce_size)
        return nonce + self.seal(key, nonce, plaintext, associated_data)

    def decrypt(self, key: bytes, data: bytes, associated_data: bytes | None = None) -> bytes:
        self._check_key(key)
        if len(data) < self.nonce_size + self.tag_size:
            raise ValueError("ciphertext too short")
        data = memoryview(data)  # zero-copy split (binary wire hands views)
        return self.open_(key, bytes(data[: self.nonce_size]),
                          data[self.nonce_size:], associated_data)


class AES256GCM(_AEADBase):
    _impl = "AESGCM"
    name = "AES-256-GCM"
    display_name = "AES-256-GCM"
    description = "AES in Galois/Counter Mode with 256-bit keys (NIST SP 800-38D)"
    security_level = 5
    backend = "cpu"


class ChaCha20Poly1305(_AEADBase):
    _impl = "ChaCha20Poly1305"
    name = "ChaCha20-Poly1305"
    display_name = "ChaCha20-Poly1305"
    description = "RFC 8439 ChaCha20-Poly1305 AEAD"
    security_level = 5
    backend = "cpu"
