"""Pure-Python RFC 8439 ChaCha20-Poly1305 — the scalar reference twin.

The KAT oracle for the batched device AEAD (core/chacha_pallas.py): the
device seal/open must be bit-exact against this implementation at every
length bucket, masked tail, and AAD shape (tests/test_chacha_pallas.py
pins the RFC 8439 §2.8.2 vector through BOTH paths).

Spec: RFC 8439 (ChaCha20 §2.3, Poly1305 §2.5, AEAD construction §2.8).
Performance is NOT a goal here — the whole point of the device path is
that this scalar twin is slow.
"""

from __future__ import annotations

import hmac as _hmac
import struct

_MASK32 = 0xFFFFFFFF
_CONSTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)
#: Poly1305 prime 2^130 - 5
_P1305 = (1 << 130) - 5

KEY_SIZE = 32
NONCE_SIZE = 12
TAG_SIZE = 16


def _rotl32(x: int, n: int) -> int:
    return ((x << n) | (x >> (32 - n))) & _MASK32


def _quarter(state: list[int], a: int, b: int, c: int, d: int) -> None:
    state[a] = (state[a] + state[b]) & _MASK32
    state[d] = _rotl32(state[d] ^ state[a], 16)
    state[c] = (state[c] + state[d]) & _MASK32
    state[b] = _rotl32(state[b] ^ state[c], 12)
    state[a] = (state[a] + state[b]) & _MASK32
    state[d] = _rotl32(state[d] ^ state[a], 8)
    state[c] = (state[c] + state[d]) & _MASK32
    state[b] = _rotl32(state[b] ^ state[c], 7)


def chacha20_block(key: bytes, counter: int, nonce: bytes) -> bytes:
    """One 64-byte ChaCha20 block (RFC 8439 §2.3)."""
    if len(key) != KEY_SIZE:
        raise ValueError("ChaCha20 key must be 32 bytes")
    if len(nonce) != NONCE_SIZE:
        raise ValueError("ChaCha20 nonce must be 12 bytes")
    init = list(_CONSTS)
    init += list(struct.unpack("<8I", key))
    init.append(counter & _MASK32)
    init += list(struct.unpack("<3I", nonce))
    x = list(init)
    for _ in range(10):
        _quarter(x, 0, 4, 8, 12)
        _quarter(x, 1, 5, 9, 13)
        _quarter(x, 2, 6, 10, 14)
        _quarter(x, 3, 7, 11, 15)
        _quarter(x, 0, 5, 10, 15)
        _quarter(x, 1, 6, 11, 12)
        _quarter(x, 2, 7, 8, 13)
        _quarter(x, 3, 4, 9, 14)
    return struct.pack("<16I", *((x[i] + init[i]) & _MASK32 for i in range(16)))


def chacha20_xor(key: bytes, counter: int, nonce: bytes, data: bytes) -> bytes:
    """XOR ``data`` with the keystream starting at block ``counter``."""
    out = bytearray(len(data))
    for blk in range(-(-len(data) // 64)):
        ks = chacha20_block(key, counter + blk, nonce)
        lo = 64 * blk
        chunk = data[lo : lo + 64]
        out[lo : lo + len(chunk)] = bytes(a ^ b for a, b in zip(chunk, ks))
    return bytes(out)


def poly1305_mac(key: bytes, msg: bytes) -> bytes:
    """Poly1305 tag (RFC 8439 §2.5.1) over arbitrary-length ``msg``."""
    r = int.from_bytes(key[:16], "little")
    r &= 0x0FFFFFFC0FFFFFFC0FFFFFFC0FFFFFFF
    s = int.from_bytes(key[16:32], "little")
    acc = 0
    for i in range(0, len(msg), 16):
        block = msg[i : i + 16]
        n = int.from_bytes(block, "little") + (1 << (8 * len(block)))
        acc = ((acc + n) * r) % _P1305
    return ((acc + s) & ((1 << 128) - 1)).to_bytes(16, "little")


def _pad16(data: bytes) -> bytes:
    rem = len(data) % 16
    return data if rem == 0 else data + bytes(16 - rem)


def _mac_data(aad: bytes, ct: bytes) -> bytes:
    """AEAD MAC input (RFC 8439 §2.8): padded AAD, padded ciphertext, lens."""
    return (_pad16(aad) + _pad16(ct)
            + struct.pack("<QQ", len(aad), len(ct)))


def seal(key: bytes, nonce: bytes, plaintext: bytes,
         aad: bytes = b"") -> bytes:
    """-> ciphertext || 16-byte tag (RFC 8439 §2.8.1)."""
    otk = chacha20_block(key, 0, nonce)[:32]
    ct = chacha20_xor(key, 1, nonce, plaintext)
    return ct + poly1305_mac(otk, _mac_data(aad, ct))


def open_(key: bytes, nonce: bytes, data: bytes, aad: bytes = b"") -> bytes:
    """Verify-then-decrypt ``ciphertext || tag``; ValueError on a bad tag."""
    if len(data) < TAG_SIZE:
        raise ValueError("ciphertext too short")
    ct, tag = data[:-TAG_SIZE], data[-TAG_SIZE:]
    otk = chacha20_block(key, 0, nonce)[:32]
    want = poly1305_mac(otk, _mac_data(aad, ct))
    if not _hmac.compare_digest(tag, want):
        raise ValueError("authentication failed")
    return chacha20_xor(key, 1, nonce, ct)
