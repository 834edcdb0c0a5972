"""The ML-KEM + ML-DSA suite family (FIPS 203 / FIPS 204).

The hub's providers at the configuration's level and backend, the
identities of the hub and of its clients (the native core, from a 32-byte
seed), and the plain reference the check holds them to
(``benchmark/reference``, which imports nothing of the program).
"""

from __future__ import annotations

from benchmark import reference

_KEM_LEVEL = {"ML-KEM-512": 1, "ML-KEM-768": 3, "ML-KEM-1024": 5}
_SIG_LEVEL = {"ML-DSA-44": 2, "ML-DSA-65": 3, "ML-DSA-87": 5}


def providers(suite: dict, backend: str, opcache_size: int) -> tuple:
    """The hub's KEM and signature providers."""
    from quantum_resistant_p2p_tpu.provider.kem_providers import (
        MLKEMKeyExchange)
    from quantum_resistant_p2p_tpu.provider.sig_providers import (
        MLDSASignature)

    return (MLKEMKeyExchange(_KEM_LEVEL[suite["kem"]], backend=backend,
                             opcache_size=opcache_size),
            MLDSASignature(_SIG_LEVEL[suite["signature"]], backend=backend,
                           opcache_size=opcache_size))


def identity(suite: dict, seed: bytes) -> tuple[bytes, bytes]:
    """A long-lived signing identity ``(pk, sk)`` from a 32-byte seed."""
    from quantum_resistant_p2p_tpu.native import NativeMLDSA

    return NativeMLDSA(suite["signature"]).keygen(seed)


def ref_public_key(suite: dict, seed: bytes) -> bytes:
    """The reference's public key of the identity made from ``seed``."""
    return reference.mldsa.keygen(reference.mldsa.PARAMS[suite["signature"]],
                                  seed)[0]


def ref_verify(suite: dict, pk: bytes, message: bytes, sig: bytes) -> bool:
    return reference.verify(suite["signature"], pk, message, sig)


def ref_decaps(suite: dict, dk: bytes, ct: bytes) -> bytes:
    return reference.decaps(suite["kem"], dk, ct)
