"""ctypes loader for the C++ host crypto core (qrp_native.cpp, this package).

Fills the role liboqs plays for the reference app (vendored .so loaded via
ctypes, reference vendor/__init__.py:12-57 + vendor/oqs.py:122-183): a native
CPU fast path for every family, compiled on demand with g++ (pybind11 is
not available in this environment; plain extern "C" + ctypes is the binding).

The library is keyed by a hash of ``qrp_native.cpp``'s content, so a cached
build of other source can never be loaded.  A failed build raises: the
native core serves the cpu backend and the batch queues' fallback, and a
silent switch to pure Python there would be orders of magnitude slower.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

logger = logging.getLogger(__name__)

# Ships inside the package so non-editable installs carry the source
# (pyproject.toml package-data) and build-on-demand works from site-packages.
_SRC = Path(__file__).resolve().parent / "qrp_native.cpp"
_CACHE_DIR = Path(
    os.environ.get("QRP_NATIVE_CACHE", Path.home() / ".cache" / "qrp2p_tpu")
)

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _build() -> Path:
    """Path of the library built from the current source (built if absent)."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    so = _CACHE_DIR / f"libqrp_native-{digest}.so"
    if so.exists():
        return so
    _CACHE_DIR.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(f".{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed: {e.stderr.decode(errors='replace')[-2000:]}"
        ) from e
    except (OSError, subprocess.SubprocessError) as e:
        raise RuntimeError(f"native build failed: {e}") from e
    tmp.replace(so)  # atomic: a concurrent loader never sees half a file
    return so


def load() -> ctypes.CDLL:
    """Build-if-needed and load the native library; raises on failure."""
    global _lib
    with _lock:
        if _lib is None:
            so = _build()
            _lib = _bind(ctypes.CDLL(str(so)))
            logger.info(
                "loaded native crypto core v%d from %s", _lib.qrp_version(), so
            )
        return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Set argtypes/restypes; raises AttributeError if a symbol is missing."""
    u8p = ctypes.POINTER(ctypes.c_uint8)
    for name, argtypes in (
        ("qrp_shake128", [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]),
        ("qrp_shake256", [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]),
        ("qrp_sha3_256", [u8p, ctypes.c_size_t, u8p]),
        ("qrp_sha3_512", [u8p, ctypes.c_size_t, u8p]),
        ("qrp_zeroize", [u8p, ctypes.c_size_t]),
        ("qrp_mlkem_keygen", [ctypes.c_int, u8p, u8p, u8p, u8p]),
        ("qrp_mlkem_encaps", [ctypes.c_int, u8p, u8p, u8p, u8p]),
        ("qrp_mlkem_decaps", [ctypes.c_int, u8p, u8p, u8p]),
        ("qrp_mldsa_keygen", [ctypes.c_int, u8p, u8p, u8p]),
        ("qrp_sha256", [u8p, ctypes.c_size_t, u8p]),
        ("qrp_sha512", [u8p, ctypes.c_size_t, u8p]),
        ("qrp_hmac_sha256", [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t, u8p]),
        ("qrp_slhdsa_keygen", [ctypes.c_int, u8p, u8p, u8p, u8p, u8p]),
        ("qrp_slhdsa_sign", [ctypes.c_int, u8p, u8p, ctypes.c_size_t, u8p, u8p]),
        ("qrp_aes128_ecb", [u8p, u8p, ctypes.c_size_t, u8p]),
        ("qrp_frodo_keygen", [ctypes.c_int, u8p, u8p, u8p, u8p, u8p]),
        ("qrp_frodo_encaps", [ctypes.c_int, u8p, u8p, u8p, u8p]),
        ("qrp_frodo_decaps", [ctypes.c_int, u8p, u8p, u8p]),
        ("qrp_hqc_keygen", [ctypes.c_int, u8p, u8p, u8p, u8p, u8p]),
        ("qrp_hqc_encaps", [ctypes.c_int, u8p, u8p, u8p, u8p, u8p]),
        ("qrp_hqc_decaps", [ctypes.c_int, u8p, u8p, u8p]),
    ):
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = None
    lib.qrp_mldsa_sign.argtypes = [ctypes.c_int, u8p, u8p, ctypes.c_size_t, u8p, u8p]
    lib.qrp_mldsa_sign.restype = ctypes.c_int
    lib.qrp_mldsa_verify.argtypes = [ctypes.c_int, u8p, u8p, ctypes.c_size_t, u8p]
    lib.qrp_mldsa_verify.restype = ctypes.c_int
    lib.qrp_slhdsa_verify.argtypes = [ctypes.c_int, u8p, u8p, ctypes.c_size_t, u8p]
    lib.qrp_slhdsa_verify.restype = ctypes.c_int
    lib.qrp_version.restype = ctypes.c_int
    return lib


def _expect(data: bytes, n: int, what: str) -> None:
    # Wrong lengths never reach the native core (it reads fixed param-set
    # sizes unconditionally) — same seam contract as the pyref oracles.
    if len(data) != n:
        raise ValueError(f"{what} must be {n} bytes, got {len(data)}")


def _buf(data: bytes):
    return (ctypes.c_uint8 * len(data)).from_buffer_copy(data)


def _out(n: int):
    return (ctypes.c_uint8 * n)()


class NativeMLKEM:
    """Scalar ML-KEM over the native core (same seams as pyref.mlkem_ref)."""

    _K = {"ML-KEM-512": 2, "ML-KEM-768": 3, "ML-KEM-1024": 4}

    def __init__(self, name: str):
        self.lib = load()
        self.k = self._K[name]
        self.ek_len = 384 * self.k + 32
        self.dk_len = 768 * self.k + 96
        du, dv = (10, 4) if self.k < 4 else (11, 5)
        self.ct_len = 32 * (du * self.k + dv)

    def keygen(self, d: bytes, z: bytes) -> tuple[bytes, bytes]:
        ek, dk = _out(self.ek_len), _out(self.dk_len)
        self.lib.qrp_mlkem_keygen(self.k, _buf(d), _buf(z), ek, dk)
        return bytes(ek), bytes(dk)

    def encaps(self, ek: bytes, m: bytes) -> tuple[bytes, bytes]:
        key, ct = _out(32), _out(self.ct_len)
        self.lib.qrp_mlkem_encaps(self.k, _buf(ek), _buf(m), key, ct)
        return bytes(key), bytes(ct)

    def decaps(self, dk: bytes, ct: bytes) -> bytes:
        key = _out(32)
        self.lib.qrp_mlkem_decaps(self.k, _buf(dk), _buf(ct), key)
        return bytes(key)


class NativeMLDSA:
    """Scalar ML-DSA over the native core (same seams as pyref.mldsa_ref:
    keygen(xi), sign_internal(sk, m_prime, rnd), verify_internal)."""

    _LEVEL = {"ML-DSA-44": 2, "ML-DSA-65": 3, "ML-DSA-87": 5}

    def __init__(self, name: str):
        from ..pyref import mldsa_ref  # single authority for sizes

        self.lib = load()
        self.level = self._LEVEL[name]
        p = mldsa_ref.PARAMS[name]
        self.pk_len, self.sk_len, self.sig_len = p.pk_len, p.sk_len, p.sig_len

    def keygen(self, xi: bytes) -> tuple[bytes, bytes]:
        _expect(xi, 32, "xi")
        pk, sk = _out(self.pk_len), _out(self.sk_len)
        self.lib.qrp_mldsa_keygen(self.level, _buf(xi), pk, sk)
        return bytes(pk), bytes(sk)

    def sign_internal(self, sk: bytes, m_prime: bytes, rnd: bytes) -> bytes:
        _expect(sk, self.sk_len, "secret key")
        _expect(rnd, 32, "rnd")
        sig = _out(self.sig_len)
        ok = self.lib.qrp_mldsa_sign(
            self.level, _buf(sk), _buf(m_prime), len(m_prime), _buf(rnd), sig
        )
        if not ok:
            # Only reachable with a pathological/adversarial sk: the 16-bit
            # ExpandMask counter space was exhausted without an accept.
            raise RuntimeError("ML-DSA sign: rejection-sampling budget exhausted")
        return bytes(sig)

    def verify_internal(self, pk: bytes, m_prime: bytes, sig: bytes) -> bool:
        if len(pk) != self.pk_len or len(sig) != self.sig_len:
            return False
        return bool(
            self.lib.qrp_mldsa_verify(
                self.level, _buf(pk), _buf(m_prime), len(m_prime), _buf(sig)
            )
        )


class NativeSLHDSA:
    """Scalar SLH-DSA / SPHINCS+-SHA2 over the native core (same seams as
    pyref.slhdsa_ref: keygen(sk_seed, sk_prf, pk_seed),
    sign_internal(msg, sk, addrnd), verify_internal)."""

    _ID = {
        "SPHINCS+-SHA2-128s-simple": 0,
        "SPHINCS+-SHA2-128f-simple": 1,
        "SPHINCS+-SHA2-192s-simple": 2,
        "SPHINCS+-SHA2-192f-simple": 3,
        "SPHINCS+-SHA2-256s-simple": 4,
        "SPHINCS+-SHA2-256f-simple": 5,
    }

    def __init__(self, name: str):
        from ..pyref import slhdsa_ref  # single authority for sizes

        self.lib = load()
        self.param_id = self._ID[name]
        p = slhdsa_ref.PARAMS[name]
        self.n, self.sig_len = p.n, p.sig_len
        self.pk_len, self.sk_len = p.pk_len, p.sk_len

    def keygen(self, sk_seed: bytes, sk_prf: bytes, pk_seed: bytes) -> tuple[bytes, bytes]:
        for nm, s in (("sk_seed", sk_seed), ("sk_prf", sk_prf), ("pk_seed", pk_seed)):
            _expect(s, self.n, nm)
        pk, sk = _out(self.pk_len), _out(self.sk_len)
        self.lib.qrp_slhdsa_keygen(
            self.param_id, _buf(sk_seed), _buf(sk_prf), _buf(pk_seed), pk, sk
        )
        return bytes(pk), bytes(sk)

    def sign_internal(self, msg: bytes, sk: bytes, addrnd: bytes | None = None) -> bytes:
        _expect(sk, self.sk_len, "secret key")
        if addrnd is not None:
            _expect(addrnd, self.n, "addrnd")
        sig = _out(self.sig_len)
        self.lib.qrp_slhdsa_sign(
            self.param_id, _buf(sk), _buf(msg), len(msg),
            _buf(addrnd) if addrnd is not None else None, sig,
        )
        return bytes(sig)

    def verify_internal(self, msg: bytes, sig: bytes, pk: bytes) -> bool:
        if len(pk) != self.pk_len or len(sig) != self.sig_len:
            return False
        return bool(
            self.lib.qrp_slhdsa_verify(self.param_id, _buf(pk), _buf(msg), len(msg), _buf(sig))
        )


class NativeFrodoKEM:
    """Scalar FrodoKEM over the native core (same seams as pyref.frodo_ref:
    keygen(s, seedSE, z), encaps(pk, mu), decaps(sk, ct))."""

    _ID = {
        "FrodoKEM-640-AES": 0, "FrodoKEM-640-SHAKE": 1,
        "FrodoKEM-976-AES": 2, "FrodoKEM-976-SHAKE": 3,
        "FrodoKEM-1344-AES": 4, "FrodoKEM-1344-SHAKE": 5,
    }

    def __init__(self, name: str):
        from ..pyref import frodo_ref  # single authority for sizes

        self.lib = load()
        self.param_id = self._ID[name]
        p = frodo_ref.PARAMS[name]
        self.len_sec = p.len_sec
        self.pk_len, self.sk_len, self.ct_len = p.pk_len, p.sk_len, p.ct_len

    def keygen(self, s: bytes, seed_se: bytes, z: bytes) -> tuple[bytes, bytes]:
        for nm, v in (("s", s), ("seedSE", seed_se), ("z", z)):
            _expect(v, self.len_sec, nm)
        pk, sk = _out(self.pk_len), _out(self.sk_len)
        self.lib.qrp_frodo_keygen(self.param_id, _buf(s), _buf(seed_se), _buf(z), pk, sk)
        return bytes(pk), bytes(sk)

    def encaps(self, pk: bytes, mu: bytes) -> tuple[bytes, bytes]:
        _expect(pk, self.pk_len, "public key")
        _expect(mu, self.len_sec, "mu")
        ct, ss = _out(self.ct_len), _out(self.len_sec)
        self.lib.qrp_frodo_encaps(self.param_id, _buf(pk), _buf(mu), ct, ss)
        return bytes(ct), bytes(ss)

    def decaps(self, sk: bytes, ct: bytes) -> bytes:
        _expect(sk, self.sk_len, "secret key")
        _expect(ct, self.ct_len, "ciphertext")
        ss = _out(self.len_sec)
        self.lib.qrp_frodo_decaps(self.param_id, _buf(sk), _buf(ct), ss)
        return bytes(ss)


class NativeHQC:
    """Scalar HQC over the native core (same seams as pyref.hqc_ref:
    keygen(sk_seed, sigma, pk_seed), encaps(pk, m, salt), decaps(sk, ct))."""

    _ID = {"HQC-128": 0, "HQC-192": 1, "HQC-256": 2}

    def __init__(self, name: str):
        from ..pyref import hqc_ref  # single authority for sizes

        self.lib = load()
        self.param_id = self._ID[name]
        p = hqc_ref.PARAMS[name]
        self.k = p.k
        self.pk_len, self.sk_len = p.pk_len, p.sk_len
        self.ct_len, self.ss_len = p.ct_len, p.ss_len

    def keygen(self, sk_seed: bytes, sigma: bytes, pk_seed: bytes) -> tuple[bytes, bytes]:
        _expect(sk_seed, 40, "sk_seed")
        _expect(sigma, self.k, "sigma")
        _expect(pk_seed, 40, "pk_seed")
        pk, sk = _out(self.pk_len), _out(self.sk_len)
        self.lib.qrp_hqc_keygen(
            self.param_id, _buf(sk_seed), _buf(sigma), _buf(pk_seed), pk, sk
        )
        return bytes(pk), bytes(sk)

    def encaps(self, pk: bytes, m: bytes, salt: bytes) -> tuple[bytes, bytes]:
        _expect(pk, self.pk_len, "public key")
        _expect(m, self.k, "m")
        _expect(salt, 16, "salt")
        ct, ss = _out(self.ct_len), _out(self.ss_len)
        self.lib.qrp_hqc_encaps(self.param_id, _buf(pk), _buf(m), _buf(salt), ct, ss)
        return bytes(ct), bytes(ss)

    def decaps(self, sk: bytes, ct: bytes) -> bytes:
        _expect(sk, self.sk_len, "secret key")
        _expect(ct, self.ct_len, "ciphertext")
        ss = _out(self.ss_len)
        self.lib.qrp_hqc_decaps(self.param_id, _buf(sk), _buf(ct), ss)
        return bytes(ss)


def shake256(data: bytes, out_len: int) -> bytes:
    lib = load()
    out = _out(out_len)
    lib.qrp_shake256(_buf(data), len(data), out, out_len)
    return bytes(out)


def zeroize(buf: bytearray) -> None:
    """Best-effort secure wipe of a mutable buffer (reference analog:
    OQS_MEM_cleanse via vendor/oqs.py:383-390)."""
    lib = load()
    c = (ctypes.c_uint8 * len(buf)).from_buffer(buf)
    lib.qrp_zeroize(c, len(buf))


def wipe(*bufs) -> None:
    """End-of-life wipe for secret buffers of whatever type a provider
    handed back: ``bytearray`` through the native cleanse, writable
    array-likes (numpy) zero-filled in place, and immutable operands
    (``bytes``, jax device arrays) left to the GC — that last case is a
    documented CPython/XLA limitation, not a policy choice, and routing
    it through here still marks the lifetime boundary for qrlife's
    wipe-completeness check."""
    for buf in bufs:
        if isinstance(buf, bytearray):
            zeroize(buf)
        elif hasattr(buf, "dtype"):
            try:
                buf[...] = 0
            except (TypeError, ValueError):
                pass  # immutable device array: lifetime ends here, GC takes it
