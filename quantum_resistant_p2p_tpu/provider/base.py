"""Abstract algorithm interfaces — the plugin boundary.

Same surface the reference exposes so application code (and users migrating
from it) find the familiar operations:
  * KEM:       generate_keypair / encapsulate / decapsulate
               (reference: crypto/key_exchange.py:19-54)
  * Signature: sign / verify            (reference: crypto/signatures.py:18-55)
  * AEAD:      encrypt / decrypt        (reference: crypto/symmetric.py:19-63)

Additions over the reference: every algorithm reports its ``backend`` ("cpu"
or "tpu") and offers ``*_batch`` operations with ``(batch, ...)`` numpy arrays
— the TPU backends implement these natively and the scalar ops are the
batch-of-1 special case, which is the inversion that makes 50k ops/s possible.
"""

from __future__ import annotations

import abc
import functools
from typing import Any

import numpy as np

from ..obs import trace as obs_trace


def try_native(class_name: str, algo_name: str):
    """Instantiate a native-core wrapper (NativeMLKEM/NativeMLDSA/...).  A
    failed native build raises (native/__init__.py): the cpu backend is the
    batch queues' fallback, and pure Python there would be far too slow."""
    from .. import native as _native

    return getattr(_native, class_name)(algo_name)


from ..utils import next_pow2  # noqa: E402  (canonical shared helper)


def pad_rows(rows: np.ndarray, target: int) -> np.ndarray:
    """Pad the batch dim to ``target`` by repeating the last row.

    Device batches are padded to power-of-two buckets so XLA compiles at most
    log2(max_batch) program variants per op instead of one per batch size —
    without this, a cold queue spends tens of seconds per novel size.
    """
    n = rows.shape[0]
    if n == target:
        return rows
    pad = np.broadcast_to(rows[-1:], (target - n,) + rows.shape[1:])
    return np.concatenate([np.asarray(rows), pad], axis=0)


def ready(out) -> None:
    """Wait until ``out``, what a device program returned, is ready on the
    device, then begin the ambient dispatch span's ``unpack`` stage.

    Every call of a device program from a batch fn reads::

        obs_trace.stage("run")
        out = program(*arrays)
        ready(out)

    so the span's ``run`` stage is the program's time on the device
    (operands' upload included) and what follows is the host's
    unpacking (obs/trace.py; both marks do nothing outside a dispatch
    span).  The program is called in the batch fn's own frame, not
    through a wrapper: on one v5e, two more Python frames at the call
    made the served programs' lowering 2.5x slower."""
    import jax

    jax.block_until_ready(out)
    obs_trace.stage("unpack")


@functools.lru_cache(maxsize=None)
def sharded_program(fn, mesh):
    """``fn`` run by every device of ``mesh`` on its own shard of the batch
    axis (``shard_map``), jitted once per (fn, mesh).

    Not GSPMD: the compiler cannot partition a Mosaic (Pallas) kernel, and
    every device program of the served path holds some.  Crypto batches are
    embarrassingly parallel, so each device runs the unchanged per-row
    program on its rows with no collective."""
    import jax
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(mesh.axis_names[0])
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def mesh_dispatch(fn, mesh, *arrays):
    """Run a jitted batch fn with the batch axis sharded across ``mesh``.

    TPU-native scale-out for embarrassingly parallel crypto batches
    (SURVEY.md §2.3): operands are placed with a batch-axis NamedSharding and
    each chip runs its shard of keygen/encaps/decaps/sign/verify locally
    (:func:`sharded_program`), with zero cross-chip collectives.

    The batch is padded (last row repeated) to ``n_devices * pow2`` so every
    device receives an equal, compile-cached shard; results gather on the
    host and are trimmed.  Non-divisible batches therefore cost at most the
    pad rows, never a recompile.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    n = arrays[0].shape[0]
    if n == 0:  # pad_rows cannot repeat a row of an empty batch
        obs_trace.stage("run")
        out = fn(*arrays)
        ready(out)
        if isinstance(out, tuple):
            return tuple(np.asarray(o) for o in out)
        return np.asarray(out)
    ndev = mesh.size
    tgt = ndev * next_pow2(-(-n // ndev))
    sh = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    parts = [jax.device_put(pad_rows(np.asarray(a), tgt), sh) for a in arrays]
    program = sharded_program(fn, mesh)
    obs_trace.stage("run")
    out = program(*parts)
    ready(out)
    if isinstance(out, tuple):
        return tuple(np.asarray(o)[:n] for o in out)
    return np.asarray(out)[:n]


def sliced_dispatch(fn, step: int, *arrays, mesh=None):
    """Run a jitted batch fn in ``step``-row slices and concatenate.

    Each family caps its dispatch at ``MAX_DEVICE_BATCH`` rows (kem/mlkem.py,
    kem/frodo.py, kem/hqc.py): the caps keep a flush's working set bounded
    and await a sweep on the chip.  A non-divisible tail is padded to a
    full slice (last row repeated) so every dispatch hits an already-compiled
    shape, then trimmed.

    Slices are DOUBLE-BUFFERED: slice N+1 is dispatched before slice N's
    host readback, so the next slice's upload + compute overlaps the
    previous readback instead of serialising behind it (jax dispatch is
    async; ``np.asarray`` is the sync point).  Holding exactly one
    in-flight slice bounds device memory to two slices' outputs, where an
    eager dispatch-all would pin every slice of an arbitrarily large queue
    flush.

    With a ``mesh``, each slice is sharded across the mesh's devices via
    ``mesh_dispatch`` and ``step`` is the PER-DEVICE cap, so one dispatch
    covers ``step * mesh.size`` rows.  (mesh_dispatch gathers to numpy
    internally, so mesh slices do not pipeline.)

    Each slice's launch begins the ambient dispatch span's ``run`` stage
    and :func:`ready` on its outputs its ``unpack`` stage; with several
    slices, ``run`` and ``unpack`` alternate and add up.
    """
    n = arrays[0].shape[0]
    if mesh is not None:
        cap = step * mesh.size
        if n <= cap:
            return mesh_dispatch(fn, mesh, *arrays)
        one = lambda *xs: mesh_dispatch(fn, mesh, *xs)  # noqa: E731
    else:
        cap = step
        if n <= cap:
            obs_trace.stage("run")
            out = fn(*arrays)
            ready(out)
            return (
                tuple(np.asarray(o) for o in out)
                if isinstance(out, tuple)
                else np.asarray(out)
            )
        one = fn

    def slice_of(a, i):
        return pad_rows(a[i : i + cap], cap)

    def read_back(p):
        ready(p)
        return (
            tuple(np.asarray(o) for o in p) if isinstance(p, tuple) else np.asarray(p)
        )

    parts = []
    in_flight = None
    for i in range(0, n, cap):
        obs_trace.stage("run")
        nxt = one(*(slice_of(a, i) for a in arrays))  # dispatch slice i ...
        if in_flight is not None:
            parts.append(read_back(in_flight))  # ... before reading slice i-1
        in_flight = nxt
    parts.append(read_back(in_flight))
    if isinstance(parts[0], tuple):
        return tuple(
            np.concatenate([p[j] for p in parts])[:n] for j in range(len(parts[0]))
        )
    return np.concatenate(parts)[:n]


def make_provider_mesh(devices: int, backend: str):
    """Build the provider-internal device mesh, or None when disabled.

    ``devices`` comes from Config.mesh_devices / the registry ``devices=``
    knob: 0 = single-device (default), N = 1-D mesh over the first N visible
    devices (make_mesh raises when fewer exist), -1 = all visible devices.
    Only the tpu backend shards; the cpu path never imports jax.
    """
    if not devices or backend != "tpu":
        return None
    from ..parallel.mesh import make_mesh

    return make_mesh(None if devices < 0 else devices)


class CryptoAlgorithm(abc.ABC):
    """Common metadata for all algorithms (reference: crypto/algorithm_base.py).

    Every concrete subclass's scalar ops (generate_keypair / encapsulate /
    decapsulate / sign / verify / encrypt / decrypt) are instrumented with
    the deterministic fault-injection hook (faults/) at class-creation time
    — one module-global ``None`` check per call when no plan is installed,
    so chaos tests never monkeypatch a provider.
    """

    def __init_subclass__(cls, **kwargs: Any) -> None:
        super().__init_subclass__(**kwargs)
        from ..faults import instrument_scalar_ops

        instrument_scalar_ops(cls)

    #: canonical registry name, e.g. "ML-KEM-768"
    name: str = ""
    #: human-readable name for UIs / settings gossip
    display_name: str = ""
    description: str = ""
    #: NIST security level (1/3/5)
    security_level: int = 0
    #: "cpu" (pure-Python reference) or "tpu" (batched JAX)
    backend: str = "cpu"

    @property
    def is_using_mock(self) -> bool:
        # Parity with crypto/algorithm_base.py:30-33 — mock crypto is never used.
        return False

    @property
    def actual_variant(self) -> str:
        return self.name

    def get_security_info(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "display_name": self.display_name,
            "description": self.description,
            "security_level": self.security_level,
            "backend": self.backend,
            "mock": self.is_using_mock,
        }


class KeyExchangeAlgorithm(CryptoAlgorithm):
    """KEM interface; byte-level scalar API + array-level batch API."""

    public_key_len: int = 0
    secret_key_len: int = 0
    ciphertext_len: int = 0
    shared_secret_len: int = 32

    @abc.abstractmethod
    def generate_keypair(self) -> tuple[bytes, bytes]:
        """-> (public_key, secret_key)"""

    @abc.abstractmethod
    def encapsulate(self, public_key: bytes) -> tuple[bytes, bytes]:
        """-> (ciphertext, shared_secret)"""

    @abc.abstractmethod
    def decapsulate(self, secret_key: bytes, ciphertext: bytes) -> bytes:
        """-> shared_secret"""

    # -- batch API (TPU-native path; default = loop over the scalar API) ----

    def generate_keypair_batch(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        pks, sks = zip(*(self.generate_keypair() for _ in range(n)))
        return _stack_bytes(pks), _stack_bytes(sks)

    def encapsulate_batch(self, public_keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        cts, sss = zip(*(self.encapsulate(bytes(pk)) for pk in public_keys))
        return _stack_bytes(cts), _stack_bytes(sss)

    def decapsulate_batch(self, secret_keys: np.ndarray, ciphertexts: np.ndarray) -> np.ndarray:
        return _stack_bytes(
            [self.decapsulate(bytes(sk), bytes(ct)) for sk, ct in zip(secret_keys, ciphertexts)]
        )


class SignatureAlgorithm(CryptoAlgorithm):
    """Signature interface; verify returns False on any failure, never raises."""

    public_key_len: int = 0
    secret_key_len: int = 0
    signature_len: int = 0  # maximum length where variable

    @abc.abstractmethod
    def generate_keypair(self) -> tuple[bytes, bytes]:
        """-> (public_key, secret_key)"""

    def generate_keypair_batch(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """-> (public_keys (n, pk_len), secret_keys (n, sk_len)) uint8.

        Default loops the scalar path; batched backends override (the KEM
        interface's counterpart is abstract, but signature keypairs are
        long-lived so most callers never need the batch form)."""
        pairs = [self.generate_keypair() for _ in range(n)]
        return (
            np.stack([np.frombuffer(pk, np.uint8) for pk, _ in pairs]),
            np.stack([np.frombuffer(sk, np.uint8) for _, sk in pairs]),
        )

    @abc.abstractmethod
    def sign(self, secret_key: bytes, message: bytes) -> bytes:
        """-> signature"""

    @abc.abstractmethod
    def verify(self, public_key: bytes, message: bytes, signature: bytes) -> bool:
        """-> True iff the signature is valid (exceptions map to False)"""

    def sign_batch(self, secret_keys: np.ndarray, messages: list[bytes]) -> list[bytes]:
        return [self.sign(bytes(sk), m) for sk, m in zip(secret_keys, messages)]

    def verify_batch(
        self, public_keys: np.ndarray, messages: list[bytes], signatures: list[bytes]
    ) -> np.ndarray:
        return np.array(
            [self.verify(bytes(pk), m, s) for pk, m, s in zip(public_keys, messages, signatures)]
        )


class FusedHandshakeOps(abc.ABC):
    """Optional capability: composite device programs for a (KEM, signature)
    provider pair, fusing what one handshake step executes back-to-back
    (kem op + transcript hash + signature op) into a single dispatch.

    Discovered through ``provider.registry.get_fused(kem, sig)`` — ``None``
    (capability absent: unregistered pair, or either provider not on the
    tpu backend) means callers stay on the per-op path; the wire protocol
    is identical either way.  ``templates`` are canonical transcript bytes
    with a zeroed gap at the given static offset where the device
    hex-encodes its own output (fresh public key / ciphertext) before
    hashing; ``msgs_in``/``msgs_out`` are fully host-known transcripts.

    Signature ops follow the provider conventions: sign raises when a lane
    exhausts its rejection budget, verify maps any failure to False.
    """

    kem: KeyExchangeAlgorithm
    sig: SignatureAlgorithm
    name: str = ""
    backend: str = "tpu"
    #: per-kind template capacity (static compiled buffer widths); callers
    #: fall back to the per-op path for transcripts that exceed them
    init_template_len: int = 0
    resp_template_len: int = 0

    @abc.abstractmethod
    def keygen_sign_batch(self, sig_sks: np.ndarray, templates: list[bytes],
                          pk_off: int, rnd=None):
        """-> (public_keys (n, pk_len), secret_keys (n, sk_len),
        sigs list[bytes]) — KEM keygen + sign(template with hex(pk) at
        ``pk_off``)."""

    @abc.abstractmethod
    def encaps_verify_sign_batch(self, public_keys: np.ndarray,
                                 peer_sig_pks: np.ndarray,
                                 msgs_in: list[bytes], sigs_in: list[bytes],
                                 sig_sks: np.ndarray, templates: list[bytes],
                                 ct_off: int, m=None, rnd=None):
        """-> (oks (n,) bool, cts, shared_secrets, sigs list[bytes]) —
        verify(msgs_in) + KEM encaps + sign(template with hex(ct) at
        ``ct_off``)."""

    @abc.abstractmethod
    def decaps_verify_sign_batch(self, secret_keys: np.ndarray,
                                 ciphertexts: np.ndarray,
                                 peer_sig_pks: np.ndarray,
                                 msgs_in: list[bytes], sigs_in: list[bytes],
                                 sig_sks: np.ndarray, msgs_out: list[bytes],
                                 rnd=None):
        """-> (oks (n,) bool, shared_secrets, sigs list[bytes]) —
        verify(msgs_in) + KEM decaps + sign(msgs_out)."""

    def warmup(self, sizes: tuple[int, ...] = (1,), pk_off: int | None = None,
               ct_off: int | None = None) -> None:
        """Pre-compile the composite programs (blocking; run off-loop).
        Offsets must match the live transcripts' — jit keys on them."""


class SymmetricAlgorithm(CryptoAlgorithm):
    """AEAD interface (scalar; the per-message CPU path).

    The batched device path is a SEPARATE optional capability
    (:class:`BatchedAEADOps`, discovered via
    ``provider.registry.get_batched_aead``) — the scalar ops here stay the
    universal fallback and the wire-format authority: 12-byte nonce
    prepended to ``ciphertext || tag``.
    """

    key_size: int = 32
    nonce_size: int = 12

    @abc.abstractmethod
    def encrypt(self, key: bytes, plaintext: bytes, associated_data: bytes | None = None) -> bytes:
        """-> nonce || ciphertext || tag"""

    @abc.abstractmethod
    def decrypt(self, key: bytes, data: bytes, associated_data: bytes | None = None) -> bytes:
        """-> plaintext; raises ValueError on authentication failure"""

    def seal(self, key: bytes, nonce: bytes, plaintext: bytes,
             associated_data: bytes | None = None) -> bytes:
        """Deterministic-nonce seal: -> ``ciphertext || tag`` (no nonce
        prefix).  The primitive both the batched facade's cpu fallback and
        the device cross-check tests need; ``encrypt`` is ``urandom nonce +
        seal``.  Default raises — concrete AEADs override."""
        raise NotImplementedError(f"{self.name} has no deterministic seal")

    def open_(self, key: bytes, nonce: bytes, data: bytes,
              associated_data: bytes | None = None) -> bytes:
        """Deterministic-nonce open of ``ciphertext || tag``; ValueError on
        authentication failure.  Default raises — concrete AEADs override."""
        raise NotImplementedError(f"{self.name} has no deterministic open")


class BatchedAEADOps(abc.ABC):
    """Optional capability: batched device seal/open for one AEAD.

    Discovered through ``provider.registry.get_batched_aead(symmetric)`` —
    ``None`` (capability absent: unregistered AEAD, jax unavailable, or
    ``QRP2P_BATCH_AEAD=0``) keeps every caller on the scalar
    :class:`SymmetricAlgorithm` path; the wire format is identical either
    way (the facade prepends the same random 12-byte nonce the scalar
    ``encrypt`` does).

    Array conventions: keys/nonces are ``(n, key_size)`` / ``(n,
    nonce_size)`` uint8 rows; messages and AADs are ragged lists of
    bytes-like objects (``memoryview`` welcome — the binary wire path hands
    socket-buffer views straight through).  Implementations pad to pow2
    length buckets with masked tails, so one flush costs one device
    program per (batch, length, aad) bucket triple.  Per-item
    authentication failures are reported as ``ValueError`` INSTANCES in
    the result list (the provider/batched.py per-item failure convention),
    never raised — one tampered ciphertext must not poison its batch
    mates.
    """

    name: str = ""
    backend: str = "tpu"
    key_size: int = 32
    nonce_size: int = 12
    tag_size: int = 16
    #: longest message / AAD the device bucket space serves; callers route
    #: longer items to the scalar path (bounded compile count + memory)
    max_len: int = 1 << 20
    max_aad_len: int = 1 << 16

    @abc.abstractmethod
    def seal_batch(self, keys: np.ndarray, nonces: np.ndarray,
                   plaintexts: list, aads: list) -> list[bytes]:
        """-> per-item ``ciphertext || tag``."""

    @abc.abstractmethod
    def open_batch(self, keys: np.ndarray, nonces: np.ndarray,
                   data: list, aads: list) -> list:
        """``data`` items are ``ciphertext || tag``; -> per-item plaintext
        bytes, or a ``ValueError`` instance where authentication failed."""


def _stack_bytes(items) -> np.ndarray:
    return np.stack([np.frombuffer(b, dtype=np.uint8) for b in items])


def expect_len(buf: bytes, expected: int, what: str, algo: str) -> None:
    """Reject wrong-length attacker-controlled material BEFORE it reaches a
    backend.  The native C++ core reads exactly ``expected`` bytes from the
    buffer it is handed, so an unchecked short input is a heap out-of-bounds
    read; the JAX backends would raise an opaque reshape error instead of a
    protocol-level one.  Raises ValueError (which the messaging layer maps to
    a typed rejection)."""
    if len(buf) != expected:
        raise ValueError(f"{algo}: {what} must be {expected} bytes, got {len(buf)}")


def expect_cols(arr: np.ndarray, expected: int, what: str, algo: str) -> None:
    """Batch-array analog of expect_len: trailing dim must match exactly."""
    if arr.ndim != 2 or arr.shape[1] != expected:
        raise ValueError(
            f"{algo}: batched {what} must have shape (n, {expected}), got {arr.shape}"
        )
