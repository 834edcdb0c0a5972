"""Device ChaCha20-Poly1305 — the ``BatchedAEAD`` capability implementation.

Thin array-marshalling shim between the batched facade
(provider/batched.py ``BatchedAEAD``) and the jitted seal/open core
(core/chacha_pallas.py): ragged bytes in, padded pow2 buckets through one
device program, exact-length bytes out.  Bucket policy:

* message length -> ``64 * next_pow2(ceil(len / 64))`` (whole ChaCha
  blocks; 64, 128, 256, ... up to :attr:`max_len`);
* AAD length -> ``16 * next_pow2(ceil(len / 16))`` (whole Poly1305
  blocks);
* one flush dispatches ONE program at the flush's max buckets — mixed
  sizes ride together with masked tails, bit-exact per item (the KAT
  suite pins every bucket edge).

jit compiles one program per (batch, length, aad) bucket triple; the
coarse pow2 grid keeps that space small enough for the facade warmup to
cover (docs/dispatch_budget.md "aead" row has the trip ledger).
"""

from __future__ import annotations

import hmac
import threading

import numpy as np

from ..obs import trace as obs_trace
from ..utils import next_pow2
from .base import BatchedAEADOps, ready


class ChaChaPolyDevice(BatchedAEADOps):
    """RFC 8439 ChaCha20-Poly1305 over the batched device core."""

    name = "ChaCha20-Poly1305"
    backend = "tpu"
    key_size = 32
    nonce_size = 12
    tag_size = 16
    #: device bucket caps: a 64 KiB message compiles the largest program
    #: this capability owns; longer payloads (file sends) stay scalar
    max_len = 64 * 1024
    max_aad_len = 4 * 1024

    def __init__(self, use_pallas: bool | None = None,
                 interpret: bool = False):
        from ..core import chacha_pallas, keccak

        self._core = chacha_pallas
        #: Pallas kernel on a TPU, jnp twin elsewhere (bit-identical;
        #: core.keccak's platform gate)
        self.use_pallas = (keccak._use_pallas()
                           if use_pallas is None else use_pallas)
        self.interpret = interpret
        #: (seal, batch, msg_bucket, aad_bucket) program shapes this
        #: instance has dispatched at least once — the facade's OpQueue
        #: ``warm_check`` axis (a warm batch bucket with a novel LENGTH
        #: bucket would otherwise jit-compile inside a live dispatch).
        #: Lock-guarded: written from device/warmup worker threads, read
        #: from the event loop's warm check (qrflow cross-thread-state).
        self._shape_lock = threading.Lock()
        self.compiled_shapes: set[tuple[bool, int, int, int]] = set()

    # -- marshalling --------------------------------------------------------
    #
    # Bucket floors collapse the small end of the shape space: every
    # message <= 256 B and every AAD <= 256 B lands on ONE (msg, aad)
    # bucket pair, so the default facade warm shapes cover the whole
    # small-message regime instead of fragmenting across 64/128/16/32/...
    # variants (a novel shape costs a fallback window while it warms —
    # padding a few hundred bytes of ChaCha/Poly lanes costs ~nothing).

    MSG_BUCKET_FLOOR = 256
    AAD_BUCKET_FLOOR = 256

    @classmethod
    def _msg_bucket(cls, n: int) -> int:
        return max(cls.MSG_BUCKET_FLOOR, 64 * next_pow2(max(1, -(-n // 64))))

    @classmethod
    def _aad_bucket(cls, n: int) -> int:
        return max(cls.AAD_BUCKET_FLOOR, 16 * next_pow2(max(1, -(-n // 16))))

    def _pack(self, items: list, bucket: int) -> tuple[np.ndarray, np.ndarray]:
        out = np.zeros((len(items), bucket), np.uint8)
        lens = np.zeros(len(items), np.int32)
        for i, it in enumerate(items):
            row = np.frombuffer(it, np.uint8)
            out[i, : row.shape[0]] = row
            lens[i] = row.shape[0]
        return out, lens

    def _run(self, keys, nonces, data_items, aads, seal: bool):
        l_bucket = self._msg_bucket(max((len(d) for d in data_items),
                                        default=1))
        a_bucket = self._aad_bucket(max((len(a) for a in aads), default=1))
        data, lens = self._pack(data_items, l_bucket)
        aad_arr, aad_lens = self._pack(aads, a_bucket)
        obs_trace.stage("run")
        out, tags = self._core.aead_core(
            np.ascontiguousarray(keys, dtype=np.uint8),
            np.ascontiguousarray(nonces, dtype=np.uint8),
            data, lens, aad_arr, aad_lens, seal=seal,
            use_pallas=self.use_pallas, interpret=self.interpret,
        )
        ready((out, tags))
        with self._shape_lock:
            self.compiled_shapes.add((seal, len(data_items), l_bucket,
                                      a_bucket))
        return np.asarray(out), np.asarray(tags), lens

    def covers(self, seal: bool, batch: int, msg_len: int,
               aad_len: int) -> bool:
        """True when the program for these buckets is already compiled —
        the facade's warm_check predicate (provider/batched.py)."""
        with self._shape_lock:
            return (seal, batch, self._msg_bucket(msg_len),
                    self._aad_bucket(aad_len)) in self.compiled_shapes

    # -- capability surface -------------------------------------------------

    def seal_batch(self, keys: np.ndarray, nonces: np.ndarray,
                   plaintexts: list, aads: list) -> list[bytes]:
        out, tags, lens = self._run(keys, nonces, plaintexts, aads, seal=True)
        return [bytes(out[i, : lens[i]]) + bytes(tags[i])
                for i in range(len(plaintexts))]

    def open_batch(self, keys: np.ndarray, nonces: np.ndarray,
                   data: list, aads: list) -> list:
        views = [memoryview(d) for d in data]
        cts = [v[: -self.tag_size] for v in views]
        out, tags, lens = self._run(keys, nonces, cts, aads, seal=False)
        results: list = []
        for i, v in enumerate(views):
            # constant-time per-item compare; a mismatch is a per-item
            # ValueError result, matching the scalar decrypt contract
            if hmac.compare_digest(bytes(tags[i]),
                                   bytes(v[-self.tag_size:])):
                results.append(bytes(out[i, : lens[i]]))
            else:
                results.append(ValueError("authentication failed"))
        return results
