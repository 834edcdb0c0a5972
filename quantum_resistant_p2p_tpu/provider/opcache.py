"""Device-resident operand cache — stop re-uploading hot keys every dispatch.

Without it every dispatch re-uploads the key, and the per-key
preprocessing (ExpandA matrix expansion, the key-dependent NTTs) is
recomputed by every dispatch that carries the same key.  Both costs are per-KEY, not per-op:
a node signs every transcript with one long-lived key, verifies a given
peer with one public key, and a swarm encapsulates repeatedly against hot
peers.  The cache pins the precomputed per-key device state (pytrees of
jax arrays produced by ``kem.mlkem.precompute_ek`` /
``sig.mldsa.precompute_sk`` / ``sig.mldsa.precompute_pk``) keyed by a
content hash of the raw key bytes, with LRU eviction so unbounded peer
churn cannot pin unbounded device memory.

Security note: cached entries derived from SECRET keys (the sign-path
precompute) hold key-equivalent material on device for the cache's
lifetime — the same trust boundary as the provider object itself, which
already holds the raw secret key in host memory.  Keys are identified by
SHA-256 of their bytes; raw key material never appears in stats or logs.

Thread-safety: lookups/inserts take a lock (queues dispatch from executor
threads); the miss-path compute runs OUTSIDE the lock because it may jit,
so two threads racing the same cold key may both compute — the second
insert wins, which is harmless (identical value) and cheaper than holding
a lock across a compile.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import threading
from collections import OrderedDict
from typing import Any

#: the placement-axis coordinate of the CURRENT dispatch (set by
#: Shard.placement on the dispatching thread).  Cached pytrees live on one
#: chip; feeding shard i's device arrays to a program placed on shard j
#: would force a cross-chip transfer (or fail on committed operands), so
#: cache keys are namespaced by this scope — the opcache state partitions
#: across the device mesh.  Default 0 = the single-device world.
_SHARD: contextvars.ContextVar[int] = contextvars.ContextVar(
    "qrp2p_opcache_shard", default=0
)


@contextlib.contextmanager
def shard_scope(index: int):
    """Namespace opcache lookups/inserts to placement shard ``index`` for
    the duration of the block (entered on the dispatch worker thread by
    ``provider.scheduler.Shard.placement``)."""
    token = _SHARD.set(index)
    try:
        yield
    finally:
        _SHARD.reset(token)


def current_shard() -> int:
    """The active placement scope (tests; diagnostics)."""
    return _SHARD.get()


class DeviceOperandCache:
    """Content-hash-keyed LRU of per-key device operand pytrees,
    partitioned by placement shard (see :func:`shard_scope`)."""

    def __init__(self, capacity: int = 8):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: OrderedDict[tuple[str, int, bytes], Any] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        #: cost-ledger feed (obs/cost.py): sliding-window hit rates next
        #: to the cumulative counters above — attached by the engine,
        #: None (the default) records nothing extra
        self._cost = None
        self._cost_kind = ""

    def attach_cost(self, ledger, kind: str) -> None:
        """Feed hit/miss events into a :class:`obs.cost.CostLedger` under
        cache label ``kind`` ("kem" / "sig")."""
        self._cost = ledger
        self._cost_kind = kind

    @staticmethod
    def _key(kind: str, key_bytes: bytes) -> tuple[str, int, bytes]:
        # the shard coordinate keeps per-chip device state per chip; LRU
        # pressure is shared (one capacity across shards, matching the
        # single HBM budget the cache models per process)
        return (kind, _SHARD.get(), hashlib.sha256(key_bytes).digest())

    def lookup(self, kind: str, key_bytes: bytes) -> Any | None:
        """Cached state or None.  Deliberately a lookup/put split, not a
        compute-on-miss callback: the providers' miss path is a COMBINED
        program (op + precompute in one dispatch, e.g. kem.mlkem.
        encaps_cold) whose other outputs the caller needs — a callback
        could not return those."""
        k = self._key(kind, bytes(key_bytes))
        with self._lock:
            if k in self._entries:
                self._entries.move_to_end(k)
                self.hits += 1
                hit, out = True, self._entries[k]
            else:
                self.misses += 1
                hit, out = False, None
        if self._cost is not None:
            # outside the lock: the ledger takes its own (obs/cost.py)
            self._cost.opcache_event(self._cost_kind, hit)
        return out

    def put(self, kind: str, key_bytes: bytes, val: Any) -> None:
        k = self._key(kind, bytes(key_bytes))
        with self._lock:
            self._entries[k] = val
            self._entries.move_to_end(k)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1

    def clear(self) -> int:
        """Drop every entry; returns how many were released (read and
        cleared under one lock hold, so the count is exact)."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            return n

    def zeroize(self) -> None:
        """End the cached keys' device-state lifetime (same convention as
        SecureLogger.zeroize / KeyStorage.lock).  Sign-path entries are
        KEY-EQUIVALENT material: an algorithm hot-swap or shutdown must not
        leave them pinned on device — dropping the references releases the
        buffers to the runtime (host code cannot overwrite device memory, so
        release is the strongest zeroization available here).  Called by
        SecureMessaging's hot-swap paths."""
        n = self.clear()
        # key-lifetime events belong in the flight ring: a dump after a
        # hot-swap shows WHEN the outgoing provider's device state was
        # released (counts only — never key identities)
        from ..obs import flight as _flight

        _flight.record("opcache_zeroized", entries=n)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }
