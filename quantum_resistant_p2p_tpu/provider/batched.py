"""Async batching queue — the host<->TPU boundary (the north-star refactor).

The reference performs one blocking liboqs FFI call per handshake op
(crypto/key_exchange.py:155,178).  Here, concurrent handshakes enqueue their
crypto ops as futures; a flusher collects them into one padded batch and
dispatches a single jitted TPU program, then resolves every future.  Flush
policy: immediately at ``max_batch``, otherwise ``max_wait_ms`` after the
first enqueue — bounding added p50 latency while amortising dispatch overhead
(SURVEY.md §7.4 item 6).

The dispatch itself runs in a worker thread (``run_in_executor``) so the
asyncio loop — which is also serving TCP peers (net.p2p_node) — never blocks
on device compute.

Wrapper classes expose the same op names as the plugin boundary
(KeyExchangeAlgorithm / SignatureAlgorithm, provider.base) but as coroutines;
``SecureMessaging`` awaits them on its handshake path (app/messaging.py here;
reference flow app/messaging.py:546-1134).
"""

from __future__ import annotations

import asyncio
import functools
import logging
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from ..faults import plan as _faults
from ..native import wipe
from ..obs import flight as obs_flight
from ..obs import trace as obs_trace
from ..obs.metrics import LatencyHistogram
from .base import (KeyExchangeAlgorithm, SignatureAlgorithm,
                   next_pow2 as _next_pow2, pad_rows as _pad_rows)

#: priority lanes, highest priority first (lowest value wins the flush
#: order): re-keys of live sessions must never starve behind a bulk
#: flood, and fresh handshakes sit between the two (docs/gateway.md).
#: Lane tags ride each queued op; the flush drain takes ops in
#: (lane, arrival) order, so with single-lane traffic (every pre-gateway
#: caller) the drain is bit-for-bit the old insertion-order slice.
LANE_REKEY, LANE_HANDSHAKE, LANE_BULK = 0, 1, 2
#: (Ticket-resume classification, docs/protocol.md "Session resumption":
#: the abbreviated exchange dispatches NO device ops, and any op a
#: RESUMED session later queues — a post-resume rekey, its bulk seals —
#: already classifies onto LANE_REKEY through the engine's
#: had-a-completed-session rule, which a successful resume marks exactly
#: like a full handshake.  No separate lane tag exists on purpose.)
LANE_NAMES = {LANE_REKEY: "rekey", LANE_HANDSHAKE: "handshake",
              LANE_BULK: "bulk"}


class LaneShed(RuntimeError):
    """A lane hit its pending-depth bound and this op was shed (loudly) —
    admission control at the queue: bounded memory, and a bulk flood
    degrades BULK, not the rekey/handshake lanes sharing the queue."""

    def __init__(self, label: str, lane: int, depth: int):
        super().__init__(
            f"queue {label}: {LANE_NAMES.get(lane, lane)} lane shed at "
            f"depth {depth}"
        )
        self.lane = lane


@dataclass
class QueueStats:
    """Per-op-queue counters (surfaced in metrics; SURVEY.md §5 tracing gap)."""

    ops: int = 0
    flushes: int = 0
    max_batch_seen: int = 0
    total_wait_s: float = 0.0
    total_dispatch_s: float = 0.0
    #: ops/flushes served by the cpu fallback while the device path was
    #: slow or timed out (degrade-don't-fail; VERDICT r2 weak #1)
    fallback_ops: int = 0
    fallback_flushes: int = 0
    breaker_trips: int = 0
    #: serial device-dispatch round trips this queue has made (one batch_fn
    #: call through the device or warmup executor = one trip; serial trips
    #: bound the handshake's latency, so they are counted, not inferred —
    #: see docs/dispatch_budget.md)
    device_trips: int = 0
    #: per-flush dispatch latency percentiles (obs.metrics) — measured
    #: from the event loop, so queue-wait/executor contention included
    dispatch_hist: LatencyHistogram = field(default_factory=LatencyHistogram)
    #: ON-WORKER batch-fn latency (the device program itself, no executor
    #: queueing): what the autotuner's amortization window keys on — the
    #: loop-side number would feed back (contention -> wider window ->
    #: more contention)
    device_hist: LatencyHistogram = field(default_factory=LatencyHistogram)
    #: ops submitted / shed per priority lane (lane tag -> count)
    lane_ops: dict = field(default_factory=dict)
    lane_sheds: dict = field(default_factory=dict)

    def as_dict(self) -> dict[str, Any]:
        h = self.dispatch_hist
        return {
            "ops": self.ops,
            "flushes": self.flushes,
            "max_batch_seen": self.max_batch_seen,
            "avg_batch": (self.ops / self.flushes) if self.flushes else 0.0,
            "avg_dispatch_ms": (
                1e3 * self.total_dispatch_s / self.flushes if self.flushes else 0.0
            ),
            "p50_dispatch_ms": round(1e3 * (h.percentile(50) or 0.0), 3),
            "p99_dispatch_ms": round(1e3 * (h.percentile(99) or 0.0), 3),
            "p50_device_ms": round(
                1e3 * (self.device_hist.percentile(50) or 0.0), 3),
            "p99_device_ms": round(
                1e3 * (self.device_hist.percentile(99) or 0.0), 3),
            "fallback_ops": self.fallback_ops,
            "fallback_flushes": self.fallback_flushes,
            "breaker_trips": self.breaker_trips,
            "device_trips": self.device_trips,
            # the degradation gauge (VERDICT r3: the config-5 "TPU" swarm was
            # silently ~100% cpu-served): 1.0 = every op rode the device path
            "device_served_fraction": (
                round((self.ops - self.fallback_ops) / self.ops, 4)
                if self.ops else None
            ),
            # additive keys (the legacy layout above is a compatibility
            # contract): per-lane submit/shed counts, by lane name
            "lanes": {LANE_NAMES.get(k, str(k)): v
                      for k, v in sorted(self.lane_ops.items())},
            "lane_sheds": {LANE_NAMES.get(k, str(k)): v
                           for k, v in sorted(self.lane_sheds.items())},
        }


class CoalescingHub:
    """Shared flush-coalescing machinery: the queues registered on one hub
    (a :class:`Breaker`, or the placement scheduler) flush in the same
    scheduling window, so independent KEM/SIG batches go in flight
    together instead of serialising one timer window apart.  Only queues
    that already hold items are touched: nothing flushes emptier/earlier
    than it would have on its own timer."""

    def _init_coalescer(self) -> None:
        #: weak: a hot-swapped facade's dead queues must not linger
        import weakref

        self._queues: weakref.WeakSet = weakref.WeakSet()
        self._coalescing = False

    def register_queue(self, queue: "OpQueue") -> None:
        self._queues.add(queue)

    def coalesce(self, origin: "OpQueue") -> None:
        """Flush every sibling queue with pending items in the SAME
        scheduling window as ``origin``'s flush."""
        if self._coalescing:
            return
        self._coalescing = True
        try:
            for q in list(self._queues):
                if q is not origin and q._items:
                    q._flush_local()
        finally:
            self._coalescing = False


class Breaker(CoalescingHub):
    """Shared circuit breaker for one device's dispatch path — a full
    closed -> open -> half-open state machine (the r3 self-healing fix:
    the old open/closed breaker let one transient device fault pin a fleet
    on the cpu fallback forever).

    States:

    * ``closed``      — every armed flush dispatches to the device.
    * ``open``        — every armed flush runs on the fallback until the
                        cool-off clock expires.  Consecutive failures make
                        the cool-off grow exponentially (capped).
    * ``half_open``   — the cool-off expired: exactly ONE real queued flush
                        is let through as a canary probe; siblings keep
                        falling back while it is in flight.  Probe success
                        closes the breaker (traffic returns to the device,
                        cool-off resets); failure re-opens it with a doubled
                        cool-off.
    * ``quarantined`` — the device-health gate (provider/health.py) found
                        the device path INCORRECT (not merely slow); the
                        breaker pins the fallback for the process lifetime —
                        wrong answers cannot be probed back to health.

    State transitions log ONE loud WARNING each, so a degraded fleet is
    visible in logs, not just in metrics.

    All op queues of a provider (and, via SecureMessaging, the KEM and
    signature facades together) share one breaker: the device is the
    common resource, so one op type discovering slowness shields the rest.

    The breaker also owns TWO executors: a 2-thread DEVICE pool for live
    dispatches (normal priority — steady-state dispatches must not be
    starved by the cpu fallback's own load, or the canary probe measures
    starvation instead of the device) and a 1-thread WARMUP pool
    at nice 19 for cold-bucket jit compiles, whose host-side CPU burn would
    otherwise starve the event loop and the fallback.  Hung, abandoned
    dispatches occupy at most the 2 device threads; they can never starve
    the default executor the fallback runs on.
    """

    def __init__(self, cooloff_s: float = 30.0, cooloff_max_s: float = 480.0,
                 clock: Callable[[], float] = time.monotonic):
        import threading

        #: injectable monotonic clock: the fleet manager (fleet/manager.py)
        #: reuses this exact state machine for its per-GATEWAY breakers and
        #: drives handoff/heal tests on deterministic timelines; production
        #: callers never pass it
        self._clock = clock

        #: guards every state-machine mutation: the breaker is shared between
        #: the event loop (dispatch outcomes) and the warmup thread (the
        #: device-health gate quarantines from there) — qrflow's
        #: cross-thread-state pack proved the unlocked writes racy
        self._lock = threading.RLock()
        self.base_cooloff_s = cooloff_s
        self.cooloff_s = cooloff_s  # current (grows exponentially while open)
        self.cooloff_max_s = cooloff_max_s
        #: placement identity ("shard<i>" when owned by a scheduler shard):
        #: rides in logs and flight events so a degraded SHARD is
        #: distinguishable from a degraded fleet
        self.label = ""
        self.state = "closed"
        self.trips = 0
        #: open/close transition counters (metrics; every transition also
        #: logs one WARNING)
        self.opens = 0
        self.closes = 0
        #: serial device-dispatch round trips aggregated across every queue
        #: sharing this breaker (KEM + signature + composite): the number
        #: SecureMessaging diffs around a handshake to measure
        #: trips-per-handshake (docs/dispatch_budget.md)
        self.device_trips = 0
        #: fallback flushes aggregated the same way (a fallback flush is a
        #: serial step too — just a cpu one)
        self.fallback_trips = 0
        self._open_until = 0.0
        self._probe_in_flight = False
        #: cumulative seconds spent NOT closed (open/half-open/quarantined)
        #: plus the start of the current degraded stretch — the "breaker
        #: open time" SLO feed (obs/slo.py): budget burn is the fraction of
        #: wall time the device path was unavailable
        self._degraded_s = 0.0
        self._degraded_since: float | None = None
        self._executor = None
        self._warmup_executor = None
        # queues sharing this breaker coalesce their flushes (CoalescingHub)
        self._init_coalescer()

    def is_open(self) -> bool:
        """True while no regular device dispatch may proceed."""
        with self._lock:
            if self.state == "quarantined":
                return True
            return self.state == "open" and self._clock() < self._open_until

    def probe_ready(self) -> bool:
        """True when the next :meth:`acquire_dispatch` would route a canary
        probe (open past the cool-off, or half-open with no probe in
        flight).  The placement policy (provider/scheduler.py) routes one
        flush back to such a shard so it can heal — without this, a
        multi-shard plane would starve open shards of the probe traffic
        the half-open state machine needs."""
        with self._lock:
            if self._probe_in_flight or self.state == "quarantined":
                return False
            if self.state == "half_open":
                return True
            return self.state == "open" and self._clock() >= self._open_until

    def _set_state(self, new: str, why: str = "") -> None:
        """Transition + loud log + structured flight-recorder event (the
        one-time WARNINGs were log-only and invisible to tooling before
        obs/; breaker-open and quarantine are auto-dump triggers).
        Callers hold ``self._lock`` (RLock)."""
        with self._lock:
            if new == self.state:
                return
            log = logging.getLogger(__name__)
            old = self.state
            self.state = new
            # degraded-time ledger (the breaker-availability SLO feed)
            now = self._clock()
            if old == "closed" and new != "closed":
                self._degraded_since = now
            elif new == "closed" and self._degraded_since is not None:
                self._degraded_s += now - self._degraded_since
                self._degraded_since = None
            if new == "open":
                self.opens += 1
                log.warning(
                    "circuit breaker OPEN (%s): device dispatch path degraded; "
                    "serving from cpu fallback for %.1fs, then probing",
                    why or "tripped", self.cooloff_s,
                )
            elif new == "closed":
                self.closes += 1
                self.cooloff_s = self.base_cooloff_s
                log.warning(
                    "circuit breaker CLOSED: device canary probe succeeded; "
                    "traffic restored to the device path"
                )
            elif new == "quarantined":
                log.error(
                    "circuit breaker QUARANTINED (%s): device path disabled for "
                    "this process; all ops served from the cpu fallback", why,
                )
            # emit AFTER the bookkeeping so the event carries the real
            # counters (open/quarantined are auto-dump triggers; the bundle
            # build runs on the flight recorder's own thread, never here)
            emit = (obs_flight.trigger if new in ("open", "quarantined")
                    else obs_flight.record)
            emit(
                "breaker_open" if new == "open"
                else "breaker_quarantined" if new == "quarantined"
                else "breaker_transition",
                state=new, prev=old, why=why, cooloff_s=round(self.cooloff_s, 3),
                opens=self.opens, closes=self.closes, shard=self.label or None,
            )

    def trip(self) -> None:
        """Record a device failure observed outside the claim protocol
        (direct callers, tests): opens the breaker without escalating the
        canary backoff."""
        self._trip(escalate=False)

    def _trip(self, escalate: bool) -> None:
        """From closed: open at the base cool-off.  ``escalate`` (a FAILED
        CANARY PROBE — the only fresh evidence the device is still broken)
        doubles the cool-off, capped.  Non-probe failures never escalate
        and never touch the probe token: a straggler dispatch from the
        previous incident finishing late while open/half-open only
        refreshes the clock (or re-opens), so one incident's concurrent
        dispatches cannot compound the backoff or race the live canary.
        A quarantined breaker stays quarantined."""
        with self._lock:
            self.trips += 1
            if self.state == "quarantined":
                return
            if escalate:
                self.cooloff_s = min(self.cooloff_s * 2.0, self.cooloff_max_s)
            elif self.state == "closed":
                self.cooloff_s = self.base_cooloff_s
            self._open_until = self._clock() + self.cooloff_s
            if self.state == "open":
                logging.getLogger(__name__).debug(
                    "circuit breaker already open: cool-off clock refreshed "
                    "(concurrent dispatch of the same incident)"
                )
            else:
                self._set_state(
                    "open", "canary probe failed" if escalate else "tripped"
                )

    def degraded_seconds(self) -> float:
        """Cumulative wall seconds this breaker spent NOT closed (open,
        half-open, or quarantined), the live stretch included — the
        numerator of the availability SLO (obs/slo.py): ``bad time /
        total time`` is the burn of the "device path available" objective."""
        with self._lock:
            total = self._degraded_s
            if self._degraded_since is not None:
                total += self._clock() - self._degraded_since
            return total

    def quarantine(self, why: str) -> None:
        """Pin the fallback for the process lifetime (device-health gate:
        the device path computes WRONG answers, which no latency probe can
        detect).  Runs on the WARMUP THREAD — the lock is what makes it safe
        against concurrent loop-side trips."""
        with self._lock:
            self.trips += 1
            self._set_state("quarantined", why)

    def acquire_dispatch(self) -> str:
        """Claim the next armed flush's route: ``"device"`` (closed),
        ``"probe"`` (half-open canary — exactly one in flight), or
        ``"fallback"``.  Pair with :meth:`record_success` /
        :meth:`record_failure` / :meth:`release`."""
        with self._lock:
            if self.state == "closed":
                return "device"
            if self.state == "quarantined":
                return "fallback"
            if self.state == "open":
                if self._clock() < self._open_until:
                    return "fallback"
                self._set_state("half_open")
            if self._probe_in_flight:
                return "fallback"
            self._probe_in_flight = True
            return "probe"

    def record_success(self, claim: str) -> None:
        with self._lock:
            if claim == "probe":
                self._probe_in_flight = False
                self._set_state("closed")

    def record_failure(self, claim: str) -> None:
        with self._lock:
            if claim == "probe":
                self._probe_in_flight = False
                self._trip(escalate=True)
            else:
                self._trip(escalate=False)

    def release(self, claim: str) -> None:
        """Return an un-dispatched claim (e.g. the flush went to the warm-up
        path instead) without recording an outcome."""
        with self._lock:
            if claim == "probe":
                self._probe_in_flight = False

    @property
    def device_executor(self):
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor

            self._executor = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="qrp2p-device"
            )
        return self._executor

    @property
    def warmup_executor(self):
        if self._warmup_executor is None:
            import os
            from concurrent.futures import ThreadPoolExecutor

            def _background_priority():
                # Linux nice() is per-thread: demote the compile worker so
                # cold-bucket jit never preempts the loop or the fallback.
                try:
                    os.nice(19)
                except OSError:  # pragma: no cover
                    pass

            self._warmup_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="qrp2p-warmup",
                initializer=_background_priority,
            )
        return self._warmup_executor


class OpQueue:
    """Accumulates (item -> future) pairs; flushes through a batch function.

    ``batch_fn(items) -> list[results]`` is called with at most ``max_batch``
    items, inside the default executor.

    Degradation policy (a production queue must not fail handshakes because
    its accelerator link is slow — the reference's serial liboqs path never
    does): when ``fallback_fn`` is given, a circuit breaker watches device
    dispatch latency.  A dispatch slower than ``degrade_after_ms`` (or one
    that exceeds the hard ``dispatch_timeout_ms``, in which case the stuck
    device call is abandoned to finish in the background) trips the breaker
    for its cool-off; while open, flushes run on the fallback — slower per
    op, but it completes.  After the cool-off the next flush probes the
    device path again.
    """

    def __init__(
        self,
        batch_fn: Callable[[list[Any]], list[Any]],
        max_batch: int = 4096,
        max_wait_ms: float = 2.0,
        fallback_fn: Callable[[list[Any]], list[Any]] | None = None,
        degrade_after_ms: float = 2000.0,
        dispatch_timeout_ms: float = 15000.0,
        degrade_ref_batch: int = 256,
        breaker: Breaker | None = None,
        bucket_floor: int = 1,
        label: str = "",
        scheduler=None,
        lane_capacity: dict[int, int] | None = None,
        warm_check: Callable[[list[Any], int], bool] | None = None,
    ):
        #: queue name at the fault-injection boundary (faults/) and in logs
        self.label = label
        #: placement axis (provider.scheduler.DeviceProgramScheduler):
        #: every flush is placed on one of its shards, each with its OWN
        #: breaker + executors.  None = the classic single-breaker path.
        self.scheduler = scheduler
        self.batch_fn = batch_fn
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        self.fallback_fn = fallback_fn
        self.degrade_after_s = degrade_after_ms / 1e3
        self.dispatch_timeout_s = dispatch_timeout_ms / 1e3
        #: flushes pad UP to at least this pow2 bucket.  Collapses the
        #: bucket space from log2(max_batch) sizes to a handful, so a
        #: pre-warm covers every size a live swarm can hit; small flushes
        #: cost about the same as a floor-sized one (dispatches this small
        #: are expected to be launch-dominated; not measured on this chip).
        #: Rounded up to a power of two and capped at max_batch so the
        #: effective bucket always matches what warmup() compiles.
        self.bucket_floor = min(_next_pow2(max(1, bucket_floor)), max_batch)
        #: thresholds are for a <= degrade_ref_batch flush and scale
        #: linearly above it — a 4096-row dispatch is ALLOWED to take 16x
        #: longer than a 256-row one before it counts as "slow"; without
        #: this, peak load (big healthy batches) trips the breaker forever
        self.degrade_ref_batch = degrade_ref_batch
        #: clears a stuck _warming flag so warm-ups are retried (see
        #: _run_batch); generous — a cold fused-handshake compile takes
        #: minutes
        self.warmup_watchdog_s = 600.0
        if scheduler is not None:
            # shard 0's breaker doubles as the compat handle (legacy stats
            # readers); claims are taken per-PLACED-shard in _run_batch
            self.breaker = (breaker if breaker is not None
                            else scheduler.shards[0].breaker)
            self._coalescer = scheduler
        else:
            self.breaker = breaker if breaker is not None else Breaker()
            self._coalescer = self.breaker
        self._coalescer.register_queue(self)
        #: pow2 sizes whose device program has completed at least once; a
        #: cold bucket's ops are served by the fallback while the compile
        #: runs in the background (never hostage to a compile).  Guarded by
        #: ``_warm_lock``: facade warmups mark buckets from the WARMUP
        #: THREAD while loop-side dispatches read and mutate the same sets
        #: (qrflow cross-thread-state).
        import threading

        self._warm_lock = threading.Lock()
        self._warm_buckets: set[int] = set()
        self._warming: set[int] = set()
        #: optional SECOND warm axis: ``warm_check(items, bucket) -> bool``
        #: refines the pow2-batch-bucket tracking for ops whose compiled
        #: program also keys on per-item shape (the AEAD queues' message/
        #: aad length buckets).  A flush whose batch bucket is warm but
        #: whose shapes are novel is served from the fallback while the
        #: background warm compiles EXACTLY the live shapes (_warm_call
        #: runs the real batch fn on the real items) — a novel length
        #: bucket must degrade gracefully, never compile inside a live
        #: device dispatch and trip the breaker as "slow".
        self.warm_check = warm_check
        self.stats = QueueStats()
        #: per-lane pending-depth bounds (lane tag -> max pending); an op
        #: submitted to a full lane is SHED (LaneShed, loud) instead of
        #: growing the queue without bound — None/absent = unbounded
        self.lane_capacity = lane_capacity
        #: adaptive flush policy (provider/autotune.py QueueTuner): when
        #: attached, overrides the flush-at threshold and timer window on
        #: the hot path; None (the default, and QRP2P_AUTOTUNE=0) reads
        #: the static constructor values — bit-for-bit the old behavior
        self.tuner = None
        #: device-cost ledger (obs/cost.py CostLedger): when attached,
        #: flushes record their occupancy (real vs padded slots), cold
        #: buckets their compile seconds, dispatches their device time.
        #: Observation only — never steers when/what a flush dispatches
        self.cost = None
        self._items: list[Any] = []
        self._futures: list[asyncio.Future] = []
        #: lane tag per pending item (parallel to _items), plus O(1)
        #: pending counts per lane — the capacity check runs on EVERY
        #: capped-lane submit, and a list scan there would make a
        #: saturated queue quadratic across a burst
        self._lane_tags: list[int] = []
        self._lane_pending: dict[int, int] = {}
        self._timer: asyncio.TimerHandle | None = None
        self._first_enqueue_t = 0.0
        #: strong refs to in-flight dispatch tasks: the loop holds only weak
        #: references, so an unreferenced flush could be GC'd mid-dispatch
        self._dispatch_tasks: set[asyncio.Task] = set()

    def mark_warm(self, bucket: int) -> None:
        """Record that ``bucket``'s device program is compiled.  Thread-safe:
        the facades' ``warmup()`` runs on the background warmup thread while
        the event loop reads/mutates the same sets mid-dispatch."""
        with self._warm_lock:
            self._warming.discard(bucket)
            self._warm_buckets.add(bucket)

    def _wait_s(self) -> float:
        """Flush-timer window: the tuner's adaptive window when attached
        and past its cold start, else the static constructor value
        (bit-for-bit the old path)."""
        if self.tuner is None:
            return self.max_wait_s
        w = self.tuner.wait_s()
        return self.max_wait_s if w is None else w

    def _flush_at(self) -> int:
        """Pending-op count that triggers an immediate flush: the tuner's
        chosen bucket when attached and decided, else ``max_batch`` (the
        old path: flush on the timer or a full batch).  A bucket of 1 is
        NOT an early trigger — flushing every submit solo would shatter
        the coalescing the (short) window still provides; at bucket 1 the
        window is the whole policy."""
        if self.tuner is None:
            return self.max_batch
        b = self.tuner.flush_at()
        if b is None or b <= 1:
            return self.max_batch
        return min(self.max_batch, b)

    def _shed(self, lane: int) -> None:
        n = self.stats.lane_sheds.get(lane, 0) + 1
        self.stats.lane_sheds[lane] = n
        # loud but bounded: a bulk flood must not turn the log/flight ring
        # into a wall of identical shed lines
        if n == 1 or n % 128 == 0:
            logging.getLogger(__name__).warning(
                "queue %s: %s lane at capacity (%d pending); op shed "
                "(%d total)", self.label or "?", LANE_NAMES.get(lane, lane),
                self.lane_capacity.get(lane), n,
            )
            obs_flight.record(
                "load_shed", where="lane", queue=self.label,
                lane=LANE_NAMES.get(lane, str(lane)), sheds=n,
            )
        raise LaneShed(self.label, lane, self.lane_capacity.get(lane, 0))

    async def submit(self, item: Any, lane: int = LANE_HANDSHAKE) -> Any:
        loop = asyncio.get_running_loop()
        cap = (self.lane_capacity or {}).get(lane)
        if cap is not None and self._lane_pending.get(lane, 0) >= cap:
            self._shed(lane)
        fut: asyncio.Future = loop.create_future()
        self._items.append(item)
        self._futures.append(fut)
        self._lane_tags.append(lane)
        self._lane_pending[lane] = self._lane_pending.get(lane, 0) + 1
        self.stats.ops += 1
        self.stats.lane_ops[lane] = self.stats.lane_ops.get(lane, 0) + 1
        if len(self._items) == 1:
            self._first_enqueue_t = time.perf_counter()
            self._timer = loop.call_later(self._wait_s(), self._flush_soon)
        if len(self._items) >= self._flush_at():
            self._flush_soon()
        return await fut

    def _flush_soon(self) -> None:
        """Flush this queue, then coalesce sibling queues sharing the
        breaker/scheduler into the same scheduling window so independent
        KEM/SIG batches go in flight together (under a scheduler, each
        coalesced flush is then PLACED independently — siblings can land
        on different shards and run in parallel)."""
        self._flush_local()
        self._coalescer.coalesce(self)

    def _take_batch(self) -> tuple[list[Any], list[asyncio.Future], int]:
        """Detach up to ``max_batch`` pending ops in (lane, arrival) order.

        With single-lane traffic (every caller that never passes ``lane``)
        the priority sort degenerates to the old insertion-order slice —
        the drain is bit-for-bit the pre-lane behavior.  Under mixed-lane
        load, a flush that cannot carry everything takes rekeys first,
        then handshakes, then bulk: a bulk flood defers bulk, never the
        rekey lane (the starvation bound, tests/test_gateway.py).
        Returns (items, futures, flush_lane) — flush_lane is the highest-
        priority lane aboard, stamped on the ``queue.flush`` span."""
        n = len(self._items)
        k = min(self.max_batch, n)
        if len(set(self._lane_tags)) <= 1:
            items = self._items[:k]
            futs = self._futures[:k]
            lane = self._lane_tags[0] if self._lane_tags else LANE_HANDSHAKE
            del self._items[:k], self._futures[:k], self._lane_tags[:k]
            if self._lane_tags:
                self._lane_pending[lane] = len(self._lane_tags)
            else:
                self._lane_pending.clear()
            return items, futs, lane
        order = sorted(range(n), key=lambda i: (self._lane_tags[i], i))
        take = order[:k]
        taken = set(take)
        items = [self._items[i] for i in take]
        futs = [self._futures[i] for i in take]
        lane = min(self._lane_tags[i] for i in take)
        for i in take:
            self._lane_pending[self._lane_tags[i]] -= 1
        self._items = [x for i, x in enumerate(self._items) if i not in taken]
        self._futures = [x for i, x in enumerate(self._futures)
                         if i not in taken]
        self._lane_tags = [x for i, x in enumerate(self._lane_tags)
                           if i not in taken]
        return items, futs, lane

    def _flush_local(self) -> None:
        """Detach pending items synchronously (so late submits can't bloat a
        batch past max_batch) and dispatch them as a task."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        loop = asyncio.get_running_loop()
        while self._items:
            items, futs, lane = self._take_batch()
            task = loop.create_task(
                self._dispatch(items, futs, self._first_enqueue_t, lane))
            self._dispatch_tasks.add(task)
            task.add_done_callback(self._reap_dispatch)

    def _reap_dispatch(self, task: asyncio.Task) -> None:
        self._dispatch_tasks.discard(task)
        if not task.cancelled() and task.exception() is not None:
            # _dispatch forwards batch errors to the waiter futures; anything
            # surfacing HERE escaped that path and must not vanish.
            logging.getLogger(__name__).error(
                "batch dispatch task failed", exc_info=task.exception()
            )

    def _trip_breaker(self, reason: str, dt: float, claim: str = "device",
                      breaker: Breaker | None = None) -> None:
        breaker = breaker if breaker is not None else self.breaker
        self.stats.breaker_trips += 1
        breaker.record_failure(claim)
        logging.getLogger(__name__).warning(
            "batch queue %s%s: device dispatch %s (%.1fs); serving from cpu "
            "fallback for %.0fs", self.label or "?",
            f" [{breaker.label}]" if breaker.label else "", reason, dt,
            breaker.cooloff_s,
        )

    async def _run_fallback(self, items: list[Any],
                            breaker: Breaker | None = None) -> list[Any]:
        breaker = breaker if breaker is not None else self.breaker
        self.stats.fallback_flushes += 1
        self.stats.fallback_ops += len(items)
        breaker.fallback_trips += 1
        loop = asyncio.get_running_loop()
        parent = obs_trace.current()
        return await loop.run_in_executor(
            None, self._traced_call, self.fallback_fn, "fallback.dispatch",
            "fallback", parent, items,
        )

    def _traced_call(self, fn, span_name: str, route: str, parent,
                     items: list[Any], shard=None,
                     first_t: float | None = None) -> list[Any]:
        """Run one dispatch callable inside a span, ON the worker thread —
        so the span measures the actual device/fallback time and carries
        the worker's thread lane in the flame graph.  ``parent`` is the
        loop-side context captured before the executor hop (contextvars do
        not cross ``run_in_executor``).  With a ``shard``, the call runs
        under that shard's placement context (Shard.run_placed) and the
        span carries the shard index — the flame graph shows which chip
        served each dispatch.

        A dispatch that runs a device program (every route but
        ``fallback`` and ``warmup``) is split into stages (obs/trace.py
        ``Span.begin_stages``): ``pack`` from the worker's start until the
        program is launched, ``run`` until its outputs are ready, and
        ``unpack`` until the batch fn returns (each program call marks the
        changes through the ambient span: provider/base.py ``ready``), each
        mirrored as a profiler annotation (:class:`_StageAnnotations`).
        Such a span also carries ``queued_ms``, from the flush's first
        submit (``first_t``) to the worker's start, and ``cpu_ms``, the
        worker thread's CPU time over the batch fn."""
        attrs = {"op": self.label, "n": len(items), "route": route}
        if shard is not None:
            attrs["shard"] = shard.index
        device = route not in ("fallback", "warmup")
        with obs_trace.span(span_name, parent=parent, **attrs) as sp:
            t0 = time.perf_counter()
            if device:
                if first_t is not None:
                    sp.set_attr("queued_ms", 1e3 * (t0 - first_t))
                sp.begin_stages("pack", _StageAnnotations(self.label))
                cpu0 = time.thread_time()
            try:
                if shard is not None:
                    return shard.run_placed(fn, items)
                return fn(items)
            finally:
                if device:
                    sp.set_attr("cpu_ms", 1e3 * (time.thread_time() - cpu0))
                    # on-worker DEVICE-program time (no executor queueing):
                    # the autotuner's amortization signal.  Fallback and
                    # warmup-compile durations must not pollute it — a
                    # recovery phase would otherwise tune its windows to
                    # cpu/compile time instead of device time
                    dt = time.perf_counter() - t0
                    self.stats.device_hist.record(dt)
                    if self.cost is not None:
                        # the cost ledger's device-seconds feed shares the
                        # same purity rule: device-program time only
                        self.cost.device_time(self.label, dt)

    def _count_trip(self, breaker: Breaker | None = None) -> None:
        """One serial device round trip (device or warmup executor): the
        per-handshake SLO currency (docs/dispatch_budget.md).  Recorded on
        the PLACED shard's breaker so per-shard ledgers stay truthful."""
        self.stats.device_trips += 1
        (breaker if breaker is not None else self.breaker).device_trips += 1

    def _device_call(self, items: list[Any], shard_index: int | None = None,
                     lane: int | None = None) -> list[Any]:
        """The device dispatch boundary: the explicit fault-injection hook
        (faults/) wraps the real batch fn — a raise here IS a device fault
        and is handled (breaker + fallback) exactly like one.  The shard
        index and the flush's priority lane ride into the fault-match info
        so chaos plans can kill ONE shard's device (match={"shard": i}) or
        target one lane's flushes (match={"lane": "bulk"})."""
        _faults.device_dispatch(
            self.label, len(items), shard=shard_index,
            lane=LANE_NAMES.get(lane) if lane is not None else None,
        )
        return _faults.poison_results(self.label, self.batch_fn(items))

    def _warm_call(self, items: list[Any]) -> list[Any]:
        """The warm-up boundary (fault scope "warmup": a killed warm-up
        thread surfaces as this call raising).  Under a scheduler the warm
        runs on every CLOSED shard (``scheduler.warmable_shards``) — a
        sick shard's hung device must not block warm-marking for the
        healthy plane; it cold-compiles inside its first placed flush
        after healing, absorbed by the slow-trip machinery."""
        _faults.warmup(self.label)
        if self.scheduler is not None:
            warm = self.scheduler.warmable_shards()
            if warm:
                out = None
                for sh in warm:
                    out = sh.run_placed(self.batch_fn, items)
                return out
        return self.batch_fn(items)

    def _claim(self):
        """The placement step: -> (shard | None, claim, breaker).  With a
        scheduler, the flush is placed on a shard (load-aware, probe-first,
        quarantine-aware) and the claim is taken on THAT shard's breaker;
        without one, the classic single-breaker claim."""
        if self.scheduler is not None:
            shard = self.scheduler.place()
            return shard, shard.breaker.acquire_dispatch(), shard.breaker
        return None, self.breaker.acquire_dispatch(), self.breaker

    async def _run_batch(self, items: list[Any], flush_span=None,
                         lane: int | None = None,
                         first_t: float | None = None) -> list[Any]:
        """Device path with watchdog + breaker; falls back to cpu when the
        device is slow, hung, or raising.  Each flush is placed whole on
        one shard (when a scheduler is armed) — a flush never splits
        across shards, so results stay bit-exact vs. the single path.
        ``first_t`` is the flush's first submit (``perf_counter``), the
        start of the dispatch span's ``queued_ms``."""
        loop = asyncio.get_running_loop()
        if self.fallback_fn is None:
            shard = self.scheduler.place() if self.scheduler is not None else None
            if flush_span is not None and shard is not None:
                flush_span.set_attr("shard", shard.index)
            try:
                self._count_trip(shard.breaker if shard is not None else None)
                self._cost_occupancy(items, lane, shard)
                return await loop.run_in_executor(
                    shard.breaker.device_executor if shard is not None else None,
                    self._traced_call, self._direct_fn(shard, lane),
                    "device.dispatch", "direct", obs_trace.current(), items,
                    shard, first_t,
                )
            finally:
                if shard is not None:
                    self.scheduler.done(shard)
        shard, claim, breaker = self._claim()
        if flush_span is not None and shard is not None:
            flush_span.set_attr("shard", shard.index)
        try:
            return await self._run_claimed(loop, items, shard, claim, breaker,
                                           lane, first_t)
        finally:
            if shard is not None:
                self.scheduler.done(shard)

    def _cost_occupancy(self, items: list[Any], lane: int | None,
                        shard) -> None:
        """Ledger hook for one DEVICE-path flush: real items vs the padded
        pow2 bucket the batch fn will dispatch (cpu-fallback flushes pad
        nothing and never reach here)."""
        if self.cost is None:
            return
        bucket = max(self.bucket_floor, _next_pow2(len(items)))
        self.cost.flush_occupancy(
            self.label,
            LANE_NAMES.get(lane, str(lane)) if lane is not None else "?",
            len(items), bucket,
            shard=shard.index if shard is not None else None,
        )

    def _direct_fn(self, shard, lane: int | None = None):
        """Bind the shard index and flush lane into the fault-hooked device
        call (the callable crosses run_in_executor positionally)."""
        if shard is None and lane is None:
            return self._device_call
        return functools.partial(
            self._device_call,
            shard_index=shard.index if shard is not None else None, lane=lane,
        )

    async def _run_claimed(self, loop, items: list[Any], shard, claim: str,
                           breaker: Breaker, lane: int | None = None,
                           first_t: float | None = None) -> list[Any]:
        if claim == "fallback":
            return await self._run_fallback(items, breaker)
        bucket = max(self.bucket_floor, _next_pow2(len(items)))
        scale = max(1.0, bucket / self.degrade_ref_batch)
        with self._warm_lock:
            is_warm = bucket in self._warm_buckets
            if is_warm and self.warm_check is not None:
                is_warm = self.warm_check(items, bucket)
            start_warm = not is_warm and bucket not in self._warming
            if start_warm:
                self._warming.add(bucket)
        if not is_warm:
            # A bucket's first device dispatch is a jit compile — tens of
            # seconds cold, easily past the protocol timeout.  Never hold
            # live ops hostage to a compile: serve them from the cpu NOW and
            # warm the bucket in the background (the nice-19 1-thread warmup
            # pool serialises compiles; the device takes over once warm).
            breaker.release(claim)  # nothing dispatches on this claim
            if start_warm:
                self._count_trip(breaker)
                warm_t0 = time.perf_counter()
                warm = loop.run_in_executor(
                    breaker.warmup_executor, self._traced_call,
                    self._warm_call, "device.dispatch", "warmup",
                    obs_trace.current(), items,
                )

                def _mark(f, b=bucket, t0=warm_t0):
                    if f.cancelled():
                        with self._warm_lock:
                            self._warming.discard(b)
                        return
                    if f.exception() is None:
                        self.mark_warm(b)
                        if self.cost is not None:
                            # in-flush cold compile: a live flush hit this
                            # bucket cold and these are the wall seconds
                            # until the device path could take over (the
                            # 1-thread warmup pool's queueing included —
                            # that wait IS part of the observed cost)
                            self.cost.compile_event(
                                self.label, b, time.perf_counter() - t0,
                                where="in_flush",
                            )
                    else:
                        with self._warm_lock:
                            self._warming.discard(b)
                        logging.getLogger(__name__).warning(
                            "bucket %d warm-up failed: %s", b, f.exception()
                        )

                warm.add_done_callback(_mark)

                # Watchdog: a hung warm-up must not pin the bucket in
                # _warming forever (that would silently disable the device
                # path with no retry).  After the timeout, clear the flag so
                # a later flush retries; the stuck thread, if any, still
                # occupies only the 1-thread warmup pool.
                def _unstick(b=bucket, w=warm):
                    with self._warm_lock:
                        stuck = not w.done() and b in self._warming
                        if stuck:
                            self._warming.discard(b)
                    if stuck:
                        logging.getLogger(__name__).warning(
                            "bucket %d warm-up still running after %.0fs; "
                            "will retry on a later flush", b,
                            self.warmup_watchdog_s,
                        )

                loop.call_later(self.warmup_watchdog_s, _unstick)
            return await self._run_fallback(items, breaker)
        t0 = time.perf_counter()
        self._count_trip(breaker)
        self._cost_occupancy(items, lane, shard)
        # Dedicated 2-thread device pool PER BREAKER (per shard, under a
        # scheduler — placed flushes on different shards genuinely run in
        # parallel): an abandoned hung dispatch can never starve the
        # default executor that the cpu fallback runs on.
        device = loop.run_in_executor(
            breaker.device_executor, self._traced_call,
            self._direct_fn(shard, lane), "device.dispatch", claim,
            obs_trace.current(), items, shard, first_t,
        )
        try:
            results = await asyncio.wait_for(
                asyncio.shield(device), self.dispatch_timeout_s * scale
            )
        except asyncio.TimeoutError:
            # The device call cannot be cancelled (it is a thread); abandon it
            # to finish in the background and serve these ops from the cpu.
            self._trip_breaker("timed out", time.perf_counter() - t0, claim,
                               breaker)
            device.add_done_callback(lambda f: f.exception())  # reap quietly
            return await self._run_fallback(items, breaker)
        except Exception as exc:  # qrlint: disable=broad-except  — the failure is recorded to the breaker and logged by _trip_breaker, then served from the fallback
            # The device dispatch RAISED (device error, compile blow-up,
            # injected fault): record it to the breaker and degrade — a
            # raising device must heal through the half-open probe exactly
            # like a slow one, not fail its waiters.
            self._trip_breaker(f"raised {type(exc).__name__}",
                               time.perf_counter() - t0, claim, breaker)
            return await self._run_fallback(items, breaker)
        dt = time.perf_counter() - t0
        if dt > self.degrade_after_s * scale:
            self._trip_breaker("slow", dt, claim, breaker)
        else:
            breaker.record_success(claim)
        return results

    async def _dispatch(self, items: list[Any], futs: list[asyncio.Future],
                        first_t: float, lane: int = LANE_HANDSHAKE) -> None:
        self.stats.flushes += 1
        self.stats.max_batch_seen = max(self.stats.max_batch_seen, len(items))
        self.stats.total_wait_s += time.perf_counter() - first_t
        t0 = time.perf_counter()
        try:
            # The flush task inherits the context captured when its timer/
            # task was scheduled — i.e. the FIRST enqueuer's span — so a
            # handshake's flushes chain under its handshake span.
            with obs_trace.span("queue.flush", op=self.label, n=len(items),
                                lane=LANE_NAMES.get(lane, str(lane)),
                                waited_ms=round(
                                    1e3 * (t0 - first_t), 3)) as sp:
                # _run_batch stamps the placed shard onto this span, so the
                # flame graph's flush lane names the chip that served it
                results = await self._run_batch(items, sp, lane, first_t)
            dt = time.perf_counter() - t0
            self.stats.total_dispatch_s += dt
            self.stats.dispatch_hist.record(dt)
            if self.tuner is not None:
                # the autotuner steps on flush completion (no background
                # task): cheap cadence check, decisions off the hot path
                self.tuner.maybe_step()
            for f, r in zip(futs, results):
                if f.cancelled():
                    continue
                # batch fns report per-item failures as Exception instances so
                # one bad item doesn't poison its batch mates
                if isinstance(r, Exception):
                    f.set_exception(r)
                else:
                    f.set_result(r)
        except Exception as exc:  # propagate to every waiter
            for f in futs:
                if not f.cancelled():
                    f.set_exception(exc)


#: the profiler's names of a device dispatch's stages, each followed by
#: the queue's label (docs/observability.md)
STAGE_ANNOTATIONS = {"pack": "qrp2p.host.pack", "run": "qrp2p.device.run",
                     "unpack": "qrp2p.host.unpack"}


class _StageAnnotations:
    """A dispatch span's stages as ``jax.profiler.TraceAnnotation``s on the
    worker thread (the ``on_stage`` hook of ``Span.begin_stages``), so a
    profiler trace holds them on the device trace's clock.  Inactive
    annotations cost a name and a constructor call.  A process that has
    not imported JAX runs no device program and has no profiler, so it
    annotates nothing."""

    __slots__ = ("label", "_open")

    def __init__(self, label: str):
        self.label = label
        self._open = None

    def __call__(self, stage: str | None) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if stage is not None and profiler is not None:
            self._open = profiler.TraceAnnotation(
                f"{STAGE_ANNOTATIONS[stage]} {self.label}")
            self._open.__enter__()


def _run_valid(items, is_valid, dispatch, invalid_result, floor=1):
    """Shared filter-pad-dispatch-scatter skeleton for the batch fns.

    ``is_valid(item) -> bool`` selects items safe to stack; ``dispatch(valid
    items, pow2 target) -> per-item results`` runs the padded device batch;
    invalid slots get ``invalid_result()`` so one attacker-supplied ragged
    input never poisons its batch mates.
    """
    valid_idx = [i for i, it in enumerate(items) if is_valid(it)]
    results = [invalid_result() for _ in items]
    if valid_idx:
        # pad to the pow2 of the FLUSH size (raised to the facade's bucket
        # floor), not the valid count: OpQueue keys its warm-bucket tracking
        # on that same size, so the compiled program shape must match it
        # even when attacker-supplied invalid items were filtered out
        tgt = max(floor, _next_pow2(len(items)))
        out = dispatch([items[i] for i in valid_idx], tgt)
        for j, i in enumerate(valid_idx):
            results[i] = out[j]
    return results


def _make_queues(algo, fallback, breaker, max_batch, max_wait_ms,
                 batch_meths, degrade_opts, bucket_floor=1, scheduler=None,
                 lane_capacity=None):
    """Build one OpQueue per batch method, wiring the shared breaker (or the
    placement scheduler) and the fallback partials (used by both facades
    below).  The device path pads to ``bucket_floor``; the cpu fallback
    keeps floor 1 (padding would only add serial native work)."""
    out = []
    for meth in batch_meths:
        fb = functools.partial(meth, fallback, 1) if fallback is not None else None
        op = meth.__name__.strip("_").removesuffix("_batch").removesuffix("_")
        out.append(
            OpQueue(functools.partial(meth, algo, bucket_floor), max_batch,
                    max_wait_ms, fallback_fn=fb, breaker=breaker,
                    bucket_floor=bucket_floor, scheduler=scheduler,
                    lane_capacity=lane_capacity,
                    label=f"{algo.name}.{op}", **degrade_opts)
        )
    return out


def _facade_breaker(breaker, cooloff_s, scheduler=None):
    if scheduler is not None:
        if breaker is not None or cooloff_s is not None:
            raise ValueError("pass either scheduler or breaker/cooloff_s — "
                             "a scheduler owns one breaker per shard")
        return scheduler.shards[0].breaker  # the compat/metrics handle
    if breaker is not None:
        if cooloff_s is not None:
            raise ValueError("pass either breaker or cooloff_s, not both "
                             "(an explicit breaker carries its own cool-off)")
        return breaker
    return Breaker(cooloff_s if cooloff_s is not None else 30.0)


def _shard_placements(scheduler):
    """``(shard_index, placement context)`` pairs a facade warmup must
    compile under: one per CLOSED shard (jit caches are per device — a
    program warmed only on shard 0 would cold-compile inside shard 3's
    first live dispatch; a sick shard is skipped so its hung device
    cannot stall the sweep), or one ``(None, null context)`` for the
    classic single-device path (also the no-healthy-shard fallback:
    compiling the default-device program keeps the warmup contract's
    shape, and every claim routes to the cpu fallback until a shard
    heals anyway).  The index rides into the cost ledger's compile
    attribution (obs/cost.py)."""
    import contextlib

    if scheduler is None:
        yield None, contextlib.nullcontext()
        return
    warm = scheduler.warmable_shards()
    if not warm:
        yield None, contextlib.nullcontext()
        return
    for sh in warm:
        yield sh.index, sh.placement()


def facade_queues(facade):
    """The live OpQueues of one batched facade — BatchedKEM owns
    ``_kg``/``_enc``/``_dec``, BatchedSignature ``_sign``/``_verify``,
    BatchedFused the first three.  THE single source the engine-side
    attach loops iterate (the autotuner's ``attach_facades`` and the cost
    ledger's ``_attach_cost``): a queue added to a facade joins every
    observer by appearing here, instead of in N copied attribute lists."""
    for attr in ("_kg", "_enc", "_dec", "_sign", "_verify", "_seal", "_open"):
        q = getattr(facade, attr, None)
        if q is not None:
            yield q


def _timed_warm(facade, n: int, shard_idx: int | None) -> None:
    """Run one facade ``_warm_one`` under the clock and attribute its
    compile wall seconds to the cost ledger (obs/cost.py): one
    ``where="warmup"`` event per (shard, bucket) the background sweep
    compiled — the other half of the warmup-vs-in-flush attribution."""
    t0 = time.perf_counter()
    facade._warm_one(n)
    if facade.cost is not None:
        facade.cost.compile_event(
            facade.name, max(facade.bucket_floor, _next_pow2(n)),
            time.perf_counter() - t0, where="warmup", shard=shard_idx)


class BatchedAEAD:
    """Async facade over a ``BatchedAEADOps`` capability: the DATA plane.

    Bulk AEAD seal/open ops from every live session coalesce on the SAME
    OpQueue → scheduler → autotuner → breaker machinery as the KEM/
    signature facades — by default on :data:`LANE_BULK`, so a bulk flood
    defers bulk, never the rekey/handshake lanes sharing the queue window.

    Wire-format parity with the scalar path is structural: ``encrypt``
    prepends the same random 12-byte nonce ``SymmetricAlgorithm.encrypt``
    does, and the device seal/open is KAT-pinned bit-exact against the
    scalar twin at every length bucket (tests/test_chacha_pallas.py) — a
    peer cannot tell which path sealed a frame.

    ``scalar`` (the same-name scalar provider over OpenSSL) arms the
    degrade-don't-fail fallback:
    a slow/hung/raising device trips the shared breaker and messages are
    sealed on the cpu instead of failing.  Items longer than the device's
    bucket caps never enqueue at all — they run on the scalar path in an
    executor (one oversized file send must not compile a giant one-off
    device program or stall the loop).

    Zero-copy: plaintext/ciphertext operands may be ``memoryview``s (the
    binary wire path hands socket-buffer views straight through);
    ``np.frombuffer`` packs them into the device batch without an
    intermediate copy.
    """

    def __init__(self, device, scalar, max_batch: int = 4096,
                 max_wait_ms: float = 2.0,
                 breaker: Breaker | None = None,
                 cooloff_s: float | None = None,
                 bucket_floor: int = 1,
                 scheduler=None,
                 lane_capacity: dict[int, int] | None = None,
                 warm_shapes: tuple = ((256, 256), (1024, 256)),
                 **degrade_opts):
        self.device = device
        self.scalar = scalar
        #: the cpu-fallback handle the health gate checks (health.py)
        self.fallback = scalar
        self.name = device.name
        self.key_size = device.key_size
        self.nonce_size = device.nonce_size
        self.tag_size = device.tag_size
        self.bucket_floor = min(_next_pow2(max(1, bucket_floor)), max_batch)
        self.scheduler = scheduler
        #: cost ledger (obs/cost.py): warmup compile attribution
        self.cost = None
        #: (msg_len, aad_len) bucket pairs the background warmup compiles;
        #: storm/bench callers override to match their live payload shape
        self.warm_shapes = tuple(warm_shapes)
        self.breaker = _facade_breaker(breaker, cooloff_s, scheduler)
        self._seal, self._open = (
            OpQueue(batch_fn, max_batch, max_wait_ms, fallback_fn=fb,
                    breaker=None if scheduler is not None else self.breaker,
                    bucket_floor=self.bucket_floor, scheduler=scheduler,
                    lane_capacity=lane_capacity, warm_check=warm,
                    label=f"{device.name}.{op}", **degrade_opts)
            for batch_fn, fb, op, warm in (
                (self._seal_batch, self._seal_fallback, "seal",
                 self._seal_covered),
                (self._open_batch, self._open_fallback, "open",
                 self._open_covered),
            )
        )

    # -- validity (attacker-malformed operands fail alone, never the batch) --

    def _seal_valid(self, it) -> bool:
        key, nonce, pt, aad = it
        return (len(key) == self.key_size
                and len(nonce) == self.nonce_size
                and len(pt) <= self.device.max_len
                and len(aad) <= self.device.max_aad_len)

    def _open_valid(self, it) -> bool:
        key, nonce, data, aad = it
        return (len(key) == self.key_size
                and len(nonce) == self.nonce_size
                and self.tag_size <= len(data)
                and len(data) - self.tag_size <= self.device.max_len
                and len(aad) <= self.device.max_aad_len)

    # -- shape-aware warm checks (the OpQueue's second warm axis) ------------

    def _seal_covered(self, items, bucket: int) -> bool:
        valid = [it for it in items if self._seal_valid(it)]
        if not valid:
            return True
        return self.device.covers(True, bucket,
                                  max(len(it[2]) for it in valid),
                                  max(len(it[3]) for it in valid))

    def _open_covered(self, items, bucket: int) -> bool:
        valid = [it for it in items if self._open_valid(it)]
        if not valid:
            return True
        return self.device.covers(False, bucket,
                                  max(len(it[2]) - self.tag_size
                                      for it in valid),
                                  max(len(it[3]) for it in valid))

    # -- batch fns -----------------------------------------------------------

    @staticmethod
    def _rows(valid, idx, tgt):
        return _pad_rows(
            np.stack([np.frombuffer(it[idx], np.uint8) for it in valid]), tgt)

    def _seal_batch(self, items):
        def dispatch(valid, tgt):
            pad = tgt - len(valid)
            out = self.device.seal_batch(
                self._rows(valid, 0, tgt), self._rows(valid, 1, tgt),
                [it[2] for it in valid] + [valid[-1][2]] * pad,
                [it[3] for it in valid] + [valid[-1][3]] * pad,
            )
            return out

        return _run_valid(items, self._seal_valid, dispatch,
                          lambda: ValueError("bad AEAD seal operand"),
                          self.bucket_floor)

    def _open_batch(self, items):
        def dispatch(valid, tgt):
            pad = tgt - len(valid)
            return self.device.open_batch(
                self._rows(valid, 0, tgt), self._rows(valid, 1, tgt),
                [it[2] for it in valid] + [valid[-1][2]] * pad,
                [it[3] for it in valid] + [valid[-1][3]] * pad,
            )

        # the open contract maps EVERY malformed input to the same typed
        # failure the scalar decrypt raises — never a distinguishable crash
        return _run_valid(items, self._open_valid, dispatch,
                          lambda: ValueError("authentication failed"),
                          self.bucket_floor)

    # -- cpu scalar fallbacks (wire-identical) -------------------------------

    def _seal_fallback(self, items):
        def dispatch(valid, _tgt):
            return [self.scalar.seal(k, n, bytes(p), bytes(a) or None)
                    for k, n, p, a in valid]

        return _run_valid(items, self._seal_valid, dispatch,
                          lambda: ValueError("bad AEAD seal operand"), 1)

    def _open_fallback(self, items):
        def dispatch(valid, _tgt):
            out = []
            for k, n, d, a in valid:
                try:
                    out.append(self.scalar.open_(k, n, bytes(d),
                                                 bytes(a) or None))
                except ValueError as e:
                    out.append(ValueError(str(e)))
            return out

        return _run_valid(items, self._open_valid, dispatch,
                          lambda: ValueError("authentication failed"), 1)

    # -- async surface (scalar-compatible byte layouts) ----------------------

    async def encrypt(self, key: bytes, plaintext, associated_data=None,
                      lane: int = LANE_BULK) -> bytes:
        """-> ``nonce || ciphertext || tag`` — byte-compatible with the
        scalar ``SymmetricAlgorithm.encrypt``."""
        ad = bytes(associated_data) if associated_data else b""
        if (len(plaintext) > self.device.max_len
                or len(ad) > self.device.max_aad_len):
            # oversized for the device bucket space: scalar path, off-loop
            # (sealing a big file must not stall every peer this loop
            # serves)
            if self.cost is not None:
                # keep the ledger's device-served story truthful: this item
                # never enqueues, so the occupancy rows never see it
                self.cost.bypass_items(f"{self.name}.seal", "oversize")
            return await asyncio.get_running_loop().run_in_executor(
                None, functools.partial(
                    self.scalar.encrypt, bytes(key), bytes(plaintext),
                    ad or None))
        nonce = os.urandom(self.nonce_size)
        ct_tag = await self._seal.submit((bytes(key), nonce, plaintext, ad),
                                         lane)
        return nonce + ct_tag

    async def decrypt(self, key: bytes, data, associated_data=None,
                      lane: int = LANE_BULK) -> bytes:
        """Open ``nonce || ciphertext || tag``; ValueError on failure —
        the scalar decrypt contract.  ``data`` may be a memoryview (the
        binary wire's zero-copy socket-buffer slice)."""
        ad = bytes(associated_data) if associated_data else b""
        if len(data) < self.nonce_size + self.tag_size:
            raise ValueError("ciphertext too short")
        if (len(data) - self.nonce_size - self.tag_size > self.device.max_len
                or len(ad) > self.device.max_aad_len):
            if self.cost is not None:
                self.cost.bypass_items(f"{self.name}.open", "oversize")
            return await asyncio.get_running_loop().run_in_executor(
                None, functools.partial(
                    self.scalar.decrypt, bytes(key), bytes(data), ad or None))
        view = memoryview(data)
        return await self._open.submit(
            (bytes(key), bytes(view[: self.nonce_size]),
             view[self.nonce_size:], ad), lane)

    # -- warmup --------------------------------------------------------------

    def warmup(self, sizes: tuple[int, ...] = (1,)) -> None:
        """Compile seal/open for the pow2 batch buckets at every
        ``warm_shapes`` (msg, aad) bucket pair, then mark the buckets warm
        (blocking; run on the warmup thread).  Under a scheduler every
        size compiles on every shard first (see BatchedKEM.warmup)."""
        for shard_idx, placement in _shard_placements(self.scheduler):
            with placement:
                for n in sizes:
                    _timed_warm(self, n, shard_idx)
        for n in sizes:
            n2 = max(self.bucket_floor, _next_pow2(n))
            for q in (self._seal, self._open):
                q.mark_warm(n2)  # runs on the warmup thread: locked handoff

    def _warm_one(self, n: int) -> None:
        n2 = max(self.bucket_floor, _next_pow2(n))
        keys = np.zeros((n2, self.key_size), np.uint8)
        nonces = np.zeros((n2, self.nonce_size), np.uint8)
        for msg_len, aad_len in self.warm_shapes:
            pts = [bytes(msg_len)] * n2
            aads = [bytes(aad_len)] * n2
            sealed = self.device.seal_batch(keys, nonces, pts, aads)
            self.device.open_batch(keys, nonces, sealed, aads)

    def stats(self) -> dict[str, Any]:
        return {
            "seal": self._seal.stats.as_dict(),
            "open": self._open.stats.as_dict(),
        }


class BatchedKEM:
    """Async facade over a KeyExchangeAlgorithm's batch ops.

    ``fallback`` (a same-name cpu-backend provider) arms the OpQueues'
    degrade-don't-fail path: slow/hung device dispatches trip a breaker and
    ops run on the cpu instead of failing their protocol timeouts.
    """

    def __init__(self, algo: KeyExchangeAlgorithm, max_batch: int = 4096,
                 max_wait_ms: float = 2.0,
                 fallback: KeyExchangeAlgorithm | None = None,
                 breaker: Breaker | None = None,
                 cooloff_s: float | None = None,
                 bucket_floor: int = 1,
                 scheduler=None,
                 lane_capacity: dict[int, int] | None = None,
                 **degrade_opts):
        self.algo = algo
        self.fallback = fallback
        self.name = algo.name
        self.bucket_floor = min(_next_pow2(max(1, bucket_floor)), max_batch)
        #: placement axis shared with the sibling facades (None = classic)
        self.scheduler = scheduler
        #: cost ledger (obs/cost.py): warmup compile attribution
        self.cost = None
        # one breaker across keygen/encaps/decaps: the device is shared, so
        # any op discovering slowness shields the others immediately (per
        # SHARD under a scheduler — each shard carries its own)
        self.breaker = _facade_breaker(breaker, cooloff_s, scheduler)
        self._kg, self._enc, self._dec = _make_queues(
            algo, fallback, None if scheduler is not None else self.breaker,
            max_batch, max_wait_ms,
            (self._kg_batch, self._enc_batch, self._dec_batch), degrade_opts,
            self.bucket_floor, scheduler, lane_capacity,
        )

    @staticmethod
    def _kg_batch(algo, floor, items: list[None]) -> list[tuple[bytes, bytes]]:
        n = len(items)
        pks, sks = algo.generate_keypair_batch(max(floor, _next_pow2(n)))
        return [(bytes(pk), bytes(sk)) for pk, sk in zip(pks[:n], sks[:n])]

    @staticmethod
    def _enc_batch(algo, floor, items: list[bytes]):
        def dispatch(valid, tgt):
            pks = _pad_rows(np.stack([np.frombuffer(pk, np.uint8) for pk in valid]), tgt)
            cts, sss = algo.encapsulate_batch(pks)
            return [(bytes(ct), bytes(ss)) for ct, ss in zip(cts, sss)]

        return _run_valid(
            items,
            lambda pk: len(pk) == algo.public_key_len,
            dispatch,
            lambda: ValueError("bad public-key length"),
            floor,
        )

    @staticmethod
    def _dec_batch(algo, floor, items: list[tuple[bytes, bytes]]):
        def dispatch(valid, tgt):
            sks = _pad_rows(np.stack([np.frombuffer(sk, np.uint8) for sk, _ in valid]), tgt)
            cts = _pad_rows(np.stack([np.frombuffer(ct, np.uint8) for _, ct in valid]), tgt)
            return [bytes(ss) for ss in algo.decapsulate_batch(sks, cts)]

        return _run_valid(
            items,
            lambda it: (
                len(it[0]) == algo.secret_key_len
                and len(it[1]) == algo.ciphertext_len
            ),
            dispatch,
            lambda: ValueError("bad secret-key/ciphertext length"),
            floor,
        )

    def warmup(self, sizes: tuple[int, ...] = (1,)) -> None:
        """Compile the pow2 buckets a live queue will hit (blocking; run in a
        background thread).  Cold jit of the first handshake's size-1 bucket
        otherwise races the protocol timeout (SURVEY.md §7.4 item 6).

        Single-key encaps batches (every handshake; swarm hot peers) take
        the operand-cache fast path — different jit programs on miss
        (``_enc_cold``) and hit (``_enc_pre``) — so each size additionally
        runs a same-key pair of encaps calls to compile both.

        Under a scheduler every size compiles on EVERY shard (jit caches
        are per device; the opcache partitions per shard) before the
        bucket is marked warm — a warm bucket means warm wherever the
        placement policy can put a flush."""
        for shard_idx, placement in _shard_placements(self.scheduler):
            with placement:
                for n in sizes:
                    _timed_warm(self, n, shard_idx)
        for n in sizes:
            n2 = max(self.bucket_floor, _next_pow2(n))
            for q in (self._kg, self._enc, self._dec):
                q.mark_warm(n2)  # runs on the warmup thread: locked handoff

    def _warm_one(self, n: int) -> None:
        # compile the shape the live bucket will use
        n2 = max(self.bucket_floor, _next_pow2(n))
        pks, sks = self.algo.generate_keypair_batch(n2)
        # distinct keys: at n2 > 1 this compiles the mixed-key sliced
        # program; at n2 == 1 a single row takes the same opcache path
        # live batch-1 encaps always takes, so nothing is missed
        cts, _ = self.algo.encapsulate_batch(pks)
        self.algo.decapsulate_batch(sks, cts)
        if getattr(self.algo, "opcache", None) is not None:
            same = np.repeat(np.asarray(pks)[:1], n2, axis=0)
            self.algo.encapsulate_batch(same)  # cache miss: _enc_cold
            self.algo.encapsulate_batch(same)  # cache hit:  _enc_pre
        wipe(sks)  # warmup-only key material

    async def generate_keypair(self, lane: int = LANE_HANDSHAKE) -> tuple[bytes, bytes]:
        return await self._kg.submit(None, lane)

    async def encapsulate(self, public_key: bytes,
                          lane: int = LANE_HANDSHAKE) -> tuple[bytes, bytes]:
        return await self._enc.submit(public_key, lane)

    async def decapsulate(self, secret_key: bytes, ciphertext: bytes,
                          lane: int = LANE_HANDSHAKE) -> bytes:
        return await self._dec.submit((secret_key, ciphertext), lane)

    def stats(self) -> dict[str, Any]:
        return {
            "keygen": self._kg.stats.as_dict(),
            "encaps": self._enc.stats.as_dict(),
            "decaps": self._dec.stats.as_dict(),
        }


class BatchedSignature:
    """Async facade over a SignatureAlgorithm's batch ops.

    ``fallback`` mirrors BatchedKEM: a cpu-backend provider serving ops
    while the device path is slow or hung.
    """

    def __init__(self, algo: SignatureAlgorithm, max_batch: int = 4096,
                 max_wait_ms: float = 2.0,
                 fallback: SignatureAlgorithm | None = None,
                 breaker: Breaker | None = None,
                 cooloff_s: float | None = None,
                 bucket_floor: int = 1,
                 scheduler=None,
                 lane_capacity: dict[int, int] | None = None,
                 **degrade_opts):
        self.algo = algo
        self.fallback = fallback
        self.name = algo.name
        self.bucket_floor = min(_next_pow2(max(1, bucket_floor)), max_batch)
        self.scheduler = scheduler
        #: cost ledger (obs/cost.py): warmup compile attribution
        self.cost = None
        self.breaker = _facade_breaker(breaker, cooloff_s, scheduler)
        self._sign, self._verify = _make_queues(
            algo, fallback, None if scheduler is not None else self.breaker,
            max_batch, max_wait_ms,
            (self._sign_batch, self._verify_batch), degrade_opts,
            self.bucket_floor, scheduler, lane_capacity,
        )

    @staticmethod
    def _sign_batch(algo, floor, items: list[tuple[bytes, bytes]]):
        def dispatch(valid, tgt):
            sks = _pad_rows(np.stack([np.frombuffer(sk, np.uint8) for sk, _ in valid]), tgt)
            msgs = [m for _, m in valid] + [valid[-1][1]] * (tgt - len(valid))
            return algo.sign_batch(sks, msgs)

        return _run_valid(
            items,
            lambda it: len(it[0]) == algo.secret_key_len,
            dispatch,
            lambda: ValueError("bad secret-key length"),
            floor,
        )

    @staticmethod
    def _verify_batch(algo, floor, items: list[tuple[bytes, bytes, bytes]]) -> list[bool]:
        # Per the verify contract, malformed input means False — never raise.
        def dispatch(valid, tgt):
            pks = _pad_rows(np.stack([np.frombuffer(pk, np.uint8) for pk, _, _ in valid]), tgt)
            pad = tgt - len(valid)
            msgs = [m for _, m, _ in valid] + [valid[-1][1]] * pad
            sigs = [s for _, _, s in valid] + [valid[-1][2]] * pad
            try:
                oks = algo.verify_batch(pks, msgs, sigs)
            except Exception:  # qrlint: disable=broad-except  — verify contract: malformed input means False for the whole batch, never an exception
                oks = [False] * tgt
            return [bool(ok) for ok in oks]

        return _run_valid(
            items,
            lambda it: (
                len(it[0]) == algo.public_key_len
                and len(it[2]) == algo.signature_len
            ),
            dispatch,
            lambda: False,
            floor,
        )

    def warmup(self, sizes: tuple[int, ...] = (1,)) -> None:
        """Compile keygen/sign/verify for the pow2 buckets (blocking).

        Single-key batches (a node's own long-lived sign key; a repeat
        peer's verify key) take the operand-cache fast path, which runs
        DIFFERENT jit programs on miss (cache-filling ``*_cold``) and hit
        (``*_pre``) — so each size runs twice with a key fresh to the
        cache: the first call compiles the cold program, the second the
        hit program.  Otherwise a "warm" bucket's first cache hit cold-jits
        inside a live device dispatch and trips the breaker.

        Under a scheduler every size compiles on EVERY shard before the
        bucket is marked warm (see BatchedKEM.warmup)."""
        for shard_idx, placement in _shard_placements(self.scheduler):
            with placement:
                for n in sizes:
                    _timed_warm(self, n, shard_idx)
        for n in sizes:
            n2 = max(self.bucket_floor, _next_pow2(n))
            for q in (self._sign, self._verify):
                q.mark_warm(n2)  # runs on the warmup thread: locked handoff

    def _warm_one(self, n: int) -> None:
        have_cache = getattr(self.algo, "opcache", None) is not None
        # fresh key per size: the opcache persists across sizes, and a
        # cached key would skip the cold-program compile for this shape
        pk, sk = self.algo.generate_keypair()
        # compile the shape the live bucket will use
        n2 = max(self.bucket_floor, _next_pow2(n))
        sks = np.stack([np.frombuffer(sk, np.uint8)] * n2)
        pks = np.stack([np.frombuffer(pk, np.uint8)] * n2)
        reps = 2 if have_cache else 1
        for _ in range(reps):
            sigs = self.algo.sign_batch(sks, [b"warmup"] * n2)
        for _ in range(reps):
            self.algo.verify_batch(pks, [b"warmup"] * n2, sigs)
        if have_cache and n2 > 1:
            # distinct keys: compile the MIXED-key programs that the
            # same-key stacks above divert away from (live flushes
            # coalescing >= 2 clients' ops carry distinct keys)
            pks_d, sks_d = self.algo.generate_keypair_batch(n2)
            sigs_d = self.algo.sign_batch(sks_d, [b"warmup"] * n2)
            self.algo.verify_batch(pks_d, [b"warmup"] * n2, sigs_d)
            wipe(sks_d)
        wipe(sk)  # warmup-only key material

    async def sign(self, secret_key: bytes, message: bytes,
                   lane: int = LANE_HANDSHAKE) -> bytes:
        return await self._sign.submit((secret_key, message), lane)

    async def verify(self, public_key: bytes, message: bytes, signature: bytes,
                     lane: int = LANE_HANDSHAKE) -> bool:
        return await self._verify.submit((public_key, message, signature), lane)

    def stats(self) -> dict[str, Any]:
        return {
            "sign": self._sign.stats.as_dict(),
            "verify": self._verify.stats.as_dict(),
        }


class BatchedFused:
    """Async facade over a ``FusedHandshakeOps`` capability: three composite
    queues (keygen+sign / verify+encaps+sign / verify+decaps+sign) that
    collapse a handshake step's 2-3 serial device trips into one dispatch.

    Shares the per-op facades' breaker, so composite and per-op batches
    coalesce into one scheduling window (Breaker.coalesce) and a slow
    device discovered by either shields both.

    ``pk_off``/``ct_off`` are the static byte offsets of the hex-encoded
    device output inside the init/response transcript templates — protocol
    facts the caller (SecureMessaging) computes from its canonical-JSON
    layout; jit keys on them, so one facade serves one protocol layout.

    Fallback (armed when BOTH cpu twins are given): the same step composed
    from per-op cpu calls — verify, kem op, host-side hex render into the
    template, sign — producing wire-identical bytes, so a tripped breaker
    degrades to cpu per-op work instead of failing handshakes.  A missing
    capability never reaches this class: registry.get_fused returns None
    and SecureMessaging stays on the per-op queues entirely.

    Attacker-controlled fields (peer signature key, incoming signature) are
    length-checked per item and fail as ``ok=False`` — matching the verify
    contract — while malformed LOCAL operands (own secret key, template)
    raise, matching the per-op queues.
    """

    def __init__(self, fused, pk_off: int, ct_off: int, max_batch: int = 4096,
                 max_wait_ms: float = 2.0, fallback_kem=None, fallback_sig=None,
                 breaker: Breaker | None = None, cooloff_s: float | None = None,
                 bucket_floor: int = 1, scheduler=None,
                 lane_capacity: dict[int, int] | None = None, **degrade_opts):
        self.fused = fused
        self.name = fused.name
        self.pk_off = pk_off
        self.ct_off = ct_off
        self.bucket_floor = min(_next_pow2(max(1, bucket_floor)), max_batch)
        self.scheduler = scheduler
        #: cost ledger (obs/cost.py): warmup compile attribution
        self.cost = None
        self.breaker = _facade_breaker(breaker, cooloff_s, scheduler)
        self.fallback_kem = fallback_kem
        self.fallback_sig = fallback_sig
        have_fb = fallback_kem is not None and fallback_sig is not None
        self._kg, self._enc, self._dec = (
            OpQueue(batch_fn, max_batch, max_wait_ms,
                    fallback_fn=(fb if have_fb else None),
                    breaker=None if scheduler is not None else self.breaker,
                    bucket_floor=self.bucket_floor, scheduler=scheduler,
                    lane_capacity=lane_capacity,
                    label=f"{fused.name}.{op}", **degrade_opts)
            for batch_fn, fb, op in (
                (self._kg_batch, self._kg_fallback, "keygen_sign"),
                (self._enc_batch, self._enc_fallback, "encaps_verify_sign"),
                (self._dec_batch, self._dec_fallback, "decaps_verify_sign"),
            )
        )

    # -- validity (shared by device + fallback paths) -----------------------

    def _kg_valid(self, it) -> bool:
        sk, tmpl = it
        return (
            len(sk) == self.fused.sig.secret_key_len
            and self.pk_off + 2 * self.fused.kem.public_key_len <= len(tmpl)
            <= self.fused.init_template_len
        )

    def _enc_valid(self, it) -> bool:
        peer_pk, peer_sig_pk, _msg_in, sig_in, sk, tmpl = it
        return (
            len(peer_pk) == self.fused.kem.public_key_len
            and len(peer_sig_pk) == self.fused.sig.public_key_len
            and len(sig_in) == self.fused.sig.signature_len
            and len(sk) == self.fused.sig.secret_key_len
            and self.ct_off + 2 * self.fused.kem.ciphertext_len <= len(tmpl)
            <= self.fused.resp_template_len
        )

    def _dec_valid(self, it) -> bool:
        kem_sk, ct, peer_sig_pk, _msg_in, sig_in, sk, _msg_out = it
        return (
            len(kem_sk) == self.fused.kem.secret_key_len
            and len(ct) == self.fused.kem.ciphertext_len
            and len(peer_sig_pk) == self.fused.sig.public_key_len
            and len(sig_in) == self.fused.sig.signature_len
            and len(sk) == self.fused.sig.secret_key_len
        )

    @staticmethod
    def _render(tmpl: bytes, payload: bytes, off: int) -> bytes:
        """Host-side twin of the device hex-insert (fused.mlkem_mldsa)."""
        return tmpl[:off] + payload.hex().encode() + tmpl[off + 2 * len(payload):]

    # -- device batch fns ---------------------------------------------------

    def _kg_batch(self, items):
        def dispatch(valid, tgt):
            sks = _pad_rows(
                np.stack([np.frombuffer(sk, np.uint8) for sk, _ in valid]), tgt
            )
            tmpls = [t for _, t in valid] + [valid[-1][1]] * (tgt - len(valid))
            pks, ksks, sigs = self.fused.keygen_sign_batch(sks, tmpls, self.pk_off)
            return list(zip((bytes(p) for p in pks), (bytes(k) for k in ksks), sigs))

        return _run_valid(
            items, self._kg_valid, dispatch,
            lambda: ValueError("bad secret-key/template length"),
            self.bucket_floor,
        )

    def _enc_batch(self, items):
        def dispatch(valid, tgt):
            pad = tgt - len(valid)
            pks = _pad_rows(
                np.stack([np.frombuffer(it[0], np.uint8) for it in valid]), tgt
            )
            spks = _pad_rows(
                np.stack([np.frombuffer(it[1], np.uint8) for it in valid]), tgt
            )
            msgs = [it[2] for it in valid] + [valid[-1][2]] * pad
            sigs_in = [it[3] for it in valid] + [valid[-1][3]] * pad
            sks = _pad_rows(
                np.stack([np.frombuffer(it[4], np.uint8) for it in valid]), tgt
            )
            tmpls = [it[5] for it in valid] + [valid[-1][5]] * pad
            oks, cts, sss, sigs = self.fused.encaps_verify_sign_batch(
                pks, spks, msgs, sigs_in, sks, tmpls, self.ct_off
            )
            return [
                (bool(ok), bytes(ct), bytes(ss), sig)
                for ok, ct, ss, sig in zip(oks, cts, sss, sigs)
            ]

        return _run_valid(
            items, self._enc_valid, dispatch,
            lambda: (False, b"", b"", b""),  # verify contract: malformed -> False
            self.bucket_floor,
        )

    def _dec_batch(self, items):
        def dispatch(valid, tgt):
            pad = tgt - len(valid)
            ksks = _pad_rows(
                np.stack([np.frombuffer(it[0], np.uint8) for it in valid]), tgt
            )
            cts = _pad_rows(
                np.stack([np.frombuffer(it[1], np.uint8) for it in valid]), tgt
            )
            spks = _pad_rows(
                np.stack([np.frombuffer(it[2], np.uint8) for it in valid]), tgt
            )
            msgs = [it[3] for it in valid] + [valid[-1][3]] * pad
            sigs_in = [it[4] for it in valid] + [valid[-1][4]] * pad
            sks = _pad_rows(
                np.stack([np.frombuffer(it[5], np.uint8) for it in valid]), tgt
            )
            msgs_out = [it[6] for it in valid] + [valid[-1][6]] * pad
            oks, sss, sigs = self.fused.decaps_verify_sign_batch(
                ksks, cts, spks, msgs, sigs_in, sks, msgs_out
            )
            return [
                (bool(ok), bytes(ss), sig) for ok, ss, sig in zip(oks, sss, sigs)
            ]

        return _run_valid(
            items, self._dec_valid, dispatch,
            lambda: (False, b"", b""),
            self.bucket_floor,
        )

    # -- cpu per-op fallbacks (wire-identical composition) ------------------

    def _kg_fallback(self, items):
        def dispatch(valid, _tgt):
            out = []
            for sk, tmpl in valid:
                pk, ksk = self.fallback_kem.generate_keypair()
                sig = self.fallback_sig.sign(sk, self._render(tmpl, pk, self.pk_off))
                out.append((pk, ksk, sig))
            return out

        return _run_valid(
            items, self._kg_valid, dispatch,
            lambda: ValueError("bad secret-key/template length"), 1,
        )

    def _enc_fallback(self, items):
        def dispatch(valid, _tgt):
            out = []
            for peer_pk, peer_sig_pk, msg_in, sig_in, sk, tmpl in valid:
                if not self.fallback_sig.verify(peer_sig_pk, msg_in, sig_in):
                    out.append((False, b"", b"", b""))
                    continue
                ct, ss = self.fallback_kem.encapsulate(peer_pk)
                sig = self.fallback_sig.sign(sk, self._render(tmpl, ct, self.ct_off))
                out.append((True, ct, ss, sig))
            return out

        return _run_valid(
            items, self._enc_valid, dispatch, lambda: (False, b"", b"", b""), 1,
        )

    def _dec_fallback(self, items):
        def dispatch(valid, _tgt):
            out = []
            for kem_sk, ct, peer_sig_pk, msg_in, sig_in, sk, msg_out in valid:
                if not self.fallback_sig.verify(peer_sig_pk, msg_in, sig_in):
                    out.append((False, b"", b""))
                    continue
                ss = self.fallback_kem.decapsulate(kem_sk, ct)
                out.append((True, ss, self.fallback_sig.sign(sk, msg_out)))
            return out

        return _run_valid(
            items, self._dec_valid, dispatch, lambda: (False, b"", b""), 1,
        )

    # -- async surface ------------------------------------------------------

    async def keygen_sign(self, sig_sk: bytes, template: bytes,
                          lane: int = LANE_HANDSHAKE):
        """-> (kem_pk, kem_sk, sig) for the init step, one device trip."""
        return await self._kg.submit((sig_sk, template), lane)

    async def encaps_verify_sign(self, peer_pk: bytes, peer_sig_pk: bytes,
                                 msg_in: bytes, sig_in: bytes, sig_sk: bytes,
                                 template: bytes, lane: int = LANE_HANDSHAKE):
        """-> (ok, ct, shared_secret, sig) for the response step."""
        return await self._enc.submit(
            (peer_pk, peer_sig_pk, msg_in, sig_in, sig_sk, template), lane
        )

    async def decaps_verify_sign(self, kem_sk: bytes, ct: bytes,
                                 peer_sig_pk: bytes, msg_in: bytes,
                                 sig_in: bytes, sig_sk: bytes, msg_out: bytes,
                                 lane: int = LANE_HANDSHAKE):
        """-> (ok, shared_secret, sig) for the confirm step."""
        return await self._dec.submit(
            (kem_sk, ct, peer_sig_pk, msg_in, sig_in, sig_sk, msg_out), lane
        )

    def warmup(self, sizes: tuple[int, ...] = (1,)) -> None:
        """Compile the composite programs at the LIVE offsets (jit keys on
        them) for the given pow2 buckets and mark those buckets warm.
        Sizes are raised to the facade's bucket floor FIRST — the fused
        capability compiles exactly the shapes it is handed, and live
        flushes pad to the floor, so compiling un-raised sizes would mark
        buckets warm that were never compiled.  Under a scheduler the
        composite programs compile on every shard before marking."""
        buckets = sorted({max(self.bucket_floor, _next_pow2(n)) for n in sizes})
        for shard_idx, placement in _shard_placements(self.scheduler):
            with placement:
                for b in buckets:
                    # per-bucket calls so each compile's wall seconds can
                    # be attributed individually (the sweep compiles the
                    # same shapes either way)
                    t0 = time.perf_counter()
                    self.fused.warmup((b,), pk_off=self.pk_off,
                                      ct_off=self.ct_off)
                    if self.cost is not None:
                        self.cost.compile_event(
                            self.name, b, time.perf_counter() - t0,
                            where="warmup", shard=shard_idx)
        for q in (self._kg, self._enc, self._dec):
            for b in buckets:
                q.mark_warm(b)  # runs on the warmup thread: locked handoff

    def stats(self) -> dict[str, Any]:
        return {
            "keygen_sign": self._kg.stats.as_dict(),
            "encaps_verify_sign": self._enc.stats.as_dict(),
            "decaps_verify_sign": self._dec.stats.as_dict(),
        }
