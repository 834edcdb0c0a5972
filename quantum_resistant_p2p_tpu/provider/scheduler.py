"""Device-program scheduler with an explicit placement axis — the pod-scale
sharded crypto plane's control tier (ROADMAP item 1).

Before this module the batching stack had three coordinates but no axis
tying them together: ``OpQueue`` decided WHEN a batch dispatches, the
``opcache`` decided WHAT device state a program reuses, and the breaker
decided WHETHER the device path is trusted — all implicitly pinned to one
chip (every production dispatch landed on device 0 even with 8 reachable,
``MULTICHIP_r03.json``).  The scheduler adds the missing coordinate:
WHERE.  Every device program now runs against a :class:`Shard` — one slot
of a 1-D placement axis over the visible accelerators — chosen per flush
by a load-aware, health-aware policy.

Sharding model
--------------
Handshake crypto is embarrassingly parallel, so the two production paths
split cleanly (docs/sharding.md):

* **Large-batch raw-ops path** — a single big batch is partitioned ACROSS
  the mesh via ``shard_map`` (``provider.base.mesh_dispatch``,
  the ``devices=`` knob on providers).  One program, N chips, zero
  hot-path collectives.
* **Latency-sensitive handshake path** — many small queue flushes are
  each placed WHOLE on one shard (``jax.default_device`` inside the
  dispatch worker), so concurrent flushes from independent handshakes run
  on different chips in parallel.  Program replicas compile per shard
  (the warmup loops the shards); the opcache partitions per shard
  (``opcache.shard_scope``) so device-resident operand state never
  crosses chips.

Isolation: each shard owns its own :class:`provider.batched.Breaker`
(with its own device/warmup executors), so a sick device quarantines ONE
shard while its siblings keep serving — the placement policy routes
around open/quarantined shards and routes a canary probe back when a
cool-off expires, running the PR-3 heal cycle per shard.

Degradation: ``shards=1`` (the default everywhere) is a single logical
shard with no device pinned — bit-for-bit the pre-scheduler behavior,
pinned by metrics-parity tests.  A request for more physical shards than
there are devices raises; tests that want logical slots pass
``devices=[None, ...]`` explicitly.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable

from ..obs import flight as obs_flight
from .batched import Breaker, CoalescingHub


def select_slot(slots):
    """The placement policy, shared by BOTH placement levels (ROADMAP
    item 1's two-level lift): the local shard axis
    (:class:`DeviceProgramScheduler` picking a chip for a flush) and the
    fleet's process axis (:class:`fleet.manager.GatewayFleet` picking the
    gateway that receives the next unit of work — a canary probe or a
    rebalance placement).  A *slot* is anything with ``breaker`` /
    ``inflight`` / ``index`` — :class:`Shard` and
    :class:`fleet.manager.GatewayMember` both qualify, which is what
    makes placement, quarantine and rebalance ONE policy at both scopes:

    1. a probe-eligible slot (breaker open past its cool-off, or
       half-open with no canary in flight) wins first — healing requires
       routing exactly one unit of work back to it;
    2. otherwise the least-loaded CLOSED slot (tie → lowest index);
    3. otherwise (nothing healthy) the least-loaded non-quarantined slot
       — its breaker claim then degrades the work explicitly, exactly
       like the single-device stack's fallback.

    Deterministic given the load pattern; returns None only for an empty
    slot list.
    """
    slots = list(slots)
    if not slots:
        return None
    probe = [s for s in slots if s.breaker.probe_ready()]
    if probe:
        return min(probe, key=lambda s: (s.inflight, s.index))
    closed = [s for s in slots if s.breaker.state == "closed"]
    pool = closed or [s for s in slots if s.breaker.state != "quarantined"]
    return min(pool or slots, key=lambda s: (s.inflight, s.index))


def _resolve_devices(n: int) -> list[Any]:
    """First ``n`` visible devices (n == -1: all).  A physical shard count
    that is not there raises: placing flushes on logical slots would serve
    a request for N chips from one."""
    from ..parallel.mesh import shard_devices

    return shard_devices(None if n < 0 else n)


class Shard:
    """One slot of the placement axis: a device (or a logical slot), its
    breaker, and its load gauge.

    ``run_placed(fn, items)`` is the placement boundary: it runs one
    device-program callable ON the current (worker) thread under this
    shard's placement context — ``jax.default_device`` pins uncommitted
    operands and the computation to the shard's chip, and
    ``opcache.shard_scope`` namespaces device-resident operand state so a
    pytree cached on chip ``i`` is never fed to a program on chip ``j``.
    Placement changes only WHERE a program runs, never what it computes:
    sharded results are bit-exact vs the single-device path
    (tests/test_scheduler.py).
    """

    def __init__(self, index: int, device: Any = None,
                 breaker: Breaker | None = None):
        self.index = index
        self.device = device
        self.label = f"shard{index}"
        self.breaker = breaker if breaker is not None else Breaker()
        #: rides in the breaker's flight-recorder events so a dump tells
        #: WHICH shard opened/quarantined, not just that one did
        self.breaker.label = self.label
        #: guards the load gauge: place()/done() run on the event loop,
        #: run_placed on the dispatch workers (qrflow cross-thread-state)
        self._lock = threading.Lock()
        self.inflight = 0
        self.dispatches = 0
        # labeled obs instruments (attached by the scheduler when it is
        # given a registry; None otherwise — recording stays optional)
        self._ctr_dispatches = None
        self._hist_latency = None
        #: cost-ledger feed (obs/cost.py): per-shard placed-program
        #: seconds, attached via DeviceProgramScheduler.attach_cost
        self._cost = None

    @contextlib.contextmanager
    def placement(self):
        """Enter this shard's placement context (on the dispatching
        thread).  Logical shards (``device is None``) scope only the
        opcache — the single-device behavior stays untouched."""
        from .opcache import shard_scope

        with shard_scope(self.index):
            if self.device is None:
                yield
            else:
                import jax

                with jax.default_device(self.device):
                    yield

    def run_placed(self, fn: Callable[[list[Any]], list[Any]],
                   items: list[Any]) -> list[Any]:
        """Run one device-program callable under this shard's placement.
        Failures propagate to the caller, which records them to THIS
        shard's breaker (per-shard quarantine, not fleet-wide)."""
        t0 = time.perf_counter()
        with self.placement():
            out = fn(items)
        dt = time.perf_counter() - t0
        with self._lock:
            self.dispatches += 1
        if self._ctr_dispatches is not None:
            self._ctr_dispatches.inc()
        if self._hist_latency is not None:
            self._hist_latency.record(dt)
        if self._cost is not None:
            # per-shard device-seconds (obs/cost.py): the chip-level half
            # of the cost ledger's device-time accounting
            self._cost.shard_device_time(self.index, dt)
        return out

    def snapshot(self) -> dict[str, Any]:
        b = self.breaker
        with self._lock:
            inflight, dispatches = self.inflight, self.dispatches
        return {
            "shard": self.index,
            "device": str(self.device) if self.device is not None else None,
            "inflight": inflight,
            "dispatches": dispatches,
            "breaker_state": b.state,
            "breaker_opens": b.opens,
            "breaker_closes": b.closes,
            "device_trips": b.device_trips,
            "fallback_trips": b.fallback_trips,
        }


class DeviceProgramScheduler(CoalescingHub):
    """Places device-program flushes onto shards; owns the shard set.

    Placement policy (deterministic given the load pattern — pinned by
    tests):

    1. a probe-eligible shard (breaker open past its cool-off, or
       half-open with no canary in flight) wins first — healing a shard
       requires routing exactly one real flush back to it;
    2. otherwise the least-loaded CLOSED shard (tie → lowest index);
    3. otherwise (no healthy shard) the least-loaded non-quarantined
       shard — its breaker claim then serves the flush from the cpu
       fallback, degrading exactly like the single-device stack.

    The scheduler is also the coalescing hub for the queues it serves
    (:class:`provider.batched.CoalescingHub`, the machinery a
    ``Breaker`` provides for single-breaker stacks): sibling queues
    flush in one scheduling window, and each coalesced flush is then
    PLACED independently — coalesced KEM and SIG batches can run on
    different chips in parallel.
    """

    def __init__(self, shards: int = 1, cooloff_s: float = 30.0,
                 cooloff_max_s: float = 480.0, registry=None,
                 devices: list[Any] | None = None):
        if shards == 0:
            shards = 1
        if devices is None:
            # one logical shard needs no device lookup (and must not pull
            # in jax on minimal images); a real axis resolves devices
            devices = [None] if shards == 1 else _resolve_devices(shards)
        self.shards = [
            Shard(i, dev, Breaker(cooloff_s, cooloff_max_s))
            for i, dev in enumerate(devices)
        ]
        self._lock = threading.Lock()
        self._last_healthy: frozenset[int] = frozenset(
            s.index for s in self.shards
        )
        self._init_coalescer()
        if registry is not None:
            self.attach_registry(registry)

    # -- observability --------------------------------------------------------

    def attach_registry(self, registry) -> None:
        """Create the per-shard labeled children (obs/metrics.py): a
        ``shard=<i>`` child per instrument, so one Prometheus scrape (or
        JSON snapshot) breaks dispatch counts/latency down by chip."""
        ctr = registry.counter(
            "shard_dispatches", "device programs run, by placement shard")
        hist = registry.histogram(
            "shard_dispatch_latency", "placed device-program latency (s)")
        gauge = registry.gauge(
            "shard_inflight", "flushes currently placed, by shard")
        for s in self.shards:
            s._ctr_dispatches = ctr.labels(shard=s.index)
            s._hist_latency = hist.labels(shard=s.index)
            child = gauge.labels(shard=s.index)
            child.set_fn(lambda s=s: s.inflight)

    def attach_cost(self, ledger) -> None:
        """Feed per-shard placed-program seconds into a
        :class:`obs.cost.CostLedger` (the engine attaches its ledger)."""
        for s in self.shards:
            s._cost = ledger

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    # -- placement ------------------------------------------------------------

    def place(self) -> Shard:
        """Claim the next flush's shard (pair with :meth:`done`) — the
        shared two-level policy (:func:`select_slot`) applied at the
        local-shard scope."""
        with self._lock:
            chosen = select_slot(self.shards)
            with chosen._lock:
                chosen.inflight += 1
            healthy = frozenset(
                s.index for s in self.shards if s.breaker.state == "closed"
            )
            if healthy != self._last_healthy:
                # the routing table just changed: a flight dump must show
                # WHEN traffic moved off (or back onto) a shard
                obs_flight.record(
                    "shard_rebalance",
                    healthy=sorted(healthy),
                    avoided=sorted(set(range(len(self.shards))) - healthy),
                    placed_on=chosen.index,
                )
                self._last_healthy = healthy
            return chosen

    def done(self, shard: Shard) -> None:
        with shard._lock:
            shard.inflight -= 1

    # -- fleet operations -----------------------------------------------------

    def quarantine_all(self, why: str) -> None:
        """Health-gate verdicts are about the device PROGRAMS (wrong
        answers), not one chip — every shard runs the same programs, so a
        correctness failure pins the whole axis onto the cpu fallback."""
        for s in self.shards:
            s.breaker.quarantine(why)

    def total_trips(self) -> int:
        """Serial dispatch steps (device + fallback) across every shard —
        the per-handshake SLO currency (docs/dispatch_budget.md) summed
        over the placement axis."""
        return sum(s.breaker.device_trips + s.breaker.fallback_trips
                   for s in self.shards)

    def warmable_shards(self) -> list[Shard]:
        """The shards a warm sweep should compile on: CLOSED breakers
        only.  A sick shard's device may hang the compile — and the warm
        runs on the single nice-19 warmup thread, so one hung shard would
        block warm-marking for the whole plane (the exact fleet-wide
        coupling per-shard breakers exist to prevent).  A shard skipped
        here cold-compiles inside its first placed flush after healing;
        the slow-trip machinery absorbs that (degrade, re-probe) — a
        bounded per-shard cost, never a fleet-wide stall."""
        return [s for s in self.shards if s.breaker.state == "closed"]

    def stats(self) -> dict[str, Any]:
        snaps = [s.snapshot() for s in self.shards]
        served = sum(s["dispatches"] for s in snaps)
        return {
            "n_shards": len(self.shards),
            "placement": "least-inflight, probe-first, quarantine-aware",
            "dispatches": served,
            "shards": snaps,
        }
