"""Pod-scale sharded crypto plane: the device-program scheduler's placement
axis (provider/scheduler.py) and its integration with the batching stack.

Covered here (ISSUE 6 acceptance):

* mesh-of-1 degrades to exactly the single-device behavior — same queue
  stats, same ``SecureMessaging.metrics()`` key layout;
* placement is load-aware and DETERMINISTIC under a seeded load pattern
  (least-inflight, lowest-index tie-break, probe-first);
* per-shard breaker isolation: killing ONE shard's device via ``faults/``
  quarantines that shard only — the others keep serving on their own
  breakers with ``device_served_fraction >= 0.9``, and a seeded chaos run
  over the full protocol engine completes with 0 failed handshakes;
* the opcache partitions per shard (device state never crosses chips);
* placed jitted programs are BIT-EXACT vs the single-device path,
  including the fused handshake step (the conftest pins an 8-device
  virtual CPU platform, so real per-device placement is exercised);
* obs integration: ``shard=<i>`` labeled metric children, shard attrs on
  dispatch spans, flight events for quarantine/rebalance.
"""

import asyncio
import hashlib
import hmac
import os
import time

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.app import messaging as messaging_mod
from quantum_resistant_p2p_tpu.app.messaging import SecureMessaging
from quantum_resistant_p2p_tpu.faults import FaultPlan, FaultRule
from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode
from quantum_resistant_p2p_tpu.obs import flight as obs_flight
from quantum_resistant_p2p_tpu.obs import trace as obs_trace
from quantum_resistant_p2p_tpu.obs.metrics import Registry
from quantum_resistant_p2p_tpu.provider.base import (KeyExchangeAlgorithm,
                                                     SignatureAlgorithm,
                                                     SymmetricAlgorithm)
from quantum_resistant_p2p_tpu.provider.batched import Breaker, OpQueue
from quantum_resistant_p2p_tpu.provider.opcache import (DeviceOperandCache,
                                                        current_shard,
                                                        shard_scope)
from quantum_resistant_p2p_tpu.provider.registry import (register_kem,
                                                         register_signature)
from quantum_resistant_p2p_tpu.provider.scheduler import (
    DeviceProgramScheduler, Shard)

# -- stdlib toy algorithms (the faults-suite pattern: the REAL scheduler/
# queue/breaker/engine stack runs, the crypto inside is a hash toy so a
# sharded chaos run costs milliseconds) --------------------------------------


def _keystream(key: bytes, nonce: bytes, n: int) -> bytes:
    out = b""
    ctr = 0
    while len(out) < n:
        out += hashlib.sha256(key + nonce + ctr.to_bytes(8, "big")).digest()
        ctr += 1
    return out[:n]


class ToyAEAD(SymmetricAlgorithm):
    name = "TOYS-AEAD"
    display_name = "TOYS-AEAD"
    key_size = 32
    nonce_size = 16

    def encrypt(self, key, plaintext, associated_data=None):
        nonce = os.urandom(self.nonce_size)
        ct = bytes(a ^ b for a, b in
                   zip(plaintext, _keystream(key, nonce, len(plaintext))))
        tag = hmac.new(key, nonce + ct + (associated_data or b""),
                       hashlib.sha256).digest()
        return nonce + ct + tag

    def decrypt(self, key, data, associated_data=None):
        if len(data) < self.nonce_size + 32:
            raise ValueError("ciphertext too short")
        nonce, ct, tag = (data[: self.nonce_size], data[self.nonce_size:-32],
                          data[-32:])
        want = hmac.new(key, nonce + ct + (associated_data or b""),
                        hashlib.sha256).digest()
        if not hmac.compare_digest(tag, want):
            raise ValueError("authentication failed")
        return bytes(a ^ b for a, b in zip(ct, _keystream(key, nonce, len(ct))))


class ToyKEM(KeyExchangeAlgorithm):
    name = "TOYS-KEM"
    display_name = "TOYS-KEM"
    public_key_len = 32
    secret_key_len = 32
    ciphertext_len = 32
    shared_secret_len = 32

    def __init__(self, backend="cpu"):
        self.backend = backend

    def generate_keypair(self):
        sk = os.urandom(32)
        return hashlib.sha256(b"pk" + sk).digest(), sk

    def encapsulate(self, public_key):
        ct = os.urandom(32)
        return ct, hashlib.sha256(public_key + ct).digest()

    def decapsulate(self, secret_key, ciphertext):
        pk = hashlib.sha256(b"pk" + secret_key).digest()
        return hashlib.sha256(pk + ciphertext).digest()


class ToySig(SignatureAlgorithm):
    name = "TOYS-SIG"
    display_name = "TOYS-SIG"
    public_key_len = 32
    secret_key_len = 32
    signature_len = 32

    def __init__(self, backend="cpu"):
        self.backend = backend

    def generate_keypair(self):
        sk = os.urandom(32)
        return hashlib.sha256(b"pk" + sk).digest(), sk

    def sign(self, secret_key, message):
        pk = hashlib.sha256(b"pk" + secret_key).digest()
        return hashlib.sha256(b"sig" + pk + message).digest()

    def verify(self, public_key, message, signature):
        return hmac.compare_digest(
            signature, hashlib.sha256(b"sig" + public_key + message).digest()
        )


register_kem("TOYS-KEM", lambda backend, devices=0: ToyKEM(backend),
             ("cpu", "tpu"))
register_signature("TOYS-SIG", lambda backend, devices=0: ToySig(backend),
                   ("cpu", "tpu"))


def _logical(n: int, cooloff_s: float = 60.0) -> DeviceProgramScheduler:
    """An n-shard scheduler with no physical devices: placement, breakers
    and quarantine behave exactly as on hardware, minus the device pin."""
    return DeviceProgramScheduler(shards=n, cooloff_s=cooloff_s,
                                  devices=[None] * n)


# -- placement policy ---------------------------------------------------------


def test_placement_least_loaded_deterministic():
    """The policy is a pure function of the load pattern: least-inflight,
    lowest-index tie-break — the same seeded claim/release sequence yields
    the same placements, run after run."""

    def drive():
        sched = _logical(4)
        seq = []
        held = []
        for _ in range(8):  # fill: round-robin by tie-break
            sh = sched.place()
            held.append(sh)
            seq.append(sh.index)
        # release shard 2's claims: it becomes least-loaded
        for sh in list(held):
            if sh.index == 2:
                sched.done(sh)
                held.remove(sh)
        for _ in range(3):
            sh = sched.place()
            held.append(sh)
            seq.append(sh.index)
        return seq

    first, second = drive(), drive()
    assert first == second
    assert first[:8] == [0, 1, 2, 3, 0, 1, 2, 3]
    # shard 2 drained to 0 inflight: it absorbs the next two (0->1->2
    # inflight), then ties with everyone at 2 and index 0 wins
    assert first[8:] == [2, 2, 0]


def test_placement_avoids_open_shard_then_probes_it_back():
    sched = _logical(3, cooloff_s=0.05)
    sched.shards[1].breaker.trip()
    assert sched.shards[1].breaker.state == "open"
    placed = [sched.place() for _ in range(4)]
    for sh in placed:
        sched.done(sh)
    assert all(sh.index != 1 for sh in placed)
    # cool-off expired: the policy must route ONE flush back (probe-first)
    # or the shard could never heal
    time.sleep(0.06)
    probe = sched.place()
    assert probe.index == 1
    assert probe.breaker.acquire_dispatch() == "probe"
    probe.breaker.record_success("probe")
    sched.done(probe)
    assert sched.shards[1].breaker.state == "closed"


def test_placement_skips_quarantined_shard():
    sched = _logical(2)
    sched.shards[0].breaker.quarantine("bad device")
    assert all(sched.place().index == 1 for _ in range(3))


# -- mesh-of-1 degradation ----------------------------------------------------


def test_single_shard_queue_matches_legacy_behavior():
    """A 1-shard scheduler IS the old one-breaker world: same results,
    same counters, same stats layout."""

    def batch_fn(items):
        return [x * 3 for x in items]

    async def drive(queue):
        return await asyncio.gather(*(queue.submit(i) for i in range(9)))

    async def main():
        legacy = OpQueue(batch_fn, max_batch=4, max_wait_ms=1.0,
                         fallback_fn=batch_fn, breaker=Breaker(cooloff_s=60.0))
        sharded = OpQueue(batch_fn, max_batch=4, max_wait_ms=1.0,
                          fallback_fn=batch_fn, scheduler=_logical(1))
        for q in (legacy, sharded):
            q._warm_buckets.update({1, 2, 4})
        assert await drive(legacy) == await drive(sharded)
        a, b = legacy.stats.as_dict(), sharded.stats.as_dict()
        assert set(a) == set(b)
        for key in ("ops", "flushes", "fallback_ops", "device_trips",
                    "breaker_trips", "device_served_fraction"):
            assert a[key] == b[key], key

    asyncio.run(main())


def test_metrics_key_parity_across_shard_counts(monkeypatch):
    """metrics() exposes the same key layout at 0, 1 and 2 shards — the
    scheduler is additive, never a reshaping, of the legacy contract
    (which tests/test_obs.py pins against the pre-obs layout)."""
    monkeypatch.setattr(SecureMessaging, "_spawn_warmup",
                        lambda self, **kw: None)

    def engine(shards):
        from quantum_resistant_p2p_tpu.provider import get_kem, get_signature

        node = P2PNode(node_id=f"par{shards}", host="127.0.0.1", port=0)
        return SecureMessaging(
            node, symmetric=ToyAEAD(), kem=get_kem("TOYS-KEM", "tpu"),
            signature=get_signature("TOYS-SIG", "tpu"), use_batching=True,
            shard_devices=shards, sig_keypair=(b"p", b"s"),
        )

    m0, m1, m2 = (engine(n).metrics() for n in (0, 1, 2))
    assert set(m0) == set(m1) == set(m2)
    assert m0["shards"]["n_shards"] == 1
    assert m2["shards"]["n_shards"] == 2
    assert {s["shard"] for s in m2["shards"]["shards"]} == {0, 1}


# -- opcache partitioning -----------------------------------------------------


def test_opcache_partitions_per_shard_scope():
    cache = DeviceOperandCache(capacity=8)
    key = b"k" * 32
    with shard_scope(0):
        assert current_shard() == 0
        cache.put("ek", key, "state-on-chip-0")
        assert cache.lookup("ek", key) == "state-on-chip-0"
    with shard_scope(1):
        # chip 1 must never be handed chip 0's device arrays
        assert cache.lookup("ek", key) is None
        cache.put("ek", key, "state-on-chip-1")
    with shard_scope(0):
        assert cache.lookup("ek", key) == "state-on-chip-0"
    assert current_shard() == 0  # scope restored (default shard)
    assert len(cache) == 2


# -- per-shard fault isolation ------------------------------------------------


def test_killed_shard_quarantines_one_shard_others_serve(monkeypatch):
    """ISSUE 6 acceptance (facade level): kill ONE shard's device via
    faults/ — that shard's breaker opens, placement routes around it, and
    the run finishes >= 90% device-served with the other shard closed."""

    def batch_fn(items):
        time.sleep(0.005)  # overlap flushes so both shards take traffic
        return [x + 100 for x in items]

    async def main():
        sched = _logical(2)
        q = OpQueue(batch_fn, max_batch=2, max_wait_ms=0.5,
                    fallback_fn=lambda items: [x + 100 for x in items],
                    scheduler=sched, label="toy.op")
        q._warm_buckets.update({1, 2})
        plan = FaultPlan(77, [
            FaultRule("device.dispatch", "raise",
                      match={"op": "toy.op", "shard": 1}, nth=1, times=99),
        ])
        results = []
        with plan.activate():
            for _ in range(10):  # waves of concurrent flushes
                results += await asyncio.gather(
                    *(q.submit(i) for i in range(8)))
        assert results == [i + 100 for i in range(8)] * 10  # nothing failed
        assert plan.injected, "shard 1 never took a dispatch"
        assert all(e["shard"] == 1 for e in plan.injected)
        st = q.stats.as_dict()
        assert st["device_served_fraction"] >= 0.9, st
        assert sched.shards[0].breaker.state == "closed"
        assert sched.shards[1].breaker.state == "open"

    asyncio.run(main())


@pytest.fixture
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


@pytest.fixture(autouse=True)
def fast_timeout(monkeypatch):
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 1.5)
    monkeypatch.setattr(messaging_mod, "KE_RETRY_BACKOFF_S", 0.05)


async def _pair(**kwargs):
    from quantum_resistant_p2p_tpu.provider import get_kem, get_signature

    a_node = P2PNode(node_id="alice", host="127.0.0.1", port=0)
    b_node = P2PNode(node_id="bob", host="127.0.0.1", port=0)
    await a_node.start()
    await b_node.start()
    kw = dict(kem=get_kem("TOYS-KEM", "tpu"),
              signature=get_signature("TOYS-SIG", "tpu"),
              use_batching=True, max_batch=8, max_wait_ms=1.0)
    kw.update(kwargs)
    a = SecureMessaging(a_node, symmetric=ToyAEAD(), **kw)
    b = SecureMessaging(b_node, symmetric=ToyAEAD(), **kw)
    assert await a_node.connect_to_peer("127.0.0.1", b_node.port) == "bob"
    for _ in range(100):
        if b_node.is_connected("alice"):
            break
        await asyncio.sleep(0.01)
    return a, b


def test_sharded_chaos_run_zero_failed_handshakes(run, monkeypatch):
    """ISSUE 6 acceptance (engine level): a seeded chaos plan permanently
    kills shard 1's device on both sides of a 2-shard plane.  12
    handshakes complete with 0 failures; the REMAINING shard's breaker
    ends closed on both engines and the run stays >= 90% device-served
    (the sick shard's flushes degrade to the cpu fallback; its siblings
    never do)."""
    monkeypatch.setenv("QRP2P_HEALTH_GATE", "0")

    async def main():
        # max_batch=1: every op flushes immediately, so concurrent ops are
        # concurrent flushes — the load pattern that spreads placements
        a, b = await _pair(shard_devices=2, breaker_cooloff_s=300.0,
                           max_batch=1)
        await a.wait_ready()
        await b.wait_ready()
        plan = FaultPlan(4242, [
            FaultRule("device.dispatch", "raise", match={"shard": 1},
                      nth=1, times=999),
        ])
        failures = 0
        with plan.activate():
            # a concurrent burst through the plane: placement spreads the
            # flushes across both shards, so the kill rule provably lands
            # on shard 1 before the handshake window
            await asyncio.gather(
                *(a._bkem.generate_keypair() for _ in range(8)))
            for _i in range(12):
                for side, peer in ((a, "bob"), (b, "alice")):
                    side.shared_keys.pop(peer, None)
                    side.raw_secrets.pop(peer, None)
                    side.ke_state[peer] = messaging_mod.KeyExchangeState.NONE
                if not await a.initiate_key_exchange("bob"):
                    failures += 1
        ma, mb = a.metrics(), b.metrics()
        await a.node.stop()
        await b.node.stop()
        return failures, plan, ma, mb, a, b

    failures, plan, ma, mb, a, b = run(main())
    assert failures == 0
    # the kill rule fired (coalesced sibling flushes overlap, so shard 1
    # takes traffic early) and hit ONLY shard 1
    assert plan.injected and all(e["shard"] == 1 for e in plan.injected)
    total = fb = 0
    for m in (ma, mb):
        for fam in ("kem_queue", "sig_queue"):
            for q in m[fam].values():
                total += q["ops"]
                fb += q["fallback_ops"]
    assert total and (total - fb) / total >= 0.9
    for eng, m in ((a, ma), (b, mb)):
        per_shard = {s["shard"]: s for s in m["shards"]["shards"]}
        # the sick shard quarantined ALONE: shard 0 kept its device path
        assert per_shard[0]["breaker_state"] == "closed"
        if per_shard[1]["dispatches"]:
            assert per_shard[1]["breaker_state"] == "open"
            # the legacy key reports the WORST shard, so dashboards keyed
            # on it see the degradation even though shard 0 is healthy
            assert m["breaker_state"] == "open"


# -- bit-exactness of placed programs (real 8-device virtual platform) --------


def test_missing_physical_shards_raise():
    """Asking for more chips than the platform has is an error, never a
    quiet fall back to logical slots (conftest pins 8 virtual devices)."""
    with pytest.raises(RuntimeError, match="need 16 devices, have 8"):
        DeviceProgramScheduler(shards=16)


def test_placed_kem_program_bit_exact_vs_default_device():
    """Placement changes WHERE a jitted program runs, never its bits: the
    same ML-KEM-512 keygen seeds yield identical keys on every shard of
    the virtual 8-device mesh."""
    from quantum_resistant_p2p_tpu.kem import mlkem

    sched = DeviceProgramScheduler(shards=4)
    assert [s.device for s in sched.shards].count(None) == 0, \
        "conftest pins an 8-device platform; shards must be physical"
    kg, enc, dec = mlkem.get("ML-KEM-512")
    rng = np.random.default_rng(20260803)
    d, z, m = (rng.integers(0, 256, (2, 32), dtype=np.uint8) for _ in range(3))
    ek_ref, dk_ref = (np.asarray(o) for o in kg(d, z))
    key_ref, ct_ref = (np.asarray(o) for o in enc(ek_ref, m))
    for shard in (sched.shards[1], sched.shards[3]):
        ek_s, dk_s = shard.run_placed(lambda _items: kg(d, z), [])
        assert np.array_equal(np.asarray(ek_s), ek_ref)
        assert np.array_equal(np.asarray(dk_s), dk_ref)
        key_s = shard.run_placed(lambda _items: dec(dk_ref, ct_ref), [])
        assert np.array_equal(np.asarray(key_s), key_ref)


def test_placed_fused_handshake_step_bit_exact():
    """The sharded handshake path vs the single-device fused path: the
    composite keygen+sign program with pinned randomness produces
    byte-identical keys and signatures when placed on another chip."""
    from quantum_resistant_p2p_tpu.provider import get_fused, get_kem, get_signature
    from quantum_resistant_p2p_tpu.provider.fused_providers import init_pk_offset

    kem = get_kem("ML-KEM-512", backend="tpu")
    sig = get_signature("ML-DSA-44", backend="tpu")
    fused = get_fused(kem, sig)
    assert fused is not None
    pk_off = init_pk_offset(kem.name, "AES-256-GCM")
    _spk, ssk = sig.generate_keypair()
    sks = np.frombuffer(ssk, np.uint8)[None]
    tmpl = [b"t" * (pk_off + 2 * kem.public_key_len + 64)]
    rnd = [b"\x07" * 32]

    # pin the host-drawn seeds so both runs dispatch identical operands
    seeds = os.urandom(64)

    def fixed_urandom(n, _s=seeds):
        return (_s * (n // len(_s) + 1))[:n]

    import quantum_resistant_p2p_tpu.provider.fused_providers as fp

    real = fp.os.urandom
    fp.os.urandom = fixed_urandom
    try:
        ek_ref, dk_ref, sig_ref = fused.keygen_sign_batch(sks, tmpl, pk_off,
                                                          rnd=rnd)
        sched = DeviceProgramScheduler(shards=2)
        ek_s, dk_s, sig_s = sched.shards[1].run_placed(
            lambda _items: fused.keygen_sign_batch(sks, tmpl, pk_off, rnd=rnd),
            [],
        )
    finally:
        fp.os.urandom = real
    assert np.array_equal(np.asarray(ek_s), np.asarray(ek_ref))
    assert np.array_equal(np.asarray(dk_s), np.asarray(dk_ref))
    assert [bytes(s) for s in sig_s] == [bytes(s) for s in sig_ref]


# -- obs integration ----------------------------------------------------------


def test_scheduler_labeled_metric_children_and_prometheus():
    reg = Registry(name="shardtest")
    sched = DeviceProgramScheduler(shards=2, devices=[None, None],
                                   registry=reg)
    sched.shards[1].run_placed(lambda items: items, [1, 2])
    snap = reg.snapshot()
    assert snap["counters"]['shard_dispatches{shard="1"}'] == 1
    assert snap["counters"]['shard_dispatches{shard="0"}'] == 0
    assert snap["histograms"]['shard_dispatch_latency{shard="1"}']["count"] == 1
    assert snap["gauges"]['shard_inflight{shard="0"}'] == 0
    prom = reg.to_prometheus()
    assert 'shard="1"' in prom


def test_dispatch_spans_carry_shard_attr():
    async def main():
        sched = _logical(2)
        q = OpQueue(lambda items: items, max_batch=2, max_wait_ms=0.5,
                    fallback_fn=lambda items: items, scheduler=sched,
                    label="toy.span")
        q._warm_buckets.update({1, 2})
        obs_trace.TRACER.reset()
        await asyncio.gather(*(q.submit(i) for i in range(4)))
        spans = obs_trace.TRACER.snapshot()
        flushes = [s for s in spans if s["name"] == "queue.flush"]
        dispatches = [s for s in spans if s["name"] == "device.dispatch"]
        assert flushes and dispatches
        assert all("shard" in s["attrs"] for s in flushes)
        assert all("shard" in s["attrs"] for s in dispatches)

    asyncio.run(main())


def test_flight_events_for_shard_quarantine_and_rebalance():
    sched = _logical(2, cooloff_s=60.0)
    for _ in range(2):
        sched.done(sched.place())  # settle the healthy-set baseline
    sched.shards[1].breaker.trip()  # shard 1 degrades
    sched.done(sched.place())  # placement notices the routing change
    sched.shards[0].breaker.quarantine("test: device computes wrong answers")
    events = obs_flight.RECORDER.snapshot()
    opens = [e for e in events if e["kind"] == "breaker_open"
             and e.get("shard") == "shard1"]
    quar = [e for e in events if e["kind"] == "breaker_quarantined"
            and e.get("shard") == "shard0"]
    rebal = [e for e in events if e["kind"] == "shard_rebalance"]
    assert opens and quar
    assert rebal and rebal[-1]["avoided"] == [1]


def test_quarantine_all_covers_every_shard():
    sched = _logical(3)
    sched.quarantine_all("health gate: wrong answers")
    assert all(s.breaker.state == "quarantined" for s in sched.shards)
    assert sched.total_trips() == 0
