"""obs/ — unified observability subsystem acceptance suite.

Covers: registry semantics (concurrent increments, histogram buckets,
create-or-return), span-context propagation across ``await``/task/thread/
executor hops, Prometheus-text and chrome-trace golden formats, flight-
recorder redaction + byte-reproducible seeded dumps + auto-dump triggers
+ the breaker open→half-open→close story, ``SecureMessaging.metrics()``
key parity with the pre-obs layout, and the end-to-end assert that a
traced warm ML-KEM-768×ML-DSA-65 handshake yields exactly 4
device-dispatch spans (the PR-2 budget, now visible in a flame graph).

Runs on minimal images: the AEAD is the stdlib toy from the faults suite
(no ``cryptography`` wheel needed) and obs/ itself is stdlib-only.
"""

from __future__ import annotations

import asyncio
import hashlib
import hmac
import itertools
import json
import os
import threading
import time

import pytest

from quantum_resistant_p2p_tpu.app import messaging as messaging_mod
from quantum_resistant_p2p_tpu.app.messaging import SecureMessaging
from quantum_resistant_p2p_tpu.faults import FaultPlan, FaultRule
from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode
from quantum_resistant_p2p_tpu.obs import flight as obs_flight
from quantum_resistant_p2p_tpu.obs import metrics as obs_metrics
from quantum_resistant_p2p_tpu.obs import trace as obs_trace
from quantum_resistant_p2p_tpu.obs.flight import FlightRecorder, redact_value
from quantum_resistant_p2p_tpu.obs.metrics import (Counter, Histogram,
                                                   Registry)
from quantum_resistant_p2p_tpu.obs.trace import Tracer, to_chrome_trace
from quantum_resistant_p2p_tpu.provider.base import SymmetricAlgorithm
from quantum_resistant_p2p_tpu.provider.batched import Breaker, OpQueue


@pytest.fixture
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


def _fake_clock(step: float = 1.0):
    counter = itertools.count()
    return lambda: next(counter) * step


# -- registry semantics -------------------------------------------------------


def test_counter_concurrent_increments_lose_nothing():
    c = Counter("ops", "test")
    N_THREADS, N_INCS = 8, 5000

    def hammer():
        for _ in range(N_INCS):
            c.inc()

    threads = [threading.Thread(target=hammer) for _ in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N_THREADS * N_INCS


def test_histogram_buckets_percentiles_and_reset():
    h = Histogram("trips", "test", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (1, 2, 2, 4, 9):
        h.record(v)
    assert h.count == 5 and h.last == 9 and h.total == 18
    assert h.bucket_counts() == {"1": 1, "2": 3, "4": 4, "8": 4, "+Inf": 5}
    assert h.percentile(50) == 2.0      # 3rd of 5 samples lands in le=2
    # the 9 overflows the boundaries: None, never inf (JSON-exportable)
    assert h.percentile(99) is None
    h.reset()
    assert h.count == 0 and h.last is None and h.percentile(50) is None


def test_registry_create_or_return_and_type_conflict():
    r = Registry("t1")
    c1 = r.counter("x", "d")
    assert r.counter("x") is c1  # create-or-return: one instrument per name
    with pytest.raises(TypeError):
        r.gauge("x")
    g = r.gauge("lazy")
    g.set_fn(lambda: 41 + 1)
    snap = r.snapshot()
    assert snap["gauges"]["lazy"] == 42.0
    # a crashing lazy gauge degrades to None (JSON-safe), NaN only in prom
    r.gauge("broken").set_fn(lambda: 1 // 0)
    snap = r.snapshot()
    assert snap["gauges"]["broken"] is None
    json.dumps(snap, allow_nan=False)  # strictly valid JSON, no NaN/Inf
    assert 'qrp2p_broken{registry="t1"} NaN' in r.to_prometheus()
    r.register_collector("ext", lambda: {"nested": {"n": 7}})
    assert r.snapshot()["collected"]["ext"] == {"nested": {"n": 7}}
    h = r.histogram("trips", buckets=(1.0, 2.0))
    assert r.histogram("trips") is h          # None = keep what it has
    with pytest.raises(TypeError):
        r.histogram("trips", buckets=(5.0,))  # explicit mismatch is an error


def test_labeled_children_share_the_family():
    r = Registry("t2")
    c = r.counter("reqs", "test")
    c.labels(op="enc").inc(2)
    c.labels(op="enc").inc()
    c.labels(op="dec").inc()
    snap = r.snapshot()
    assert snap["counters"]['reqs{op="enc"}'] == 3
    assert snap["counters"]['reqs{op="dec"}'] == 1


def test_prometheus_text_golden():
    r = Registry("bench")
    r.counter("ops", "operations").inc(5)
    r.counter("ops").labels(op="enc").inc(2)
    r.gauge("served_fraction", "device-served fraction").set(0.75)
    h = r.histogram("lat_s", "dispatch latency", buckets=(0.1, 1.0))
    h.record(0.05)
    h.record(0.5)
    r.register_collector("queues", lambda: {"kem": {"ops": 3, "state": "ok"}})
    assert r.to_prometheus() == (
        '# HELP qrp2p_ops_total operations\n'
        '# TYPE qrp2p_ops_total counter\n'
        'qrp2p_ops_total{registry="bench"} 5\n'
        'qrp2p_ops_total{registry="bench",op="enc"} 2\n'
        '# HELP qrp2p_served_fraction device-served fraction\n'
        '# TYPE qrp2p_served_fraction gauge\n'
        'qrp2p_served_fraction{registry="bench"} 0.75\n'
        '# HELP qrp2p_lat_s dispatch latency\n'
        '# TYPE qrp2p_lat_s histogram\n'
        'qrp2p_lat_s_bucket{registry="bench",le="0.1"} 1\n'
        'qrp2p_lat_s_bucket{registry="bench",le="1"} 2\n'
        'qrp2p_lat_s_bucket{registry="bench",le="+Inf"} 2\n'
        'qrp2p_lat_s_sum{registry="bench"} 0.55\n'
        'qrp2p_lat_s_count{registry="bench"} 2\n'
        'qrp2p_queues_kem_ops{registry="bench"} 3\n'
    )


def test_profiling_shim_is_gone():
    """PR 5 promised the utils/profiling deprecation shim would be removed
    once nothing imported it; this pins the removal (and that the real
    homes still serve the moved objects)."""
    with pytest.raises(ModuleNotFoundError):
        import quantum_resistant_p2p_tpu.utils.profiling  # noqa: F401
    h = obs_metrics.LatencyHistogram()
    h.record(0.5)
    assert h.summary()["count"] == 1 and h.percentile(50) == 0.5


# -- span propagation ---------------------------------------------------------


def test_span_context_propagates_across_await_and_tasks(run):
    tr = Tracer()

    async def inner():
        with tr.span("child"):
            await asyncio.sleep(0)

    async def main():
        with tr.span("root"):
            await asyncio.get_running_loop().create_task(inner())

    run(main())
    recs = {r["name"]: r for r in tr.snapshot()}
    assert recs["child"]["trace_id"] == recs["root"]["trace_id"]
    assert recs["child"]["parent_id"] == recs["root"]["span_id"]
    assert recs["root"]["parent_id"] is None


def test_span_context_needs_explicit_handoff_across_threads(run):
    """contextvars do not cross run_in_executor / Thread — the captured
    ``current()`` handed as ``parent=`` is the supported handoff (the
    warmup-thread / device-executor edges)."""
    tr = Tracer()
    seen: dict[str, object] = {}

    def fresh_thread():
        seen["thread_ctx"] = obs_trace.current()

    t = threading.Thread(target=fresh_thread)
    t.start()
    t.join()
    assert seen["thread_ctx"] is None  # no ambient context off-loop

    async def main():
        loop = asyncio.get_running_loop()
        with tr.span("root"):
            parent = obs_trace.current()

            def work():
                with tr.span("far_side", parent=parent):
                    pass

            await loop.run_in_executor(None, work)

    run(main())
    recs = {r["name"]: r for r in tr.snapshot()}
    assert recs["far_side"]["parent_id"] == recs["root"]["span_id"]
    assert recs["far_side"]["trace_id"] == recs["root"]["trace_id"]
    assert recs["far_side"]["thread"] != recs["root"]["thread"]


def test_span_error_attribute_and_nesting_restored():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert obs_trace.current() is None  # context restored after the raise
    (rec,) = tr.snapshot()
    assert rec["attrs"]["error"] == "ValueError"


def test_span_stages_partition_the_span():
    """Stages split a span from its open to its end: each stage's time
    adds up in ``<stage>_ms``, a repeated stage keeps running, and the
    hook sees every change on the thread that made it."""
    tr = Tracer(clock=_fake_clock(0.001))  # every reading 1 ms on
    seen = []
    with tr.span("d") as sp:                        # open at 0
        sp.begin_stages("pack", seen.append)
        obs_trace.stage("run")                      # 1
        obs_trace.stage("run")                      # still run
        obs_trace.stage("unpack")                   # 2
        obs_trace.stage("run")                      # 3
        obs_trace.stage("unpack")                   # 4
    (rec,) = tr.snapshot()                          # end at 5
    a = rec["attrs"]
    assert seen == ["pack", "run", "unpack", "run", "unpack", None]
    assert a["pack_ms"] == pytest.approx(1.0)
    assert a["run_ms"] == pytest.approx(2.0)
    assert a["unpack_ms"] == pytest.approx(2.0)
    assert rec["dur"] == pytest.approx(0.005)
    assert (a["pack_ms"] + a["run_ms"] + a["unpack_ms"]
            == pytest.approx(rec["dur"] * 1e3, abs=1e-9))
    # a span with no stages, a finished one and no span at all ignore it
    with tr.span("plain") as plain:
        obs_trace.stage("run")
    sp.stage("run")
    obs_trace.stage("run")
    assert not any(k.endswith("_ms") for k in tr.snapshot()[-1]["attrs"])
    assert seen[-1] is None and len(seen) == 6
    assert plain.attrs == {}


def test_ambient_set_attr_reaches_the_innermost_span():
    tr = Tracer()
    obs_trace.set_attr("outcome", "x")  # no span: nothing happens
    with tr.span("outer", outcome="rejected"):
        obs_trace.set_attr("outcome", "accepted")
        with tr.span("inner"):
            obs_trace.set_attr("k", 1)
    recs = {r["name"]: r["attrs"] for r in tr.snapshot()}
    assert recs == {"outer": {"outcome": "accepted"}, "inner": {"k": 1}}


def test_chrome_trace_export_golden():
    tr = Tracer(clock=_fake_clock())
    with tr.span("handshake.initiate", peer="ab"):
        with tr.span("device.dispatch", op="enc"):
            pass
    assert to_chrome_trace(tr.snapshot()) == {
        "traceEvents": [
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "MainThread"}},
            {"name": "device.dispatch", "ph": "X", "ts": 1000000.0,
             "dur": 1000000.0, "pid": 1, "tid": 1, "cat": "device",
             "args": {"trace_id": "t00000001", "span_id": "00000003",
                      "parent_id": "00000002", "op": "enc"}},
            {"name": "handshake.initiate", "ph": "X", "ts": 0.0,
             "dur": 3000000.0, "pid": 1, "tid": 1, "cat": "handshake",
             "args": {"trace_id": "t00000001", "span_id": "00000002",
                      "parent_id": None, "peer": "ab"}},
        ],
        "displayTimeUnit": "ms",
    }


# -- flight recorder ----------------------------------------------------------


def test_redaction_vocabulary_matches_qrlint():
    """Runtime redaction and qrlint's secret-hygiene pack share ONE
    vocabulary module (obs/redaction.py) — pin the import identity, not
    just pattern equality, so a re-forked copy can't sneak back in."""
    from tools.analysis import rules_secret
    from quantum_resistant_p2p_tpu.obs import redaction

    assert obs_flight.SECRET_NAME_RE is rules_secret.SECRET_NAME_RE
    assert obs_flight.SECRET_NAME_RE is redaction.SECRET_NAME_RE
    assert obs_flight.NONSECRET_NAME_RE is rules_secret.NONSECRET_NAME_RE
    assert rules_secret.is_secret_name is redaction.is_secret_name


def test_flight_redacts_at_record_time():
    rec = FlightRecorder()
    rec.record("ev", secret_key=b"\x01" * 32, shared_secret="ab" * 16,
               note="fine", n=3, public_key="cc" * 16,
               nested={"sk": "dd" * 40, "count": 2}, blob=b"xx" * 300,
               huge="z" * 1000)
    (e,) = rec.snapshot()
    assert e["secret_key"] == "[redacted:bytes:32]"
    assert e["shared_secret"] == "[redacted:str:32]"
    assert e["nested"]["sk"] == "[redacted:str:80]"
    assert e["nested"]["count"] == 2
    assert e["blob"] == "[bytes:600]"          # raw bytes never stored
    assert e["huge"] == "[str:1000 chars]"
    assert e["note"] == "fine" and e["n"] == 3
    # public-named values survive (NONSECRET walks back the match)
    assert e["public_key"] == "cc" * 16
    dumped = json.dumps(rec.dump("t", registries={}))
    assert "dd" * 40 not in dumped and "ab" * 16 not in dumped


def test_redact_value_depth_and_types():
    deep = {"a": {"b": {"c": {"d": {"e": 1}}}}}
    out = redact_value("x", deep)
    assert out["a"]["b"]["c"]["d"] == "[dict]"
    assert redact_value("x", object()).startswith("[object")
    assert redact_value("x", [b"ab", "ok"]) == ["[bytes:2]", "ok"]


def test_flight_dump_byte_reproducible_given_seed(tmp_path, monkeypatch):
    """Same seed + same event stream + injected clocks -> byte-identical
    diagnostic bundles (the chaos-run explainability contract)."""

    def drive(out_path):
        rec = FlightRecorder(clock=_fake_clock(0.25), mono=_fake_clock(0.25))
        monkeypatch.setattr(obs_flight, "RECORDER", rec)
        plan = FaultPlan(seed=11, rules=[
            FaultRule("net.send", "corrupt", match={"msg_type": "m"}, nth=1),
            FaultRule("device.dispatch", "raise", nth=2),
        ])
        with plan.activate():
            for _ in range(3):
                plan.net_send("a", "b", "m", {"ct": bytes(8)})
            for _ in range(3):
                try:
                    plan.device_dispatch("q.enc", 1)
                except Exception:
                    pass
        rec.dump("chaos", path=out_path, registries={})
        return out_path.read_bytes()

    b1 = drive(tmp_path / "d1.json")
    b2 = drive(tmp_path / "d2.json")
    assert b1 == b2
    doc = json.loads(b1)
    assert doc["trigger"] == "chaos"
    assert [e["kind"] for e in doc["events"]].count("fault_injected") == 2


def test_seeded_chaos_run_dump_tells_the_breaker_story(run, monkeypatch):
    """Acceptance: a seeded chaos run produces a redacted dump containing
    the breaker open -> half-open -> close transitions, event by event."""
    rec = FlightRecorder()
    monkeypatch.setattr(obs_flight, "RECORDER", rec)

    async def main():
        q = OpQueue(lambda items: [("dev", i) for i in items],
                    max_batch=4, max_wait_ms=1.0,
                    fallback_fn=lambda items: [("cpu", i) for i in items],
                    breaker=Breaker(cooloff_s=0.05), label="chaos.enc")
        q.mark_warm(1)
        plan = FaultPlan(3, [FaultRule("device.dispatch", "raise", nth=1)])
        with plan.activate():
            assert await q.submit(1) == ("cpu", 1)   # fault -> open -> fallback
        await asyncio.sleep(0.08)                    # ride out the cool-off
        assert await q.submit(2) == ("dev", 2)       # canary heals -> closed

    run(main())
    events = rec.snapshot()
    states = [e["state"] for e in events if e["kind"].startswith("breaker")]
    assert states == ["open", "half_open", "closed"]
    assert any(e["kind"] == "fault_injected" for e in events)
    # the dispatch spans rode along into the ring
    assert any(e["kind"] == "span" and e["name"] == "fallback.dispatch"
               for e in events)
    assert any(e["kind"] == "span" and e["name"] == "device.dispatch"
               for e in events)
    bundle = rec.dump("chaos", registries={})
    assert [e["state"] for e in bundle["events"]
            if e["kind"].startswith("breaker")] == ["open", "half_open", "closed"]


def test_autodump_fires_on_breaker_open_trigger(tmp_path, monkeypatch):
    rec = FlightRecorder()
    rec.set_autodump(tmp_path, min_interval_s=0.0, keep=4)
    monkeypatch.setattr(obs_flight, "RECORDER", rec)
    Breaker(cooloff_s=0.1).trip()
    files = []
    for _ in range(100):
        files = sorted(tmp_path.glob("flight_*.json"))
        if files:
            break
        time.sleep(0.05)
    assert files, "breaker open did not auto-dump a bundle"
    doc = json.loads(files[0].read_text())
    assert doc["trigger"] == "breaker_open"
    assert any(e["kind"] == "breaker_open" for e in doc["events"])


def test_autodump_rate_limit_and_prune(tmp_path):
    rec = FlightRecorder(mono=_fake_clock(1.0))
    rec.set_autodump(tmp_path, min_interval_s=10.0, keep=2)
    rec.trigger("fault_injected", n=1)   # mono 0 -> dump
    rec.trigger("fault_injected", n=2)   # mono 1 -> rate-limited
    rec.trigger("other_kind", n=3)       # separate kind -> dump
    deadline = time.time() + 5
    while time.time() < deadline:
        if len(list(tmp_path.glob("flight_*.json"))) >= 2:
            break
        time.sleep(0.05)
    assert len(list(tmp_path.glob("flight_*.json"))) == 2


# -- SecureMessaging metrics parity ------------------------------------------


def _keystream(key: bytes, nonce: bytes, n: int) -> bytes:
    out = b""
    ctr = 0
    while len(out) < n:
        out += hashlib.sha256(key + nonce + ctr.to_bytes(8, "big")).digest()
        ctr += 1
    return out[:n]


class StdlibAEAD(SymmetricAlgorithm):
    """Stdlib encrypt-then-MAC AEAD (the faults-suite toy): lets the full
    protocol stack run on images without the OpenSSL wheel."""

    name = "TOY-AEAD"
    display_name = "TOY-AEAD"
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


#: the exact metrics() layout shipped before obs/ (PR 2-4): removing or
#: renaming ANY of these is a compatibility break — adding keys is fine
LEGACY_TOP_KEYS = {
    "backend", "batching", "kem_queue", "sig_queue", "fused_queue",
    "device_trips", "fallback_trips", "breaker_trips", "breaker_state",
    "breaker_opens", "breaker_closes", "device_served_fraction",
    "handshake_trips",
}
LEGACY_QUEUE_KEYS = {
    "ops", "flushes", "max_batch_seen", "avg_batch", "avg_dispatch_ms",
    "p50_dispatch_ms", "p99_dispatch_ms", "fallback_ops", "fallback_flushes",
    "breaker_trips", "device_trips", "device_served_fraction",
}
LEGACY_TRIPS_KEYS = {"count", "last", "p50", "p99"}


def test_metrics_keys_parity_with_pre_obs_layout(monkeypatch):
    monkeypatch.setattr(SecureMessaging, "_spawn_warmup",
                        lambda self, **kw: None)
    node = P2PNode(node_id="paritypeer", host="127.0.0.1", port=0)
    m = SecureMessaging(node, backend="tpu", use_batching=True,
                        symmetric=StdlibAEAD(), sig_keypair=(b"p", b"s"))
    out = m.metrics()
    missing = LEGACY_TOP_KEYS - set(out)
    assert not missing, f"metrics() lost key(s): {sorted(missing)}"
    for fam in ("kem_queue", "sig_queue", "fused_queue"):
        for qname, q in out[fam].items():
            qmissing = LEGACY_QUEUE_KEYS - set(q)
            assert not qmissing, f"{fam}.{qname} lost {sorted(qmissing)}"
    assert LEGACY_TRIPS_KEYS <= set(out["handshake_trips"])
    # and the new single source serves the same data other ways too
    assert out["resilience"]["rekeys"] == 0
    snap = m.registry.snapshot()
    assert snap["collected"]["queues"]["breaker_state"] == out["breaker_state"]
    prom = m.registry.to_prometheus()
    assert "qrp2p_handshake_trips" in prom
    assert "qrp2p_queues_device_trips" in prom


def test_metrics_parity_without_batching():
    node = P2PNode(node_id="nobatch", host="127.0.0.1", port=0)
    m = SecureMessaging(node, backend="cpu", symmetric=StdlibAEAD(),
                        sig_keypair=(b"p", b"s"))
    out = m.metrics()
    assert out["backend"] == "cpu" and out["batching"] is False
    assert LEGACY_TRIPS_KEYS <= set(out["handshake_trips"])
    assert "kem_queue" not in out  # batching off: same shape as before obs/


# -- cross-peer trace propagation ---------------------------------------------


class ToyKEM:
    """Deterministic hash-based toy KEM (the faults-suite pattern): lets
    the two-node propagation e2e run the REAL protocol in milliseconds."""

    name = "TOY-KEM"
    display_name = "TOY-KEM"
    public_key_len = 32
    secret_key_len = 32
    ciphertext_len = 32
    shared_secret_len = 32
    backend = "cpu"

    def generate_keypair(self):
        sk = os.urandom(32)
        return hashlib.sha256(b"pk" + sk).digest(), sk

    def encapsulate(self, public_key):
        ct = os.urandom(32)
        return ct, hashlib.sha256(public_key + ct).digest()

    def decapsulate(self, secret_key, ciphertext):
        pk = hashlib.sha256(b"pk" + secret_key).digest()
        return hashlib.sha256(pk + ciphertext).digest()


class ToySig:
    name = "TOY-SIG"
    display_name = "TOY-SIG"
    public_key_len = 32
    secret_key_len = 32
    signature_len = 32
    backend = "cpu"

    def generate_keypair(self):
        sk = os.urandom(32)
        return hashlib.sha256(b"pk" + sk).digest(), sk

    def sign(self, secret_key, message):
        pk = hashlib.sha256(b"pk" + secret_key).digest()
        return hashlib.sha256(b"sig" + pk + message).digest()

    def verify(self, public_key, message, signature):
        return hmac.compare_digest(
            signature, hashlib.sha256(b"sig" + public_key + message).digest())


async def _toy_pair():
    a_node = P2PNode(node_id="alice", host="127.0.0.1", port=0)
    b_node = P2PNode(node_id="bob", host="127.0.0.1", port=0)
    await a_node.start()
    await b_node.start()
    kw = dict(kem=ToyKEM(), signature=ToySig(), symmetric=StdlibAEAD())
    a = SecureMessaging(a_node, **kw)
    b = SecureMessaging(b_node, **kw)
    assert await a_node.connect_to_peer("127.0.0.1", b_node.port) == "bob"
    for _ in range(100):
        if b_node.is_connected("alice"):
            break
        await asyncio.sleep(0.01)
    return a, b


def _spans_by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def test_wire_context_validation_rejects_hostile_input(monkeypatch):
    """adopt_wire_context: peers are untrusted — anything but a dict of
    two short token-charset string ids is ignored, never an exception or
    a control-flow change."""
    tr = Tracer()
    with tr.span("root"):
        ctx = obs_trace.wire_context()
        assert set(ctx) == {"trace_id", "span_id"}
        # extra kwargs: only short token strings survive; qrflow polices
        # the surface statically so nothing tainted can reach here
        rich = obs_trace.wire_context(run="r1", huge="x" * 65, n=3)  # type: ignore[arg-type]
        assert rich["run"] == "r1" and "huge" not in rich and "n" not in rich
    good = obs_trace.adopt_wire_context(ctx)
    assert good is not None and good.trace_id == ctx["trace_id"]
    assert good.node is None  # remote parents never claim a local lane
    for hostile in (
        None, 7, "t1/s1", [], {"trace_id": "a"},                  # shapes
        {"trace_id": 5, "span_id": "b"},                          # types
        {"trace_id": "a" * 65, "span_id": "b"},                   # oversize
        {"trace_id": "ok", "span_id": "bad\nid"},                 # charset
        {"trace_id": "evil\n", "span_id": "b"},                   # $-anchor hole
        {"trace_id": "a" * 64 + "\n", "span_id": "b"},            # 65B via \n
        {"trace_id": "ok", "span_id": "sp", "extra": object()},   # junk rides
    ):
        adopted = obs_trace.adopt_wire_context(hostile)
        if isinstance(hostile, dict) and hostile.get("span_id") == "sp":
            assert adopted is not None  # extra keys ignored, ids adopted
        else:
            assert adopted is None, hostile
    monkeypatch.setenv("QRP2P_TRACE_PROPAGATE", "0")
    with tr.span("root2"):
        assert obs_trace.wire_context() is None
    assert obs_trace.adopt_wire_context(ctx) is None


async def _handshake_spans(a, b, *needed):
    """Run one a->b handshake and snapshot spans once ``needed`` names
    have all been recorded (the responder's tail work is async)."""
    obs_trace.TRACER.reset()
    assert await a.initiate_key_exchange("bob")
    spans = []
    for _ in range(200):
        spans = obs_trace.TRACER.snapshot()
        if all(any(s["name"] == n for s in spans) for n in needed):
            break
        await asyncio.sleep(0.01)
    await a.node.stop()
    await b.node.stop()
    return spans


def test_two_node_handshake_joins_one_trace(run, monkeypatch):
    """Acceptance (ISSUE 10): initiator and responder handshake spans
    share ONE trace_id, and the responder's chain parents onto the
    initiator's net.send span via the propagated wire context."""
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 10.0)

    async def main():
        a, b = await _toy_pair()
        return await _handshake_spans(
            a, b, "handshake.initiate", "handshake.respond",
            "handshake.confirm")

    spans = run(main())
    by_name = _spans_by_name(spans)
    (initiate,) = by_name["handshake.initiate"]
    (respond,) = by_name["handshake.respond"]
    (confirm,) = by_name["handshake.confirm"]
    assert respond["attrs"]["outcome"] == "accepted"
    # one causal chain across both peers
    assert respond["trace_id"] == initiate["trace_id"]
    assert confirm["trace_id"] == initiate["trace_id"]
    # node attribution: each side's protocol spans sit on its own lane
    assert initiate["node"] == "alice"
    assert respond["node"] == "bob"
    # the responder chain parents onto the initiator's ke_init net.send
    by_id = {s["span_id"]: s for s in spans}
    recv_init = by_id[respond["parent_id"]]
    assert recv_init["name"] == "net.recv"
    assert recv_init["attrs"]["msg_type"] == "ke_init"
    send_init = by_id[recv_init["parent_id"]]
    assert send_init["name"] == "net.send"
    assert send_init["attrs"]["msg_type"] == "ke_init"
    assert send_init["node"] == "alice"
    # every net.recv of the exchange adopted a remote parent (no orphan
    # re-roots anywhere in the 5-message chain)
    ke_recvs = [s for s in by_name["net.recv"]
                if s["attrs"]["msg_type"].startswith("ke_")]
    assert ke_recvs and all(s["trace_id"] == initiate["trace_id"]
                            and s["parent_id"] for s in ke_recvs)


def test_refused_ke_init_reads_rejected(run, monkeypatch):
    """A ke_init whose signature the responder refuses ends its
    ``handshake.respond`` span with ``outcome`` rejected."""
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 2.0)

    async def main():
        a, b = await _toy_pair()
        b.signature.verify = lambda *args: False
        obs_trace.TRACER.reset()
        assert not await a.initiate_key_exchange("bob")
        spans = obs_trace.TRACER.snapshot()
        await a.node.stop()
        await b.node.stop()
        return spans

    responds = _spans_by_name(run(main()))["handshake.respond"]
    assert responds and all(s["attrs"]["outcome"] == "rejected"
                            for s in responds)


def test_propagation_optout_restores_disjoint_traces(run, monkeypatch):
    """QRP2P_TRACE_PROPAGATE=0: no ``_trace`` field rides any frame
    (wire-identical to the pre-propagation protocol) and the two sides'
    traces are disjoint again."""
    monkeypatch.setenv("QRP2P_TRACE_PROPAGATE", "0")
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 10.0)
    sent_messages = []
    orig = P2PNode._send_frame

    async def spy(self, writer, lock, message):
        sent_messages.append(message)
        return await orig(self, writer, lock, message)

    monkeypatch.setattr(P2PNode, "_send_frame", spy)

    async def main():
        a, b = await _toy_pair()
        return await _handshake_spans(
            a, b, "handshake.initiate", "handshake.respond")

    spans = run(main())
    assert all("_trace" not in m for m in sent_messages)
    by_name = _spans_by_name(spans)
    (initiate,) = by_name["handshake.initiate"]
    (respond,) = by_name["handshake.respond"]
    assert respond["trace_id"] != initiate["trace_id"]
    assert all(s["parent_id"] is None for s in by_name["net.recv"])


def test_propagation_on_attaches_ids_only_field(run, monkeypatch):
    """With propagation ON (the default), ke_* frames carry exactly the
    bounded ids-only ``_trace`` dict — and handlers never see it.  Both
    frame encoders are spied: peers that negotiated the binary wire send
    the same message dicts through ``_send_frame_bin``."""
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 10.0)
    sent_messages = []
    seen_by_handler = []
    orig = P2PNode._send_frame
    orig_bin = P2PNode._send_frame_bin

    async def spy(self, writer, lock, message):
        sent_messages.append(message)
        return await orig(self, writer, lock, message)

    async def spy_bin(self, writer, lock, message):
        sent_messages.append(message)
        return await orig_bin(self, writer, lock, message)

    monkeypatch.setattr(P2PNode, "_send_frame", spy)
    monkeypatch.setattr(P2PNode, "_send_frame_bin", spy_bin)

    async def main():
        a, b = await _toy_pair()

        async def on_init(peer_id, msg):
            seen_by_handler.append(msg)

        b.node.register_message_handler("ke_init", on_init)
        assert await a.initiate_key_exchange("bob")
        await a.node.stop()
        await b.node.stop()

    run(main())
    traced = [m for m in sent_messages if "_trace" in m]
    assert traced, "no frame carried the propagated context"
    for m in traced:
        assert set(m["_trace"]) == {"trace_id", "span_id"}
        assert all(isinstance(v, str) and len(v) <= obs_trace.WIRE_ID_MAX
                   for v in m["_trace"].values())
    assert seen_by_handler and all("_trace" not in m for m in seen_by_handler)


def test_chunked_message_gets_one_recv_span_with_chunk_attr(run):
    """Satellite (ISSUE 10): a reassembled chunked message carries ONE
    net.recv span for the logical message, with a ``chunks=`` attr, still
    parented on the sender's propagated context."""

    async def main():
        a = P2PNode(node_id="alice", host="127.0.0.1", port=0)
        b = P2PNode(node_id="bob", host="127.0.0.1", port=0)
        await a.start()
        await b.start()
        assert await a.connect_to_peer("127.0.0.1", b.port) == "bob"
        for _ in range(100):
            if b.is_connected("alice"):
                break
            await asyncio.sleep(0.01)
        a.chunk_size = 4096
        got = asyncio.Event()

        async def on_big(peer_id, msg):
            got.set()

        b.register_message_handler("big", on_big)
        obs_trace.TRACER.reset()
        with obs_trace.TRACER.span("caller"):
            assert await a.send_message("bob", "big", data=bytes(40_000))
        await asyncio.wait_for(got.wait(), 10)
        spans = obs_trace.TRACER.snapshot()
        await a.stop()
        await b.stop()
        return spans

    spans = run(main())
    by_name = _spans_by_name(spans)
    recvs = [s for s in by_name.get("net.recv", [])
             if s["attrs"]["msg_type"] == "big"]
    assert len(recvs) == 1, recvs  # one span per LOGICAL message
    (recv,) = recvs
    (send,) = [s for s in by_name["net.send"]
               if s["attrs"]["msg_type"] == "big"]
    assert recv["attrs"]["chunks"] >= 2  # ~40KB over 4KiB chunks
    assert recv["parent_id"] == send["span_id"]
    assert recv["trace_id"] == send["trace_id"]


def test_merged_two_node_trace_has_process_lanes_and_flow_edges(run, monkeypatch):
    """Acceptance: the merged chrome document shows both nodes as separate
    process lanes under a single trace id, with cross-node flow arrows on
    the propagated parent edges (tools/trace_merge.py)."""
    from tools import trace_merge

    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 10.0)

    async def main():
        a, b = await _toy_pair()
        return await _handshake_spans(
            a, b, "handshake.initiate", "handshake.respond",
            "handshake.confirm")

    spans = run(main())
    doc = trace_merge.merge([obs_trace.span_dump(records=spans)])
    other = doc["otherData"]
    assert {"alice", "bob"} <= set(other["merged_nodes"])
    assert other["cross_node_edges"] >= 2  # ke_init + at least one reply
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    procs = {e["name"]: e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs  # process_name metadata present
    pid_by_node = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "M" and e["name"] == "process_name":
            pid_by_node[e["args"]["name"]] = e["pid"]
    hs = {e["name"]: e for e in xs if e["name"].startswith("handshake.")}
    assert hs["handshake.initiate"]["pid"] == pid_by_node["alice"]
    assert hs["handshake.respond"]["pid"] == pid_by_node["bob"]
    assert (hs["handshake.respond"]["args"]["trace_id"]
            == hs["handshake.initiate"]["args"]["trace_id"])
    flows = [e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")]
    assert flows and len(flows) == 2 * other["cross_node_edges"]
    # loadable JSON round-trip
    json.loads(json.dumps(doc))


def test_trace_merge_aligns_multi_process_dumps(tmp_path):
    """Dumps from different processes (distinct clock epochs, distinct
    tracer tags) merge onto one timeline with parent edges intact."""
    from tools import trace_merge

    ta = Tracer(clock=_fake_clock(0.5), tag="aaaa")
    with obs_trace.node_scope("alice"), ta.span("net.send"):
        wire = obs_trace.wire_context()
    da = obs_trace.span_dump(node="alice", tracer=ta)
    da["wall_anchor"], da["mono_anchor"] = 1000.0, da["mono_anchor"]

    tb = Tracer(clock=_fake_clock(0.5), tag="bbbb")
    parent = obs_trace.adopt_wire_context(wire)
    with obs_trace.node_scope("bob"), tb.span("net.recv", parent=parent):
        pass
    db = obs_trace.span_dump(node="bob", tracer=tb)
    db["wall_anchor"], db["mono_anchor"] = 1002.0, db["mono_anchor"]

    (tmp_path / "a.json").write_text(json.dumps(da))
    (tmp_path / "b.json").write_text(json.dumps(db))
    doc = trace_merge.merge_files([tmp_path / "a.json", tmp_path / "b.json"])
    assert doc["otherData"]["merged_nodes"] == ["alice", "bob"]
    assert doc["otherData"]["cross_node_edges"] == 1
    xs = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    # bob's dump anchors 2 wall-seconds later: its span lands later on the
    # merged timeline even though both tracers' raw clocks started at ~0
    assert xs["net.recv"]["ts"] > xs["net.send"]["ts"]
    assert xs["net.recv"]["pid"] != xs["net.send"]["pid"]


# -- end to end: the traced warm handshake -----------------------------------


def test_traced_warm_handshake_yields_exactly_four_dispatch_spans(
        run, monkeypatch):
    """Acceptance: one warm ML-KEM-768 x ML-DSA-65 fused handshake =
    exactly 4 device-dispatch spans (initiator keygen+sign, responder
    verify+encaps+sign, initiator verify+decaps+sign, responder confirm
    verify — docs/dispatch_budget.md), and the trace exports as loadable
    chrome://tracing JSON."""
    monkeypatch.setenv("QRP2P_HEALTH_GATE", "0")
    monkeypatch.setattr(messaging_mod, "WARMUP_SIZES", (1,))
    monkeypatch.setattr(messaging_mod, "KEY_EXCHANGE_TIMEOUT", 120.0)

    async def main():
        a_node = P2PNode(node_id="alice", host="127.0.0.1", port=0)
        b_node = P2PNode(node_id="bob", host="127.0.0.1", port=0)
        await a_node.start()
        await b_node.start()
        kw = dict(backend="tpu", use_batching=True, max_batch=64,
                  max_wait_ms=2.0, symmetric=StdlibAEAD())
        a = SecureMessaging(a_node, **kw)
        b = SecureMessaging(b_node, **kw)
        assert a._bfused is not None  # the pair advertises the fused path
        assert await a_node.connect_to_peer("127.0.0.1", b_node.port) == "bob"
        for _ in range(100):
            if b_node.is_connected("alice"):
                break
            await asyncio.sleep(0.01)
        # background warmup compiles the size-1 buckets; waiting here makes
        # the measured handshake WARM (no warmup-route dispatches)
        await a.wait_ready()
        await b.wait_ready()
        obs_trace.TRACER.reset()
        assert await a.initiate_key_exchange("bob")
        # the responder's confirm-verify dispatch completes asynchronously —
        # and each device span's queue.flush PARENT closes on the loop side
        # a beat after the worker-side dispatch span does, so wait for the
        # parents too (snapshotting the gap made this flake on loaded hosts)
        spans = []
        for _ in range(200):
            spans = obs_trace.TRACER.snapshot()
            dev = [s for s in spans if s["name"] == "device.dispatch"]
            seen = {s["span_id"] for s in spans}
            if len(dev) >= 4 and all(d["parent_id"] in seen for d in dev):
                break
            await asyncio.sleep(0.05)
        device = [s for s in spans if s["name"] == "device.dispatch"]
        fallback = [s for s in spans if s["name"] == "fallback.dispatch"]
        assert len(device) == 4, (
            f"expected exactly 4 device-dispatch spans, got "
            f"{[s['attrs'] for s in device]} + fallback "
            f"{[s['attrs'] for s in fallback]}"
        )
        assert not fallback  # warm run: nothing degraded to the cpu path
        ops = sorted(s["attrs"]["op"] for s in device)
        assert ops == sorted([
            "ML-KEM-768+ML-DSA-65.keygen_sign",
            "ML-KEM-768+ML-DSA-65.encaps_verify_sign",
            "ML-KEM-768+ML-DSA-65.decaps_verify_sign",
            "ML-DSA-65.verify",
        ])
        # each dispatch span chains into a queue.flush parent, which chains
        # into the protocol span that enqueued first — one correlated story
        by_id = {s["span_id"]: s for s in spans}
        for d in device:
            parent = by_id.get(d["parent_id"])
            assert parent is not None and parent["name"] == "queue.flush"
        # the flame graph is loadable chrome://tracing JSON
        doc = json.loads(json.dumps(to_chrome_trace(spans)))
        events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["name"] for e in events} >= {"device.dispatch", "queue.flush"}
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in events)
        # the span count and the trip metric tell the same story
        trips = a.metrics()["handshake_trips"]
        assert trips["count"] == 1 and trips["last"] is not None
        assert trips["last"] <= 4
        await a_node.stop()
        await b_node.stop()

    run(main())


def test_storm_snapshot_digest_folds_per_peer_registries():
    """A storm mints one metrics registry PER SESSION, so raw committed
    snapshots ran to ~240k lines; write_obs_artifacts now digests them
    (tools/swarm_bench.snapshot_digest) unless --full-snapshots.  Pin
    the fold: registries group by class key, counters sum, gauges fold
    to min/mean/max, histograms merge to bucketless count/sum/p-ranges."""
    from tools.swarm_bench import snapshot_digest

    def reg(counters, gauges, hists):
        return {"counters": counters, "gauges": gauges, "histograms": hists}

    snap = {
        "messaging:peer00001": reg(
            {"sent": 2}, {"outbox": 1.0},
            {"rtt": {"count": 4, "sum": 2.0, "p50": 0.4, "p99": 0.9}}),
        "messaging:peer00002#1": reg(
            {"sent": 3}, {"outbox": 3.0},
            {"rtt": {"count": 6, "sum": 4.0, "p50": 0.6, "p99": 1.1}}),
        "router": reg({"frames": 7}, {}, {}),
    }
    d = snapshot_digest(snap)
    assert d["_digest"] == {"registries": 3,
                            "groups": {"messaging": 2, "router": 1}}
    m = d["messaging"]
    assert m["instances"] == 2
    assert m["counters"] == {"sent": 5}
    assert m["gauges"]["outbox"] == {"min": 1.0, "max": 3.0, "mean": 2.0}
    assert m["histograms"]["rtt"] == {"count": 10, "sum": 6.0,
                                      "p50_range": [0.4, 0.6],
                                      "p99_range": [0.9, 1.1]}
    assert d["router"]["counters"] == {"frames": 7}
