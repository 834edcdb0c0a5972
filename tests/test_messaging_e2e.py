"""End-to-end: two in-process nodes complete an ML-KEM-768 + ML-DSA-65 +
AES-256-GCM handshake and exchange verified messages over localhost TCP.

Models the reference's integration harness (tests/crypto_algorithms_tester.py:
two full stacks in one process, real TCP, event-driven sync).  CPU backend —
the TPU provider path is exercised by the jax test modules and bench.py.
"""

import asyncio

import pytest

from quantum_resistant_p2p_tpu.app import Message, MessageStore, SecureMessaging
from quantum_resistant_p2p_tpu.net import P2PNode
from quantum_resistant_p2p_tpu.storage import KeyStorage


@pytest.fixture
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


class Stack:
    """A full node stack minus UI: storage + transport + protocol engine."""

    def __init__(self, name: str, tmp_path, **sm_kwargs):
        self.storage = KeyStorage(tmp_path / f"{name}.vault.json")
        assert self.storage.unlock("test_password")
        self.node = P2PNode(node_id=name, host="127.0.0.1", port=0)
        self.messaging = None
        self.inbox: list[tuple[str, Message]] = []
        self.got_message = asyncio.Event()
        self._sm_kwargs = sm_kwargs

    async def start(self):
        await self.node.start()
        self.messaging = SecureMessaging(
            self.node, key_storage=self.storage, **self._sm_kwargs
        )
        self.messaging.register_message_listener(self._on_msg)

    def _on_msg(self, peer_id, message):
        self.inbox.append((peer_id, message))
        self.got_message.set()

    async def stop(self):
        await self.node.stop()


async def _connected_pair(tmp_path, **kw):
    a, b = Stack("alice", tmp_path, **kw), Stack("bob", tmp_path, **kw)
    await a.start()
    await b.start()
    assert await a.node.connect_to_peer("127.0.0.1", b.node.port) == "bob"
    for _ in range(100):
        if b.node.is_connected("alice"):
            break
        await asyncio.sleep(0.01)
    return a, b


def test_handshake_and_messaging(run, tmp_path):
    async def main():
        a, b = await _connected_pair(tmp_path)
        ok = await a.messaging.initiate_key_exchange("bob")
        assert ok
        assert a.messaging.verify_key_exchange_state("bob")
        # responder reaches ESTABLISHED after the confirm message arrives
        for _ in range(100):
            if b.messaging.verify_key_exchange_state("alice"):
                break
            await asyncio.sleep(0.01)
        assert b.messaging.verify_key_exchange_state("alice")
        # both derived the same AEAD key
        assert a.messaging.shared_keys["bob"] == b.messaging.shared_keys["alice"]

        sent = await a.messaging.send_message("bob", b"hello post-quantum world")
        assert sent is not None
        peers = []
        for _ in range(200):
            peers = [m for m in b.inbox if not m[1].is_system]
            if peers:
                break
            await asyncio.sleep(0.02)
        assert peers and peers[-1][0] == "alice"
        assert peers[-1][1].content == b"hello post-quantum world"

        # reply in the other direction
        b.got_message.clear()
        a.got_message.clear()
        assert await b.messaging.send_message("alice", b"ack") is not None
        await asyncio.wait_for(a.got_message.wait(), 5)
        assert any(m.content == b"ack" for _, m in a.inbox)

        # shared-key history was persisted on both sides
        assert a.storage.list_key_history("bob")
        assert b.storage.list_key_history("alice")
        await a.stop()
        await b.stop()

    run(main())


def test_file_transfer(run, tmp_path):
    async def main():
        a, b = await _connected_pair(tmp_path)
        assert await a.messaging.initiate_key_exchange("bob")
        payload = bytes(range(256)) * 512  # 128 KiB -> exercises chunking
        f = tmp_path / "blob.bin"
        f.write_bytes(payload)
        assert await a.messaging.send_file("bob", f) is not None
        for _ in range(200):
            if any(m.is_file for _, m in b.inbox):
                break
            await asyncio.sleep(0.02)
        files = [m for _, m in b.inbox if m.is_file]
        assert files and files[0].content == payload and files[0].filename == "blob.bin"
        await a.stop()
        await b.stop()

    run(main())


def test_tampered_ciphertext_rejected(run, tmp_path):
    async def main():
        a, b = await _connected_pair(tmp_path)
        assert await a.messaging.initiate_key_exchange("bob")
        # send a raw secure_message with corrupted ciphertext
        key_count = len(b.inbox)
        ct = b"\x00" * 64
        await a.node.send_message("bob", "secure_message", ct=ct, ad=b"{}")
        await asyncio.sleep(0.2)
        assert len([m for m in b.inbox if not m[1].is_system]) == key_count
        await a.stop()
        await b.stop()

    run(main())


def test_settings_gossip_and_mismatch_block(run, tmp_path):
    async def main():
        a, b = await _connected_pair(tmp_path)
        # gossip happens on connect; wait for it
        for _ in range(100):
            if a.messaging.peer_settings.get("bob"):
                break
            await asyncio.sleep(0.01)
        assert a.messaging.settings_match("bob") is True
        # switch bob's AEAD: alice should see a mismatch after gossip
        await b.messaging.set_symmetric_algorithm("ChaCha20-Poly1305")
        for _ in range(100):
            if a.messaging.peer_settings.get("bob", {}).get("aead") == "ChaCha20-Poly1305":
                break
            await asyncio.sleep(0.01)
        assert a.messaging.settings_match("bob") is False
        # adopt peer settings and handshake again
        assert await a.messaging.adopt_peer_settings("bob")
        assert a.messaging.settings_match("bob") is True
        assert await a.messaging.initiate_key_exchange("bob")
        assert await a.messaging.send_message("bob", b"after swap") is not None
        await a.stop()
        await b.stop()

    run(main())


def test_message_store():
    store = MessageStore()
    m = Message(content=b"x", sender_id="a", recipient_id="b")
    store.add_message("a", m, unread=True)
    assert store.get_unread_count("a") == 1
    store.mark_read("a")
    assert store.get_unread_count("a") == 0
    assert store.get_messages("a")[0].content == b"x"
    d = m.to_dict()
    assert Message.from_dict(d).content == b"x"


def test_handshake_with_batched_tpu_provider(run, tmp_path):
    """North-star path: handshake crypto routed through the batch queue."""

    async def main():
        kw = dict(backend="tpu", use_batching=True, max_batch=64, max_wait_ms=2.0)
        a, b = await _connected_pair(tmp_path, **kw)
        assert a.messaging._bkem is not None
        # background warmup precompiles the size-1 buckets; waiting here keeps
        # cold-jit time out of the protocol timeout (the round-1 flake)
        await a.messaging.wait_ready()
        await b.messaging.wait_ready()
        ok = await a.messaging.initiate_key_exchange("bob")
        assert ok
        assert a.messaging.shared_keys["bob"] == b.messaging.shared_keys["alice"]
        assert await a.messaging.send_message("bob", b"batched hello") is not None
        for _ in range(200):
            if any(m.content == b"batched hello" for _, m in b.inbox):
                break
            await asyncio.sleep(0.02)
        assert any(m.content == b"batched hello" for _, m in b.inbox)
        # ML-KEM-768 + ML-DSA-65 advertises the fused capability, so the
        # handshake crypto rides the composite queues: keygen+sign on the
        # initiator, verify+encaps+sign on the responder, verify+decaps+sign
        # back on the initiator — NOT the per-op kem/sig queues.
        assert a.messaging._bfused is not None
        fa, fb = a.messaging._bfused.stats(), b.messaging._bfused.stats()
        assert fa["keygen_sign"]["ops"] >= 1
        assert fa["decaps_verify_sign"]["ops"] >= 1
        assert fb["encaps_verify_sign"]["ops"] >= 1
        assert a.messaging._bkem.stats()["keygen"]["ops"] == 0
        # the secure message itself still signs through the per-op queue
        sig_st = a.messaging._bsig.stats()
        assert sig_st["sign"]["ops"] >= 1
        # the tentpole claim, measured: the initiator's handshake spent
        # <= 4 serial dispatch trips (2 fused on its own breaker)
        trips = a.messaging.metrics()["handshake_trips"]
        assert trips["count"] == 1
        assert trips["last"] is not None and trips["last"] <= 4
        await a.stop()
        await b.stop()

    run(main())
