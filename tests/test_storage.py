"""KeyStorage / SecureLogger / AtomicFile behavior tests (host-only, no JAX)."""

import json
import os
import time

import pytest


from quantum_resistant_p2p_tpu.storage import AtomicFile, KeyStorage, SecureLogger
from quantum_resistant_p2p_tpu.storage.key_storage import KeyStorageError


@pytest.fixture
def vault(tmp_path):
    ks = KeyStorage(tmp_path / "vault.json")
    assert ks.unlock("hunter2-long-pass")
    return ks


def test_unlock_wrong_password(tmp_path):
    ks = KeyStorage(tmp_path / "vault.json")
    assert ks.unlock("correct horse")
    ks.lock()
    assert not ks.is_unlocked
    ks2 = KeyStorage(tmp_path / "vault.json")
    assert not ks2.unlock("wrong pass")
    assert ks2.unlock("correct horse")


def test_store_retrieve_delete(vault):
    vault.store("alpha", {"x": 1})
    assert vault.retrieve("alpha") == {"x": 1}
    vault.store_bytes("blob", b"\x00\x01\xff")
    assert vault.retrieve_bytes("blob") == b"\x00\x01\xff"
    assert vault.delete("alpha")
    assert vault.retrieve("alpha") is None
    assert not vault.delete("alpha")


def test_names_not_on_disk(vault, tmp_path):
    vault.store("super_secret_entry_name", {"v": 1})
    raw = (tmp_path / "vault.json").read_text()
    assert "super_secret_entry_name" not in raw


def test_purpose_key_stable_and_survives_password_change(vault):
    k1 = vault.get_or_create_purpose_key("audit")
    assert len(k1) == 32
    assert vault.get_or_create_purpose_key("audit") == k1
    assert vault.change_password("hunter2-long-pass", "new-password-9")
    assert vault.get_or_create_purpose_key("audit") == k1


def test_change_password_requires_old(vault):
    assert not vault.change_password("nope", "x")


def test_key_history(vault):
    vault.save_peer_shared_key("peerA", b"k" * 32, "ML-KEM-768")
    time.sleep(0.01)
    vault.save_peer_shared_key("peerA", b"j" * 32, "ML-KEM-768")
    vault.save_peer_shared_key("peerB", b"i" * 32, "ML-KEM-1024")
    hist = vault.list_key_history()
    assert len(hist) == 3
    hist_a = vault.list_key_history("peerA")
    assert len(hist_a) == 2
    newest = vault.get_key_history_value(hist_a[0]["name"])
    assert newest["peer_id"] == "peerA"
    assert vault.clear_key_history() == 3
    assert vault.list_key_history() == []


def test_reset_storage(vault, tmp_path):
    vault.store("gone", {"v": 1})
    vault.reset_storage("fresh-password")
    assert vault.retrieve("gone") is None
    ks2 = KeyStorage(tmp_path / "vault.json")
    assert not ks2.unlock("hunter2-long-pass")
    assert ks2.unlock("fresh-password")


def test_locked_raises(tmp_path):
    ks = KeyStorage(tmp_path / "vault.json")
    with pytest.raises(KeyStorageError):
        ks.store("a", 1)


def test_atomic_file_backup_recovery(tmp_path):
    af = AtomicFile(tmp_path / "data.json")
    af.write_json({"gen": 1})
    af.write_json({"gen": 2})
    # corrupt the primary; read should fall back to the .bak (gen 1)
    (tmp_path / "data.json").write_text("{truncated")
    assert af.read_json() == {"gen": 1}


def test_secure_logger_roundtrip_and_metrics(tmp_path):
    key = os.urandom(32)
    sl = SecureLogger(key, tmp_path)
    sl.log_event("message_sent", size=100, algorithm="AES-256-GCM")
    sl.log_event("message_received", size=40, algorithm="AES-256-GCM")
    sl.log_event("key_exchange", algorithm="ML-KEM-768", peer="p1")
    events = sl.get_events()
    assert len(events) == 3
    assert sl.get_events(event_type="key_exchange")[0]["peer"] == "p1"
    summary = sl.get_event_summary()
    assert summary["message_sent"] == 1
    m = sl.get_security_metrics()
    assert m["bytes_sent"] == 100 and m["bytes_received"] == 40
    assert m["algorithms_used"]["AES-256-GCM"] == 2
    assert sl.clear_logs() == 1
    assert sl.get_events() == []


def test_secure_logger_corruption_recovery(tmp_path):
    key = os.urandom(32)
    sl = SecureLogger(key, tmp_path)
    sl.log_event("a")
    path = next(tmp_path.glob("*.qlog"))
    good = path.read_bytes()
    # splice garbage between two valid records
    sl.log_event("b")
    full = path.read_bytes()
    second = full[len(good):]
    path.write_bytes(good + b"\xde\xad\xbe\xef" + second)
    events = sl.get_events()
    assert [e["event_type"] for e in events] == ["a", "b"]


def test_secure_logger_wrong_key_reads_nothing(tmp_path):
    sl = SecureLogger(os.urandom(32), tmp_path)
    sl.log_event("a")
    sl2 = SecureLogger(os.urandom(32), tmp_path)
    assert sl2.get_events() == []
