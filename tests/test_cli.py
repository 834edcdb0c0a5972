"""CLI: login gate + command surface driven programmatically (no stdin)."""

import asyncio
import io

import pytest


from quantum_resistant_p2p_tpu.cli import CLI


@pytest.fixture
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


def _mk(tmp_path, name, port=0):
    out = io.StringIO()
    cli = CLI(
        vault_path=str(tmp_path / f"{name}.vault.json"),
        port=port,
        backend="cpu",
        enable_discovery=False,
        out=out,
    )
    assert cli.login("pw-" + name)
    return cli, out


def test_two_clis_chat(run, tmp_path):
    async def main():
        a, a_out = _mk(tmp_path, "a")
        b, b_out = _mk(tmp_path, "b")
        await a.start()
        await b.start()
        assert await a.handle(f"/connect 127.0.0.1 {b.node.port}")
        await asyncio.sleep(0.05)
        peer_b = a.node.get_peers()[0]
        assert await a.handle(f"/key {peer_b[:8]}")
        assert "shared key established" in a_out.getvalue()
        assert await a.handle(f"/send {peer_b[:8]} hello from cli")
        for _ in range(100):
            if "hello from cli" in b_out.getvalue():
                break
            await asyncio.sleep(0.02)
        assert "hello from cli" in b_out.getvalue()

        # settings / metrics / logs / keyhistory surfaces all respond
        assert await a.handle("/settings")
        assert "ML-KEM-768" in a_out.getvalue()
        assert await a.handle("/metrics")
        assert await a.handle("/logs")
        assert "key_exchange" in a_out.getvalue()
        assert await a.handle("/keyhistory")
        assert "peer=" in a_out.getvalue()
        assert await a.handle("/set aead ChaCha20-Poly1305")
        assert a.messaging.symmetric.name == "ChaCha20-Poly1305"
        assert await a.handle("/peers")
        assert not await a.handle("/quit")
        await b.stop()

    run(main())


def test_trace_flight_and_prometheus_commands(run, tmp_path):
    """The obs/ surface: /trace exports loadable chrome://tracing JSON,
    /flight dumps a diagnostic bundle, /metrics prom emits the text
    exposition format (docs/observability.md)."""
    import json

    async def main():
        a, a_out = _mk(tmp_path, "obs")
        await a.start()
        tpath = tmp_path / "trace.json"
        assert await a.handle(f"/trace {tpath}")
        doc = json.loads(tpath.read_text())
        assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
        fpath = tmp_path / "flight.json"
        assert await a.handle(f"/flight {fpath}")
        bundle = json.loads(fpath.read_text())
        assert bundle["trigger"] == "manual"
        assert "events" in bundle and "metrics" in bundle
        assert await a.handle("/metrics prom")
        assert "qrp2p_" in a_out.getvalue()
        assert await a.handle("/metrics")
        assert '"operational"' in a_out.getvalue()
        assert await a.handle("/slo")
        out = a_out.getvalue()
        assert '"handshake_p99"' in out and '"budget_remaining"' in out
        assert "ALERTING" not in out  # a fresh node has burned nothing
        assert not await a.handle("/quit")

    run(main())


def test_showkey_formats_warning_and_audit(run, tmp_path, monkeypatch):
    async def main():
        a, a_out = _mk(tmp_path, "a2")
        b, _ = _mk(tmp_path, "b2")
        await a.start()
        await b.start()
        await a.handle(f"/connect 127.0.0.1 {b.node.port}")
        await asyncio.sleep(0.05)
        peer_b = a.node.get_peers()[0]
        await a.handle(f"/key {peer_b[:8]}")
        entries = a.storage.list_key_history()
        assert entries
        name = entries[0]["name"]

        # declined confirmation: no key material shown, denial audited
        monkeypatch.setattr("builtins.input", lambda *_: "no")
        await a.handle(f"/showkey {name}")
        assert "cancelled" in a_out.getvalue()
        assert "hex:" not in a_out.getvalue()

        monkeypatch.setattr("builtins.input", lambda *_: "YES")
        await a.handle(f"/showkey {name}")
        assert "WARNING" in a_out.getvalue() and "hex:" in a_out.getvalue()
        await a.handle(f"/showkey {name} base64")
        assert "base64:" in a_out.getvalue()
        await a.handle(f"/showkey {name} decimal")
        assert "decimal:" in a_out.getvalue()
        # every access (granted and denied) is in the audit log
        accesses = [e for e in a.secure_logger.get_events(event_type="key_history_access")]
        assert len(accesses) == 4
        assert any(e.get("granted") is False for e in accesses)

        await a.stop()
        await b.stop()

    run(main())


def test_logs_time_and_type_filters(run, tmp_path):
    async def main():
        a, out = _mk(tmp_path, "logsf")
        await a.start()
        # startup line states native-core availability explicitly
        assert "native C++ core:" in out.getvalue()
        a.secure_logger.log_event("connection", peer="x")
        a.secure_logger.log_event("message_sent", peer="x")

        out.truncate(0), out.seek(0)
        await a.handle("/logs connection")
        assert "connection" in out.getvalue() and "message_sent" not in out.getvalue()

        out.truncate(0), out.seek(0)
        await a.handle("/logs --since 1h")
        assert "message_sent" in out.getvalue()

        out.truncate(0), out.seek(0)
        await a.handle("/logs --until 1h")  # everything is newer than 1h ago
        assert "(no events)" in out.getvalue()

        out.truncate(0), out.seek(0)
        await a.handle("/logs --since 23:59 --until 23:59")
        assert "(no events)" in out.getvalue()
        await a.stop()

    run(main())


def test_unknown_command_and_bad_args_keep_repl_alive(run, tmp_path):
    async def main():
        a, out = _mk(tmp_path, "solo")
        await a.start()
        assert await a.handle("/nope")
        assert "unknown command" in out.getvalue()
        assert await a.handle("/connect")  # IndexError -> caught
        assert "error:" in out.getvalue()
        assert await a.handle("not-a-command")
        await a.stop()

    run(main())
