"""One client process: the off-chip peers of one share of a run's schedule.

    JAX_PLATFORMS=cpu python benchmark/lib/client.py    (started by the hub)

It never touches the chip: its peers run ``P2PNode`` + ``SecureMessaging``
on the native-core providers.  It talks to the hub process in JSON lines,
one command on stdin and one reply on stdout each:

* ``init`` -- the run's parameters; builds the schedule and its share's
  identities from the seed, replies ``ready``;
* ``setup`` -- what the traffic's kind needs before its first request (its
  ``setup(peers, port)``, for a kind that has one), replies with its counts;
* ``run`` -- sends its share of the schedule open loop from the origin the
  hub gives, each request by its kind's ``send``, waits for every request,
  replies with one record per event;
* ``stop`` -- closes every connection and exits.

A forged request carries a signature with one bit flipped, made where the
client signs; the hub must reject it.  For the events in the check sample
the reply carries what the reference needs: the signed bytes, the
signatures and the KEM operands that crossed the wire.
"""

from __future__ import annotations

import asyncio
import contextvars
import json
import logging
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.lib import schedule, spec  # noqa: E402

#: the event whose request the current task serves (inherited by the
#: connection's reader task, which verifies the response and decapsulates)
EVENT: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "EVENT", default=None)
#: set while the current task sends a forged request
FORGE: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "FORGE", default=False)


def _reply(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


async def _command() -> dict:
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    if not line:
        raise SystemExit(0)
    return json.loads(line)


def _kind(message: bytes) -> str:
    """What a signed transcript is: the ke_init, the ke_confirm or a
    message (the only three things a client signs)."""
    data = json.loads(message)
    if "public_key" in data:
        return "init"
    if "content" in data:
        return "msg"
    return "confirm"


class Peers:
    """The process's share of client identities and their engines."""

    def __init__(self, spec_: dict) -> None:
        from quantum_resistant_p2p_tpu.provider.registry import (
            get_kem, get_signature, get_symmetric)

        self.seed = int(spec_["seed"])
        suite = spec_["suite"]
        self.kem = get_kem(suite["kem"], "cpu")
        self.sig = get_signature(suite["signature"], "cpu")
        self.aead = get_symmetric(suite["aead"])
        self.traffic = spec_["traffic"]
        self.kind = spec.kind(self.traffic["kind"])
        self.events = schedule.build(self.traffic, self.seed,
                                     float(spec_["seconds"]))
        self.sample = schedule.check_sample(self.traffic, self.seed,
                                            self.events)
        procs, me = int(spec_["procs"]), int(spec_["proc"])
        self.mine = [e for e in self.events
                     if self.kind.sender(e) % procs == me]
        family = spec.suite({"suite": suite})
        self.keys = {s: family.identity(suite, schedule.identity_seed(
            self.seed, s)) for s in sorted({e.session for e in self.mine})}
        #: a request not done this long after it was due has failed
        self.give_up_s = float(spec_["give_up_s"])
        self.engines: dict[int, object] = {}
        #: event index -> what the reference needs (sampled events only)
        self.records: dict[int, dict] = {}

    def engine(self, session: int):
        from quantum_resistant_p2p_tpu.app.messaging import SecureMessaging
        from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode

        node = P2PNode(node_id=schedule.node_id(self.seed, session),
                       host="127.0.0.1", port=0)
        sm = SecureMessaging(node, kem=self.kem, signature=self.sig,
                             symmetric=self.aead, backend="cpu",
                             sig_keypair=self.keys[session])
        self._instrument(sm)
        self.engines[session] = sm
        return sm

    @staticmethod
    def serve(event) -> None:
        """The current task (and the connection tasks it starts) serves
        ``event``: it forges where the event is forged, and records the
        operands of a sampled event."""
        EVENT.set(event.index)
        FORGE.set(event.forged)

    @staticmethod
    async def until(t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            await asyncio.sleep(dt)

    async def open_session(self, session: int, port: int,
                           retries: int | None = None) -> tuple[bool, object]:
        """Connect identity ``session`` to the hub and run the handshake."""
        sm = self.engine(session)
        if await sm.node.connect_to_peer("127.0.0.1", port) != "hub":
            return False, sm
        kw = {} if retries is None else {"retries": retries}
        return await sm.initiate_key_exchange("hub", **kw), sm

    def _record(self, key: str, value) -> None:
        ev = EVENT.get()
        if ev is not None and ev in self.sample:
            self.records.setdefault(ev, {}).setdefault(key, []).append(value)

    def _instrument(self, sm) -> None:
        """Wrap the engine's signing, verifying and decapsulating seams:
        forge where the request asks for it, and keep the sampled events'
        operands for the reference."""
        sign, verify, decaps = sm._sign, sm._verify, sm._kem_decaps

        async def _sign(message, *a, **kw):
            sig = await sign(message, *a, **kw)
            if FORGE.get():
                sig = bytes([sig[0] ^ 1]) + bytes(sig[1:])
            self._record(_kind(message), [bytes(message).hex(), sig.hex()])
            return sig

        async def _verify(algo, pk, message, sig, *a, **kw):
            ok = await verify(algo, pk, message, sig, *a, **kw)
            self._record("verify", [bytes(pk).hex(), bytes(message).hex(),
                                    bytes(sig).hex(), ok])
            return ok

        async def _decaps(sk, ct, *a, **kw):
            self._record("decaps", [bytes(sk).hex(), bytes(ct).hex()])
            return await decaps(sk, ct, *a, **kw)

        sm._sign, sm._verify, sm._kem_decaps = _sign, _verify, _decaps


async def main() -> None:
    logging.basicConfig(level=logging.ERROR)
    spec = (await _command())["init"]
    peers = Peers(spec)
    port = int(spec["port"])
    _reply({"ready": len(peers.mine)})
    while True:
        cmd = await _command()
        if "setup" in cmd:
            _reply(await peers.kind.setup(peers, port))
        elif "run" in cmd:
            records = list(await asyncio.gather(*(
                peers.kind.send(peers, e, float(cmd["run"]), port)
                for e in peers.mine)))
            _reply({"events": records, "sample": {
                str(k): v for k, v in peers.records.items()}})
        elif "stop" in cmd:
            for sm in peers.engines.values():
                await sm.node.stop()
            _reply({"stopped": True})
            return


if __name__ == "__main__":
    asyncio.run(main())
