"""handshake_open: every request is a new identity that connects to the hub,
runs the full handshake (``ke_init``, signed ``ke_response``,
``ke_confirm``), sends one signed message of ``message_bytes`` and closes.

A request's latency runs from its scheduled send to a usable session on
the client.  A forged request's ``ke_init`` carries a signature with one
bit flipped; the hub must refuse the session.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

from benchmark.lib import check, schedule


def draw(traffic: dict, seed: int, segment: int, n: int,
         first_index: int) -> tuple[np.ndarray, np.ndarray]:
    """Sizes and sessions of a segment's ``n`` requests: one message of the
    traffic's size each, and a new identity each."""
    return (np.full(n, int(traffic["message_bytes"]), np.int64),
            first_index + np.arange(n))


def sender(event) -> int:
    """What decides the client process that sends ``event``."""
    return event.index


async def send(peers, event, t0: float, port: int) -> dict:
    """Client side: one new session, timed from its due time."""
    peers.serve(event)
    await peers.until(t0 + event.due)
    out = {"i": event.index, "late": time.monotonic() - (t0 + event.due),
           "done": None}
    sm = None
    try:
        # a forged ke_init is refused on its first attempt; retrying it
        # would only repeat the same refusal
        ok, sm = await asyncio.wait_for(
            peers.open_session(event.session, port,
                               0 if event.forged else None),
            peers.give_up_s)
        if ok:
            out["done"] = time.monotonic() - (t0 + event.due)
            key = sm.shared_keys.get("hub")
            out["key"] = key.hex() if key is not None else None
            msg = await sm.send_message("hub", schedule.payload(
                peers.seed, event.index, event.size))
            out["msg_sent"] = msg is not None
        out["status"] = "ok" if ok else "failed"
    except Exception as ex:  # the record says what went wrong
        out["status"] = f"error: {ex!r}"[:200]
    # stay connected long enough for the hub to read the message
    await asyncio.sleep(float(peers.traffic.get("linger_s", 1.0)))
    if sm is not None:
        await sm.node.stop()
    return out


def awaits_delivery(event, record: dict) -> bool:
    """Whether the hub waits for ``event``'s message after the window."""
    return not event.forged and bool(record.get("msg_sent"))


def summary(run, give_up_s: float, timeout_s: float) -> dict:
    """The run's latencies and completions as the metric readers see them:
    every genuine handshake due in the window, one never done counted at
    ``give_up_s``; ``late`` those not done within ``timeout_s``."""
    ws, we = run.window
    lat, done_in_window, late = [], 0, 0
    for e in run.events:
        if e.forged:
            continue
        done = (run.records.get(e.index) or {}).get("done")
        if done is not None and ws <= run.t0 + e.due + done <= we:
            done_in_window += 1
        if e.in_window:
            lat.append(done if done is not None else give_up_s)
            late += done is None or done > timeout_s
    return {"handshake_latency_s": lat,
            "handshakes_done_in_window": done_in_window, "late": late}


def checks(run, ref, seed: int, counts: dict) -> None:
    """Add this kind's counts of wrong answers to ``counts``: every genuine
    session of the window and its message, then the reference's re-check
    of the sample (the ``ke_init`` verdict, the signed ``ke_response``, the
    session key from the reference's decapsulation, the message verdict)."""
    window = [e for e in run.events if e.in_window]
    counts.update(sessions_lost=0, key_disagree=0, kem_key_mismatch=0,
                  resp_sig_invalid=0, init_verdict_mismatch=0)
    delivered = []
    for e in window:
        rec = run.records.get(e.index) or {}
        nid = schedule.node_id(seed, e.session)
        if e.forged:
            counts["forged_accepted"] += (nid in run.hub_keys
                                          or rec.get("done") is not None)
            continue
        if rec.get("done") is None:
            counts["sessions_lost"] += 1
            continue
        hub_key = run.hub_keys.get(nid)
        if hub_key is None or rec.get("key") != hub_key.hex():
            counts["key_disagree"] += 1
        if rec.get("msg_sent"):
            delivered.append(e)
    check.message_checks(run, seed, delivered, counts)
    for e in window:
        if e.index not in run.sample:
            continue
        rec = run.client_samples.get(e.index)
        nid = schedule.node_id(seed, e.session)
        init = check.first(rec, "init")
        if init is None:
            counts["unchecked_sample"] += 1
            continue
        valid = ref.verify(ref.client_pk(e.session), *init)
        if valid != (nid in run.hub_keys):
            counts["init_verdict_mismatch"] += 1
        if e.forged:
            continue
        resp, dec = check.first(rec, "verify"), check.first(rec, "decaps")
        if resp is None or dec is None:
            counts["unchecked_sample"] += 1
            continue
        pk_hex, signed_hex, sig_hex, _ = resp
        ct_signed = json.loads(bytes.fromhex(signed_hex))["ciphertext"]
        if (bytes.fromhex(pk_hex) != ref.hub_pk or ct_signed != dec[1]
                or not ref.verify(ref.hub_pk, signed_hex, sig_hex)):
            counts["resp_sig_invalid"] += 1
        if run.hub_keys.get(nid) != ref.session_key(nid, dec[0], dec[1]):
            counts["kem_key_mismatch"] += 1
        msg = check.first(rec, "msg")
        if msg is None:
            counts["unchecked_sample"] += 1
            continue
        valid = ref.verify(ref.client_pk(e.session), *msg)
        if valid != (e.index in run.delivered):
            counts["msg_verdict_mismatch"] += 1
