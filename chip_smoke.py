"""Bring-up smoke test: the served handshake path, once, on the chip.

    python chip_smoke.py              # one chip: kernels, width, served
    python chip_smoke.py --chips 4    # four chips: placement + mesh only

One process drives everything (a chip belongs to one process), through the
entry points a deployment uses:

* kernels -- every Pallas launcher of the served path, through its routed
  function, bit-exact against the jnp twin traced in the same process (or
  against hashlib / the ``cryptography`` package);
* width -- one ML-KEM-768 keygen, encaps and decaps flush at
  ``MAX_DEVICE_BATCH`` rows, every row byte-for-byte against the native core;
* served -- ``P2PNode`` + ``SecureMessaging`` stacks over loopback TCP on
  the reference's default suite (ML-KEM-768 + ML-DSA-65 + AES-256-GCM),
  batched on the device, >= 256 concurrent sessions into one hub.  A warm
  round first, then a checked round in which any fallback op, any breaker
  that is not closed, a quarantine, a key mismatch or a lost message fails.

``--chips 4`` runs only what exists across chips and what it is compared
with: the scheduler placing a burst of ML-KEM-768 flushes on every chip
(against one device), and ``mesh_dispatch`` (one ``shard_map`` program over
the chips) of an ML-KEM-768 batch (against one device) and an ML-DSA-65
sign/verify batch (against the native core).

Lines before the last are smoke observations, not metrics.  The last line
is the result; without a TPU the script exits non-zero and prints none.
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import hashlib
import json
import sys
import time

import numpy as np

#: the served phase's flush shape: one bucket, so one compile per program,
#: and the width phase's (kem/mlkem.py MAX_DEVICE_BATCH), so the served
#: ML-KEM programs are the ones that phase compiled
SERVED_BATCH = 1024
CHAT_MESSAGES = 3
BULK_BYTES = 8 * 1024
#: protocol timeout of the warm round only (a program it meets cold compiles
#: inline); the checked round runs at the product's KEY_EXCHANGE_TIMEOUT
WARM_ROUND_TIMEOUT_S = 120.0
#: seconds into a ``--chips 4`` run at which every thread's stack is dumped
CHIPS4_STACKS_AFTER_S = 200


def note(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def timed(label: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    note(f"{label}: {time.perf_counter() - t0:.2f} s")
    return out


def _same(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int((got != want).sum()) if got.shape == want.shape else "shape"
        raise AssertionError(f"{what}: device result differs ({bad})")


# -- kernel phase -------------------------------------------------------------


def _routed_vs_twin(label: str, fn, *args) -> None:
    """``fn(*args)`` with the platform's routing (Pallas on a TPU) against
    the same function traced with the jnp twins.  Each side is a fresh jit;
    the caller clears jit caches after the phase, so no twin trace can
    leak into the served programs."""
    from unittest import mock

    import jax

    from quantum_resistant_p2p_tpu.core import keccak

    got = timed(f"kernel {label}", lambda: jax.tree_util.tree_map(
        np.asarray, jax.jit(lambda *a: fn(*a))(*args)))
    with mock.patch.object(keccak, "_use_pallas", lambda: False):
        want = jax.tree_util.tree_map(
            np.asarray, jax.jit(lambda *a: fn(*a))(*args))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _same(g, w, label)


def kernel_phase(lanes: int = 300) -> None:
    """``lanes`` is deliberately not a multiple of a tile."""
    import jax
    import jax.numpy as jnp

    from quantum_resistant_p2p_tpu.core import keccak, sha256, sha512
    from quantum_resistant_p2p_tpu.kem import frodo, hqc, mlkem
    from quantum_resistant_p2p_tpu.pyref.frodo_ref import NBAR
    from quantum_resistant_p2p_tpu.pyref.frodo_ref import PARAMS as FRODO
    from quantum_resistant_p2p_tpu.pyref.hqc_ref import PARAMS as HQC
    from quantum_resistant_p2p_tpu.sig import mldsa

    if not keccak._use_pallas():
        raise RuntimeError("the Pallas gate is off on this platform")
    rng = np.random.default_rng(2026)

    def u8(*shape):
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))

    # Keccak sponge (2-block squeeze) against hashlib
    msgs = u8(lanes, 64)
    out = timed("kernel keccak sponge", lambda: np.asarray(
        jax.jit(lambda m: keccak.shake256(m, 272))(msgs)))
    want = np.stack([np.frombuffer(hashlib.shake_256(bytes(m)).digest(272),
                                   np.uint8) for m in np.asarray(msgs)])
    _same(out, want, "keccak sponge vs hashlib")

    # the served suite's parameters: ML-KEM-768 (eta 2), ML-DSA-65 (eta 4)
    two = np.arange(2, dtype=np.uint8)
    _routed_vs_twin("mlkem sample_ntt", mlkem.sample_ntt, u8(lanes, 34))
    s = u8(lanes, 32)
    _routed_vs_twin("mlkem cbd", lambda x: mlkem._prf_cbd(x, two, 2), s)
    _routed_vs_twin("mlkem cbd->ntt", lambda x: mlkem._prf_cbd_ntt(x, two, 2),
                    s)
    f = jnp.asarray(rng.integers(0, mlkem.Q, (lanes, 256), dtype=np.int32))
    _routed_vs_twin("mlkem ntt/ntt_inv",
                    lambda x: (mlkem.ntt(x), mlkem.ntt_inv(x)), f)
    _routed_vs_twin("mldsa rej_ntt", mldsa.rej_ntt_poly, u8(lanes, 34))
    _routed_vs_twin("mldsa rej_bounded",
                    lambda x: mldsa.rej_bounded_poly(4, x), u8(lanes, 66))
    g = jnp.asarray(rng.integers(0, mldsa.Q, (lanes, 256), dtype=np.int32))
    _routed_vs_twin("mldsa ntt/ntt_inv",
                    lambda x: (mldsa.ntt(x), mldsa.ntt_inv(x)), g)
    st = jnp.asarray(rng.integers(0, 1 << 32, (lanes, 8), dtype=np.uint32))
    _routed_vs_twin("sha256 compress", sha256.compress, st, u8(lanes, 64))
    st2 = jnp.asarray(rng.integers(0, 1 << 32, (lanes, 8), dtype=np.uint32))
    _routed_vs_twin("sha512 compress",
                    lambda a, b, blk: sha512.compress((a, b), blk),
                    st, st2, u8(lanes, 128))

    # Frodo tiled matmul with inline SHAKE (A never lands in HBM)
    p = FRODO["FrodoKEM-640-SHAKE"]
    seed_a, b = u8(128, 16), 128
    s_mat = jnp.asarray(rng.integers(0, p.q, (b, p.n, NBAR), dtype=np.int32))
    sp = jnp.asarray(rng.integers(0, p.q, (b, NBAR, p.n), dtype=np.int32))
    _routed_vs_twin("frodo A@S",
                    lambda sa, x: frodo._a_times_s(p, sa, x), seed_a, s_mat)
    _routed_vs_twin("frodo S'@A",
                    lambda sa, x: frodo._s_times_a(p, x, sa), seed_a, sp)

    _chacha_check(rng)

    # HQC f32-FFT cyclic product vs the exact Toeplitz form on the
    # precision-worst-case input (all-ones dense row); not a Pallas kernel,
    # but device FFT numerics are what the HQC health gate guards
    hp = HQC["HQC-128"]
    dense = jnp.asarray(np.stack([np.ones(hp.n, np.int32),
                                  rng.integers(0, 2, hp.n, dtype=np.int32)]))
    sup = jnp.asarray(np.stack([
        rng.choice(hp.n, size=hp.w, replace=False).astype(np.int32)
        for _ in range(2)]))
    _same(timed("kernel hqc fft", hqc._cyclic_mul_fft, hp, dense, sup),
          hqc._cyclic_mul_matmul(hp, dense, sup), "hqc fft HQC-128")
    note("kernel phase: all launchers bit-exact")


def _chacha_check(rng) -> None:
    """The ChaCha20-Poly1305 kernel through the batched device AEAD against
    the ``cryptography`` package, ragged lengths across one bucket."""
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305

    from quantum_resistant_p2p_tpu.provider.aead_device import ChaChaPolyDevice

    dev = ChaChaPolyDevice()
    if not dev.use_pallas:
        raise RuntimeError("device AEAD did not select the Pallas kernel")
    n = 256
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    nonces = rng.integers(0, 256, (n, 12), dtype=np.uint8)
    pts = [rng.bytes(int(k)) for k in rng.integers(1, 1024, n)]
    aads = [rng.bytes(int(k)) for k in rng.integers(0, 64, n)]
    sealed = timed("kernel chacha20-poly1305 seal",
                   dev.seal_batch, keys, nonces, pts, aads)
    for i in range(n):
        ref = ChaCha20Poly1305(keys[i].tobytes()).encrypt(
            nonces[i].tobytes(), pts[i], aads[i])
        if bytes(sealed[i]) != ref:
            raise AssertionError(f"chacha20-poly1305 row {i} differs")
    opened = dev.open_batch(keys, nonces, sealed, aads)
    if [bytes(o) for o in opened] != pts:
        raise AssertionError("chacha20-poly1305 open differs")


# -- width phase ----------------------------------------------------------------


def width_phase() -> None:
    """One ML-KEM-768 flush of each op at MAX_DEVICE_BATCH rows."""
    import jax

    from quantum_resistant_p2p_tpu.kem import mlkem
    from quantum_resistant_p2p_tpu.native import NativeMLKEM

    rows = mlkem.MAX_DEVICE_BATCH
    kg, enc, dec = mlkem.get("ML-KEM-768")
    rng = np.random.default_rng(768)
    d, z, m = (rng.integers(0, 256, (rows, 32), dtype=np.uint8)
               for _ in range(3))

    def flush(label: str, fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        note(f"width ML-KEM-768 {label} x{rows}: first call {cold:.2f} s, "
             f"warm call {time.perf_counter() - t0:.4f} s")
        return jax.tree_util.tree_map(np.asarray, out)

    ek, dk = flush("keygen", kg, d, z)
    key, ct = flush("encaps", enc, ek, m)
    key2 = flush("decaps", dec, dk, ct)
    nat = NativeMLKEM("ML-KEM-768")
    for i in range(rows):
        nek, ndk = nat.keygen(d[i].tobytes(), z[i].tobytes())
        nkey, nct = nat.encaps(nek, m[i].tobytes())
        if (bytes(ek[i]), bytes(dk[i])) != (nek, ndk):
            raise AssertionError(f"keygen row {i} differs from native")
        if (bytes(key[i]), bytes(ct[i])) != (nkey, nct):
            raise AssertionError(f"encaps row {i} differs from native")
        if bytes(key2[i]) != nat.decaps(ndk, nct):
            raise AssertionError(f"decaps row {i} differs from native")
    note(f"width phase: {rows} rows x 3 ops byte-identical to the native core")


# -- served phase ---------------------------------------------------------------


def _plane(sessions: int):
    """The swarm's hub-and-clients plane (tools/swarm_bench.SwarmPlane) on
    the default suite, batched on the device at one bucket.

    The providers run without the device operand cache: each of its
    cached-key variants is one more program to compile, and a cold start
    must fit the smoke's budget.  The operand-cache programs are therefore
    not checked on the chip here."""
    from quantum_resistant_p2p_tpu.provider.kem_providers import (
        MLKEMKeyExchange)
    from quantum_resistant_p2p_tpu.provider.sig_providers import (
        MLDSASignature)
    from tools.swarm_bench import SwarmPlane

    return SwarmPlane(
        "tpu", True, SERVED_BATCH, 5.0, batch_floor=SERVED_BATCH,
        hub_max_peers=4 * sessions,
        kem=MLKEMKeyExchange(3, backend="tpu", opcache_size=0),
        signature=MLDSASignature(3, backend="tpu", opcache_size=0))


def _counters(plane) -> dict[str, int]:
    ops = fb = 0
    for e in plane.engines():
        q = e._collect_queues()
        for fam in ("kem_queue", "sig_queue", "fused_queue", "aead_queue"):
            for st in q.get(fam, {}).values():
                ops += st["ops"]
                fb += st["fallback_ops"]
    return {"ops": ops, "fallback_ops": fb}


def _breakers(plane) -> list[str]:
    out = []
    for e in plane.engines():
        if e._scheduler is not None:
            out += [s.breaker.state for s in e._scheduler.shards]
        out += [f.breaker.state for f in (e._bkem, e._bsig, e._bfused, e._baead)
                if f is not None]
    return out


def _warming(plane) -> int:
    from quantum_resistant_p2p_tpu.provider.batched import facade_queues

    return sum(len(q._warming) for e in plane.engines()
               for f in (e._bkem, e._bsig, e._bfused, e._baead) if f is not None
               for q in facade_queues(f))


def _bulk(node_id: str) -> bytes:
    seed = hashlib.sha256(node_id.encode()).digest()
    return (seed * (BULK_BYTES // len(seed) + 1))[:BULK_BYTES]


async def _round(plane, received: dict, tag: str, sessions: int) -> dict:
    """``sessions`` concurrent clients: handshake, chat, one bulk send."""
    from quantum_resistant_p2p_tpu.native import NativeMLDSA

    # client identities are set-up, not the served path: the native core
    # makes them without compiling a keygen program for this shape
    keygen = NativeMLDSA(plane.proto.signature.name).keygen
    clients = [plane.client(f"{tag}{i:04d}", keygen(hashlib.sha256(
        f"{tag}{i:04d}".encode()).digest())) for i in range(sessions)]
    latencies: list[float] = []
    before = _counters(plane)

    async def drive(sm) -> None:
        assert await sm.node.connect_to_peer(
            "127.0.0.1", plane.hub_node.port) == "hub"
        t0 = time.perf_counter()
        if not await sm.initiate_key_exchange("hub"):
            raise RuntimeError(f"{sm.node.node_id}: handshake failed")
        latencies.append(time.perf_counter() - t0)
        for j in range(CHAT_MESSAGES):
            await sm.send_message("hub", b"chat %d from %s" % (
                j, sm.node.node_id.encode()))
        await sm.send_message("hub", _bulk(sm.node.node_id))

    t0 = time.perf_counter()
    results = await asyncio.gather(*(drive(sm) for sm in clients),
                                   return_exceptions=True)
    failures = [r for r in results if isinstance(r, Exception)]
    want = {(sm.node.node_id, p) for sm in clients for p in
            [b"chat %d from %s" % (j, sm.node.node_id.encode())
             for j in range(CHAT_MESSAGES)] + [_bulk(sm.node.node_id)]}
    deadline = time.perf_counter() + 60
    while not want <= received.keys() and time.perf_counter() < deadline:
        await asyncio.sleep(0.05)
    wall = time.perf_counter() - t0
    after = _counters(plane)
    mismatched = [sm.node.node_id for sm in clients
                  if sm.shared_keys.get("hub") is None
                  or sm.shared_keys["hub"]
                  != plane.hub.shared_keys.get(sm.node.node_id)]
    lost = len(want - received.keys())
    for sm in clients:
        await sm.node.stop()
    lat = sorted(latencies)
    stats = {
        "sessions": sessions, "completed": len(lat),
        "failures": len(failures), "key_mismatches": len(mismatched),
        "messages_expected": len(want), "messages_lost": lost,
        "ops": after["ops"] - before["ops"],
        "fallback_ops": after["fallback_ops"] - before["fallback_ops"],
        "wall_s": wall,
        "handshake_p50_s": lat[len(lat) // 2] if lat else None,
    }
    note(f"round {tag}: {json.dumps(stats)}")
    if failures:
        note(f"first failure: {failures[0]!r}")
    return stats


def _check_round(stats: dict, plane) -> None:
    problems = []
    if stats["failures"] or stats["completed"] != stats["sessions"]:
        problems.append(f"{stats['failures']} failed sessions")
    if stats["key_mismatches"]:
        problems.append(f"{stats['key_mismatches']} key mismatches")
    if stats["messages_lost"]:
        problems.append(f"{stats['messages_lost']} messages lost")
    if stats["fallback_ops"]:
        problems.append(f"{stats['fallback_ops']} ops on the fallback")
    if stats["ops"] == 0:
        problems.append("no op reached the device queues")
    states = _breakers(plane)
    if any(s != "closed" for s in states):
        problems.append(f"breakers not closed: {sorted(set(states))}")
    if problems:
        raise AssertionError("checked round: " + "; ".join(problems))


async def _served(sessions: int) -> None:
    from quantum_resistant_p2p_tpu.app import messaging
    from quantum_resistant_p2p_tpu.provider import health

    received: dict[tuple[str, bytes], int] = {}

    def on_msg(peer_id, message):
        if not message.is_system:
            k = (peer_id, bytes(message.content))
            received[k] = received.get(k, 0) + 1

    plane = _plane(sessions)
    t0 = time.perf_counter()
    await plane.start(on_msg)
    note(f"health gate + background warmup: {time.perf_counter() - t0:.1f} s")
    try:
        verdicts = [v for e in plane.engines() for v in health.gate_facades(
            e._bkem, e._bsig, e._bfused, e._baead)]
        bad = [v.as_dict() for v in verdicts if not v.ok]
        if not verdicts or bad:
            raise AssertionError(f"health gate: {bad or 'nothing probed'}")
        note("health gate: " + ", ".join(f"{v.family} ok" for v in verdicts))
        t0 = time.perf_counter()
        sizes = await plane.prewarm(sessions)
        note(f"prewarm buckets {sizes}: {time.perf_counter() - t0:.1f} s")
        # the warm round may still meet a program compiled inline; the
        # checked round runs at the product's protocol timeout
        checked_timeout = messaging.KEY_EXCHANGE_TIMEOUT
        messaging.KEY_EXCHANGE_TIMEOUT = WARM_ROUND_TIMEOUT_S
        try:
            await _round(plane, received, "warm", sessions)
        finally:
            messaging.KEY_EXCHANGE_TIMEOUT = checked_timeout
        while _warming(plane):
            await asyncio.sleep(0.5)
        _check_round(await _round(plane, received, "peer", sessions), plane)
        note(f"served phase: {sessions} sessions checked at a "
             f"{checked_timeout:g} s protocol timeout, 0 fallback ops, "
             f"breakers {sorted(set(_breakers(plane)))}")
    finally:
        await plane.stop()


# -- four chips -------------------------------------------------------------------
#
# ML-KEM-768 encaps runs at one 128-row shape in both phases (placement
# flushes, the one-device reference run slice by slice, each chip's shard of
# the mesh batch), so it is traced once and compiled per placement.


def _per_device(fn, n_dev: int, *arrays):
    """``fn`` on one device over the batch padded as ``mesh_dispatch`` pads
    it, one shard-sized slice at a time; (outputs trimmed, shard rows)."""
    import jax

    from quantum_resistant_p2p_tpu.provider.base import pad_rows
    from quantum_resistant_p2p_tpu.utils import next_pow2

    n = arrays[0].shape[0]
    rows = next_pow2(-(-n // n_dev))
    padded = [pad_rows(np.asarray(a), n_dev * rows) for a in arrays]
    outs = [jax.tree_util.tree_map(np.asarray, fn(
        *(a[i * rows:(i + 1) * rows] for a in padded))) for i in range(n_dev)]
    return jax.tree_util.tree_map(
        lambda *o: np.concatenate(o)[:n], *outs), rows


def _mlkem_inputs(n: int, seed: int):
    from quantum_resistant_p2p_tpu.native import NativeMLKEM

    rng = np.random.default_rng(seed)
    nat = NativeMLKEM("ML-KEM-768")
    ek = np.stack([np.frombuffer(nat.keygen(rng.bytes(32), rng.bytes(32))[0],
                                 np.uint8) for _ in range(n)])
    return ek, rng.integers(0, 256, (n, 32), dtype=np.uint8)


def mesh_phase(n_dev: int) -> None:
    """``mesh_dispatch`` on uneven batches.  ML-KEM-768 encaps is checked
    bit-exact against one device (the per-device program the placement
    phase compiled).  ML-DSA-65 sign and verify are checked bit-exact
    against the native core: a one-device reference would compile the
    sign program a second time, and the core is the independent check."""
    import jax

    from quantum_resistant_p2p_tpu.kem import mlkem
    from quantum_resistant_p2p_tpu.native import NativeMLDSA
    from quantum_resistant_p2p_tpu.parallel.mesh import make_mesh
    from quantum_resistant_p2p_tpu.provider.base import mesh_dispatch
    from quantum_resistant_p2p_tpu.sig import mldsa

    mesh = make_mesh(n_dev)
    _, enc, _ = mlkem.get("ML-KEM-768")
    n = 4 * 64 + 3
    ek, m = _mlkem_inputs(n, 4)
    want, rows = timed("one device ML-KEM-768 encaps", _per_device, enc,
                       n_dev, ek, m)
    got = timed(f"mesh ML-KEM-768 encaps x{n} on {n_dev} devices ({rows} "
                "rows each)", mesh_dispatch, enc, mesh, ek, m)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _same(g, w, "mesh ML-KEM-768 encaps")

    _, sign, verify = mldsa.get("ML-DSA-65")
    rows = 4 * 8 - 3
    rng = np.random.default_rng(65)
    nat = NativeMLDSA("ML-DSA-65")
    keys = [nat.keygen(rng.bytes(32)) for _ in range(rows)]
    pk, sk = (np.stack([np.frombuffer(k[j], np.uint8) for k in keys])
              for j in (0, 1))
    msgs = [rng.bytes(48) for _ in range(rows)]  # M' of FIPS 204 Sign_internal
    mu = np.stack([np.frombuffer(hashlib.shake_256(
        hashlib.shake_256(bytes(pk[i])).digest(64) + msgs[i]).digest(64),
        np.uint8) for i in range(rows)])
    rnd = rng.integers(0, 256, (rows, 32), dtype=np.uint8)
    sig, done = timed(f"mesh ML-DSA-65 sign x{rows} on {n_dev} devices",
                      mesh_dispatch, sign, mesh, sk, mu, rnd)
    if not np.asarray(done).all():
        raise AssertionError("mesh ML-DSA-65 sign left lanes unfinished")
    want_sig = np.stack([np.frombuffer(nat.sign_internal(
        keys[i][1], msgs[i], bytes(rnd[i])), np.uint8) for i in range(rows)])
    _same(sig, want_sig, "mesh ML-DSA-65 sign vs the native core")
    sig = np.array(sig)
    sig[0, 0] ^= 1  # one forged row: verify must say no there, yes elsewhere
    ok = np.asarray(timed(f"mesh ML-DSA-65 verify x{rows} on {n_dev} devices",
                          mesh_dispatch, verify, mesh, pk, mu, sig))
    want_ok = [nat.verify_internal(keys[i][0], msgs[i], bytes(sig[i]))
               for i in range(rows)]
    if ok.tolist() != want_ok or want_ok[0] or not all(want_ok[1:]):
        raise AssertionError(f"mesh ML-DSA-65 verify verdicts wrong: {ok}")
    note(f"mesh phase: {n}-row ML-KEM-768 encaps bit-exact vs one device; "
         f"{rows}-row ML-DSA-65 sign/verify on {n_dev} devices bit-exact vs "
         "the native core")


def placement_phase(n_dev: int, flushes: int = 16) -> None:
    """A burst of ML-KEM-768 encaps flushes placed by the scheduler's
    policy over ``n_dev`` physical shards: every flush bit-exact against
    one device, and flushes landing on every device."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from quantum_resistant_p2p_tpu.kem import mlkem
    from quantum_resistant_p2p_tpu.provider.scheduler import (
        DeviceProgramScheduler)

    _, enc, _ = mlkem.get("ML-KEM-768")
    sched = DeviceProgramScheduler(shards=n_dev)
    rows = 128  # the mesh phase's per-device shape: one trace for both
    ek, m = _mlkem_inputs(rows * flushes, 5)
    ms = [(ek[i * rows:(i + 1) * rows], m[i * rows:(i + 1) * rows])
          for i in range(flushes)]
    want = timed("placement reference on one device", lambda: [
        jax.tree_util.tree_map(np.asarray, enc(*a)) for a in ms])

    def flush(args):
        shard = sched.place()
        try:
            out = shard.run_placed(
                lambda _items: jax.block_until_ready(enc(*args)), [])
            return jax.tree_util.tree_map(np.asarray, out)
        finally:
            sched.done(shard)

    with ThreadPoolExecutor(n_dev) as pool:
        got = timed(f"placement burst {flushes} flushes",
                    lambda: list(pool.map(flush, ms)))
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            _same(a, b, "placed ML-KEM-768 encaps")
    shards = sched.stats()["shards"]
    note("placement: " + json.dumps(
        [(s["device"], s["dispatches"]) for s in shards]))
    used = {s["device"] for s in shards if s["dispatches"]}
    if len(used) != n_dev or None in used:
        raise AssertionError(f"flushes landed on {sorted(map(str, used))}, "
                             f"not on {n_dev} physical devices")
    note(f"placement phase: {flushes} flushes bit-exact on {n_dev} devices")


# -------------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--sessions", type=int, default=256)
    args = ap.parse_args(argv)
    try:
        from quantum_resistant_p2p_tpu.utils.compile_cache import (
            enable_compile_cache)
    except ImportError as e:
        print(f"chip_smoke: the repository is not here ({e})", file=sys.stderr)
        return 2
    import jax

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devs)} device(s))", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    note(f"device: {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
         f"compile cache {cache}")
    t_all = time.perf_counter()
    if args.chips == 1:
        timed("phase kernels", kernel_phase)
        jax.clear_caches()  # no jnp-twin trace survives into served programs
        timed("phase width", width_phase)
        timed("phase served", lambda: asyncio.run(_served(args.sessions)))
    else:
        # the one four-chip call so far (PR 21) printed nothing past the
        # device line in 290 s: a call cut at its limit leaves every
        # thread's stack on stderr
        faulthandler.dump_traceback_later(CHIPS4_STACKS_AFTER_S)
        # placement first: it compiles least, and a budget-cut run still
        # shows flushes on every chip
        timed("phase placement", placement_phase, args.chips)
        timed("phase mesh", mesh_phase, args.chips)
    note(f"total wall {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
