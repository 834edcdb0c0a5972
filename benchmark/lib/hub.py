"""The hub process: the gateway under test, the only process on the chip.

One hub is ``P2PNode`` on loopback TCP and ``SecureMessaging`` with the
configuration's suite on the TPU backend, batched at the configuration's
flush shape.  Client processes (``client.py``) are its off-chip peers.  A
run, in order: start the clients (they build their identities while the
hub compiles), build the engine and warm the programs a responding hub
runs (:func:`warm`), set up what the traffic's kind needs, send the
warm-up segment and then the measured window open loop, drain the
requests due in the window, read the device's peak memory, stop, and hand
the records to the check and the metric readers.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import schedule, spec, trace
from .compiles import LEDGER
from .spec import ROOT

CLIENT = Path(__file__).resolve().parent / "client.py"
#: a request not done this long after it was due has failed (its latency
#: counts as this), and the hub waits this long after the window closed
#: for the window's messages
GIVE_UP_S = 60.0
#: the profiled sub-window of a traced run, around the window's last
#: arrival that leaves room for it
PROFILE_S = 0.5
#: the sub-window opens this long before that arrival's due time
PROFILE_LEAD_S = 0.2
#: the protocol's handshake timeout (app/messaging.py KEY_EXCHANGE_TIMEOUT):
#: a handshake slower than this has failed for its user
HANDSHAKE_TIMEOUT_S = 20.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def build_engine(config: dict, seed: int, node):
    """The configuration's gateway engine on ``node``, with the engine's
    own background warm-up (the device-health gate, then every program of
    every family at the warm-up sizes) held back: :func:`warm` compiles
    what this hub runs in its place."""
    from quantum_resistant_p2p_tpu.app.messaging import SecureMessaging
    from quantum_resistant_p2p_tpu.provider.registry import get_symmetric

    suite, hub = config["suite"], config["hub"]
    backend = hub["backend"]
    family = spec.suite(config)
    kem, sig = family.providers(suite, backend, hub["opcache_size"])
    # a gateway loads its long-lived identity; it never makes one per start
    identity = family.identity(suite, schedule.hub_identity_seed(seed))
    spawn = SecureMessaging._spawn_warmup
    SecureMessaging._spawn_warmup = lambda self, *a, **kw: None
    try:
        return SecureMessaging(
            node, kem=kem, signature=sig,
            symmetric=get_symmetric(suite["aead"]), backend=backend,
            use_batching=hub["use_batching"], max_batch=hub["max_batch"],
            max_wait_ms=hub["max_wait_ms"], batch_floor=hub["batch_floor"],
            autotune=hub["autotune"], sig_keypair=identity)
    finally:
        SecureMessaging._spawn_warmup = spawn


def _warm_items(engine) -> dict:
    """One valid operand of each queue a responding hub drives: a signed
    ``ke_init`` for the fused ``encaps_verify_sign`` program and a signed
    transcript for ``verify``, made on the native core."""
    from quantum_resistant_p2p_tpu.app.messaging import _canonical
    from quantum_resistant_p2p_tpu.native import NativeMLKEM
    from quantum_resistant_p2p_tpu.provider.registry import get_signature

    kem, sig = engine.kem, engine.signature
    ek, _ = NativeMLKEM(kem.name).keygen(bytes(32), bytes(range(32)))
    signer = get_signature(sig.name, "cpu")
    spk, ssk = signer.generate_keypair()
    init = _canonical({"message_id": "warm", "kem": kem.name,
                       "aead": engine.symmetric.name, "public_key": ek.hex(),
                       "sender": "warm", "recipient": engine.node_id,
                       "timestamp": time.time()})
    resp = _canonical({"message_id": "warm",
                       "ciphertext": "0" * (2 * kem.ciphertext_len),
                       "sender": engine.node_id, "recipient": "warm",
                       "timestamp": time.time()})
    return {"fused.encaps_verify_sign": (
                ek, spk, init, signer.sign(ssk, init),
                engine._sig_keypair[1], resp),
            "sig.verify": (spk, init, signer.sign(ssk, init))}


#: the queues a responding hub drives, by the engine's facade and its
#: queue attribute
RESPONDER_QUEUES = {"fused.encaps_verify_sign": ("_bfused", "_enc"),
                    "sig.verify": ("_bsig", "_verify")}


def warm(engine) -> dict:
    """Blocking: each queue a responding hub drives (the fused
    ``encaps_verify_sign`` and ``verify``) compiled and run at its flush
    shape, twice, and marked warm; seconds of each.  A queue that is not
    there raises, so that no compile can move into the window unseen.  A
    hub on the CPU backend (the tests') has no device program to warm."""
    if engine.backend != "tpu":
        return {}
    queues = {}
    for name, (facade, attr) in RESPONDER_QUEUES.items():
        queues[name] = getattr(getattr(engine, facade, None), attr, None)
        if queues[name] is None:
            raise RuntimeError(f"the engine has no {name} queue "
                               f"(engine.{facade}.{attr})")
    items = _warm_items(engine)
    out = {}
    for name, q in queues.items():
        t = time.monotonic()
        for _ in range(2):
            q.batch_fn([items[name]])
        q.mark_warm(q.bucket_floor)
        out[name] = time.monotonic() - t
    return out


def queue_counts(engine) -> dict:
    """Every device queue's counters, by ``family.op``."""
    out = {}
    for fam, ops in engine._collect_queues().items():
        if isinstance(ops, dict) and fam.endswith("_queue"):
            for op, st in ops.items():
                out[f"{fam[:-6]}.{op}"] = {k: st[k] for k in (
                    "ops", "flushes", "fallback_ops", "fallback_flushes",
                    "breaker_trips")}
    return out


def queue_latency(engine) -> dict:
    """Each busy queue's flush latency percentiles (ms, since start):
    on the worker (the program) and from the loop (with queueing)."""
    keys = ("p50_device_ms", "p99_device_ms", "p50_dispatch_ms",
            "p99_dispatch_ms")
    return {f"{fam[:-6]}.{op}": {k: st[k] for k in keys}
            for fam, ops in engine._collect_queues().items()
            if isinstance(ops, dict) and fam.endswith("_queue")
            for op, st in ops.items() if st["flushes"]}


def _delta(after: dict, before: dict) -> dict:
    return {q: {k: v - before.get(q, {}).get(k, 0) for k, v in c.items()}
            for q, c in after.items()}


class Clients:
    """The client processes of one run, in JSON lines over pipes."""

    def __init__(self, procs: list) -> None:
        self.procs = procs

    @classmethod
    async def start(cls, n: int, spec: dict) -> "Clients":
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
        procs = [await asyncio.create_subprocess_exec(
            sys.executable, str(CLIENT), stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, env=env, limit=1 << 28)
            for _ in range(n)]
        self = cls(procs)
        await self.ask([{"init": dict(spec, proc=i, procs=n)}
                        for i in range(n)])
        return self

    async def ask(self, commands: list[dict]) -> list[dict]:
        """One command to each process (or the same to all); their replies."""
        if len(commands) == 1:
            commands = commands * len(self.procs)
        for p, c in zip(self.procs, commands):
            p.stdin.write((json.dumps(c) + "\n").encode())
            await p.stdin.drain()

        async def reply(p) -> dict:
            line = await p.stdout.readline()
            if not line:
                raise RuntimeError(f"client process {p.pid} ended "
                                   f"(exit {await p.wait()})")
            return json.loads(line)

        return list(await asyncio.gather(*(reply(p) for p in self.procs)))

    async def stop(self) -> None:
        try:
            await asyncio.wait_for(self.ask([{"stop": True}]), 60)
        except Exception:
            pass
        for p in self.procs:
            if p.returncode is None:
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    p.kill()
                    await p.wait()


class Profiler:
    """The traced run's profiled sub-window, in a directory of its own.

    The window is a host mark on a thread of its own, opened after
    ``start_trace`` returned and closed at the sub-window's end.
    ``stop_trace`` takes longer the more device work it recorded (on one
    v5e, 19–46 s after a traced run of ``d1-handshake-open``, 0.25 s with
    the device idle) and stalls the device meanwhile, so the sub-window is
    short and the stop waits for the run's traffic."""

    def __init__(self, seed: int) -> None:
        self.dir = tempfile.mkdtemp(
            prefix=f"qrp2p-bench-trace-{seed}-{os.getpid()}-")
        self._done = threading.Event()
        self._marked = threading.Event()
        self.t0 = self.t1 = 0.0

    def _mark(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
            self.t0 = time.monotonic()
            self._marked.set()
            self._done.wait()
            self.t1 = time.monotonic()

    def start(self) -> None:
        """Blocking: start the profiler, then open the window mark."""
        import jax

        t = time.monotonic()
        opts = jax.profiler.ProfileOptions()
        # host events: the window mark and annotations only, no Python
        # function tracing; no HLO of the (large) fused programs
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.start_s = time.monotonic() - t
        self._thread = threading.Thread(target=self._mark, daemon=True)
        self._thread.start()
        self._marked.wait()

    def end_window(self) -> None:
        """Close the window mark (the profiler keeps running)."""
        self._done.set()
        self._thread.join()

    def stop(self) -> None:
        """Blocking: stop the profiler."""
        import jax

        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t

    def reduce(self) -> dict:
        """Read the one trace this run wrote, then delete its directory."""
        try:
            return trace.reduce_file(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Run:
    """What one run measured: the raw material of the check and of every
    metric reader."""

    kind: str
    seconds: float
    events: list
    sample: set
    window: tuple[float, float]
    t0: float
    records: dict = field(default_factory=dict)
    client_samples: dict = field(default_factory=dict)
    delivered: dict = field(default_factory=dict)
    hub_keys: dict = field(default_factory=dict)
    queues: dict = field(default_factory=dict)
    fallback_ops: int = 0
    #: the hub process's CPU seconds (every thread) over the window
    cpu_s: float = 0.0
    profile: dict | None = None
    setup_s: float | None = None
    #: the clients' replies to the kind's set-up, for a kind that has one
    prepared: list | None = None


class Hub:
    """The gateway under test and its listener's record of deliveries."""

    def __init__(self, config: dict, seed: int) -> None:
        self.config, self.seed = config, seed
        self.delivered: dict[int, tuple[str, bytes, float]] = {}
        self.extra: list[tuple[str, bytes, float]] = []

    def _on_message(self, peer_id: str, message) -> None:
        if message.is_system:
            return
        now = time.monotonic()
        content = bytes(message.content)
        head = content.split(b":", 1)[0]
        if head.isdigit() and int(head) not in self.delivered:
            self.delivered[int(head)] = (peer_id, content, now)
        else:
            self.extra.append((peer_id, content, now))

    async def start(self) -> int:
        from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode

        self.node = P2PNode(node_id="hub", host="127.0.0.1", port=0)
        await self.node.start()
        return self.node.port

    def build(self) -> None:
        """Construct the engine; its warm-up starts in the background."""
        self.engine = build_engine(self.config, self.seed, self.node)
        self.engine.register_message_listener(self._on_message)

    async def stop(self) -> None:
        await self.node.stop()


async def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        await asyncio.sleep(dt)


def profiled_start(events: list, t0: float, window_end: float) -> float:
    """When a traced run's sub-window opens: shortly before the window's
    last arrival that is due at least ``PROFILE_S - PROFILE_LEAD_S`` before
    the window closes, so that the sub-window lies inside the window and
    holds the device work of at least that request."""
    room = PROFILE_S - PROFILE_LEAD_S
    due = max((t0 + e.due for e in events
               if e.in_window and t0 + e.due <= window_end - room),
              default=window_end - room)
    return due - PROFILE_LEAD_S


async def drive(hub: Hub, clients: Clients, traffic: dict, seed: int,
                seconds: float, profile: bool, t_start: float | None = None
                ) -> Run:
    """One run's traffic through a warm ``hub``: the kind's set-up, then
    warm-up and window open loop, then drain."""
    events = schedule.build(traffic, seed, seconds)
    kind = spec.kind(traffic["kind"])
    run = Run(traffic["kind"], seconds, events,
              schedule.check_sample(traffic, seed, events), (0.0, 0.0), 0.0)
    if hasattr(kind, "setup"):
        run.prepared = await clients.ask([{"setup": True}])
    loop = asyncio.get_running_loop()
    before_all = queue_counts(hub.engine)
    t0 = time.monotonic() + 0.5
    run.t0 = t0
    ws = t0 + float(traffic["warmup_s"])
    run.window = (ws, ws + seconds)
    if t_start is not None:
        run.setup_s = ws - t_start
    replies_task = asyncio.ensure_future(clients.ask([{"run": t0}]))
    await _sleep_until(ws)
    LEDGER.window_open = True
    q0 = queue_counts(hub.engine)
    cpu0 = time.process_time()
    prof = None
    if profile:
        # the profiler is stopped only once the run's requests are done:
        # its stop stalls the device, and takes longer the more device
        # work it recorded
        await _sleep_until(profiled_start(events, t0, ws + seconds))
        prof = Profiler(seed)
        await loop.run_in_executor(None, prof.start)
        await _sleep_until(prof.t0 + PROFILE_S)
        prof.end_window()
    await _sleep_until(ws + seconds)
    run.cpu_s = time.process_time() - cpu0
    LEDGER.window_open = False
    run.queues = _delta(queue_counts(hub.engine), q0)
    replies = await replies_task
    for r in replies:
        for rec in r["events"]:
            run.records[rec["i"]] = rec
        run.client_samples.update({int(k): v for k, v in r["sample"].items()})
    # every message of the window that was sent is waited for
    due = {e.index for e in events if e.in_window and kind.awaits_delivery(
        e, run.records.get(e.index, {}))}
    deadline = ws + seconds + GIVE_UP_S
    while not due <= hub.delivered.keys() and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
    # forged messages may still be in the verify queue: give them the same
    # chance to be (wrongly) delivered
    await asyncio.sleep(0.5)
    run.delivered = dict(hub.delivered)
    hub.delivered.clear()
    run.hub_keys = {pid: bytes(k) for pid, k in hub.engine.shared_keys.items()}
    run.fallback_ops = sum(c["fallback_ops"] for c in _delta(
        queue_counts(hub.engine), before_all).values())
    if prof is not None:
        await loop.run_in_executor(None, prof.stop)
        t = time.monotonic()
        run.profile = await loop.run_in_executor(None, prof.reduce)
        log(f"profile: start {prof.start_s:.2f} s, stop {prof.stop_s:.2f} s, "
            f"read and reduced in {time.monotonic() - t:.2f} s, window "
            f"{prof.t0 - ws:.3f}..{prof.t1 - ws:.3f} s into the window")
    return run


def lateness(run: Run) -> dict:
    """How late the clients sent the window's requests."""
    late = np.array([run.records[e.index]["late"] for e in run.events
                     if e.in_window and e.index in run.records])
    if late.size == 0:
        return {"p50_ms": None, "p99_ms": None, "max_ms": None}
    return {"p50_ms": float(np.percentile(late, 50) * 1e3),
            "p99_ms": float(np.percentile(late, 99) * 1e3),
            "max_ms": float(late.max() * 1e3)}


def memory_peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0
