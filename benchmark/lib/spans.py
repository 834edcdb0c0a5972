"""The program's own spans of a measured window, and what they say of each
device flush.

The gateway records a span for each protocol step, queue flush and device
dispatch (``quantum_resistant_p2p_tpu/obs/trace.py``).  A
``device.dispatch`` span of a route that runs a device program
(``device``, ``probe``, ``direct``) carries its stages, measured on the
worker thread: ``queued_ms`` (first submit to the worker's start),
``pack_ms``, ``run_ms`` (launch until the outputs are ready), ``unpack_ms``
and ``cpu_ms`` (the worker thread's CPU time); ``handshake.respond``
carries ``outcome``.  :class:`Recorder` keeps a compact record of each span
that finishes while the measured window is open.  The readers below give
the numbers of those records, or None where the program has no such spans
or attributes (one older than the dispatch stages).

:func:`stage_gaps` ties a profiler trace's idle gaps to the same stages:
the worker opens each as a host annotation ``qrp2p.<layer>.<stage> <queue
label>`` on the trace's clock.
"""

from __future__ import annotations

import numpy as np

from . import trace
from .compiles import LEDGER

#: the routes of a dispatch that runs a device program
DEVICE_ROUTES = ("device", "probe", "direct")
#: the fused queue a responder's ke_init rides (its label ends so)
FUSED_OP = ".encaps_verify_sign"
#: the prefix of the worker's stage annotations
STAGE_PREFIX = "qrp2p."


class Recorder:
    """A listener on the program's tracer: ``(name, dur, attrs)`` of every
    span that finishes while the measured window is open."""

    def __init__(self) -> None:
        self.records: list[dict] = []

    def __call__(self, rec: dict) -> None:
        if LEDGER.window_open:
            self.records.append({"name": rec["name"], "dur": rec["dur"],
                                 "attrs": dict(rec["attrs"])})

    def install(self) -> "Recorder":
        from quantum_resistant_p2p_tpu.obs import trace as obs_trace

        obs_trace.TRACER.add_listener(self)
        return self

    def take(self) -> list[dict]:
        """The records so far, which the recorder then forgets."""
        out, self.records = self.records, []
        return out


def _p50(values: list[float]) -> float | None:
    return float(np.percentile(values, 50)) if values else None


def _dispatches(run: dict, op: str = "") -> list[dict]:
    """The attributes of the window's device-program dispatches of queues
    whose label ends with ``op``."""
    return [s["attrs"] for s in run.get("spans") or ()
            if s["name"] == "device.dispatch"
            and s["attrs"].get("route") in DEVICE_ROUTES
            and str(s["attrs"].get("op", "")).endswith(op)]


def _fused(run: dict, *keys: str) -> list[float]:
    """Per fused dispatch that has every one of ``keys``, their sum."""
    return [sum(a[k] for k in keys) for a in _dispatches(run, FUSED_OP)
            if all(k in a for k in keys)]


def respond_ms(run: dict) -> float | None:
    """p50 of the accepted ``handshake.respond`` spans, in ms: the
    responder's time from a ke_init's arrival to its signed response."""
    return _p50([1e3 * s["dur"] for s in run.get("spans") or ()
                 if s["name"] == "handshake.respond"
                 and s["attrs"].get("outcome") == "accepted"])


def queue_wait_ms(run: dict) -> float | None:
    """p50 of ``queued_ms`` over the fused dispatches: the flush timer and
    the wait for a device-executor thread."""
    return _p50(_fused(run, "queued_ms"))


def device_ms(run: dict) -> float | None:
    """p50 of ``run_ms`` over the fused dispatches."""
    return _p50(_fused(run, "run_ms"))


def host_ms(run: dict) -> float | None:
    """p50 of ``pack_ms + unpack_ms`` over the fused dispatches."""
    return _p50(_fused(run, "pack_ms", "unpack_ms"))


def flush_cpu_ms(run: dict) -> float | None:
    """The worker threads' CPU ms of every device dispatch of the window,
    per genuine handshake completed in it."""
    cpu = [a["cpu_ms"] for a in _dispatches(run) if "cpu_ms" in a]
    done = run.get("handshakes_done_in_window")
    if not cpu or not done:
        return None
    return sum(cpu) / done


#: the metric each reader gives
READERS = {"respond_ms.handshake": respond_ms,
           "queue_wait_ms.fused": queue_wait_ms,
           "device_ms.fused": device_ms,
           "host_ms.fused": host_ms,
           "flush_cpu_ms.handshake": flush_cpu_ms}


def flush_account(run: dict) -> dict:
    """Whether the stages account for the fused flushes: p50 of
    ``queued + pack + run + unpack`` of the dispatches beside p50 of the
    loop's view of the same flushes, ``queue.flush``'s duration plus its
    ``waited_ms``."""
    loop = [1e3 * s["dur"] + s["attrs"]["waited_ms"]
            for s in run.get("spans") or ()
            if s["name"] == "queue.flush"
            and str(s["attrs"].get("op", "")).endswith(FUSED_OP)
            and "waited_ms" in s["attrs"]]
    return {"stages_p50_ms": _p50(_fused(
                run, "queued_ms", "pack_ms", "run_ms", "unpack_ms")),
            "loop_p50_ms": _p50(loop)}


def stage_gaps(planes, min_s: float = 0.005) -> list[dict]:
    """The profiled window's device-idle gaps of at least ``min_s``, each
    with the name ``trace.reduce_planes`` gives it and the worker stage
    annotation that overlaps it most (None where no flush was open)."""
    planes = list(planes)
    lo, hi = trace._window(planes)
    busy = None
    for plane in planes:
        if not plane.name.startswith("/device:") or plane.name.startswith(
                "/device:CUSTOM"):
            continue
        line = trace._op_line(plane)
        if line is not None:
            busy = trace.union([(s, e) for _, s, e in trace._events(line)],
                               lo, hi)
            if busy:
                break
    host = [(n, s, e) for p in planes if not p.name.startswith("/device:")
            for line in p.lines for n, s, e in trace._events(line)
            if n != trace.WINDOW_MARK and e > lo and s < hi]
    out = []
    for s, e in trace.gaps(busy or [], lo, hi):
        if (e - s) * 1e-9 < min_s:
            continue
        over = [(min(e, he) - max(s, hs), n) for n, hs, he in host
                if he > s and hs < e]
        stages = [o for o in over if o[1].startswith(STAGE_PREFIX)]
        out.append({"ms": (e - s) * 1e-6,
                    "label": max(over)[1] if over else "unattributed",
                    "stage": max(stages)[1] if stages else None})
    return out
