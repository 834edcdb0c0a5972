"""Reduction of one profiler trace to the device numbers of a traced run.

The hub marks the profiled sub-window with a host annotation
(:data:`WINDOW_MARK`) on a thread of its own, begun after ``start_trace``
returned and ended before ``stop_trace`` is called.  Its start and end, on
the trace's own clock, bound the window:

* ``window_s`` -- the mark's length;
* ``busy_s`` -- the union of the device-plane operation intervals, clipped
  to the window, averaged over the chips that ran anything, so
  ``0 < busy_s <= window_s``;
* ``device_ops`` -- the operations that took most device time in it;
* ``idle_gaps`` -- its longest device-idle gaps, each named by the longest
  host event that overlaps it, or ``unattributed``.

A trace with no device plane, no device operation in the window or no
mark raises :class:`TraceError`: a traced run never reports without them.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

WINDOW_MARK = "qrp2p_bench.profiled_window"
#: the device-plane lines that hold one event per executed operation, in
#: order of preference (a TPU plane has "XLA Ops"; "XLA Modules" holds
#: whole programs)
OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10


class TraceError(RuntimeError):
    """The trace cannot give the traced run's device numbers."""


def union(intervals: list[tuple[float, float]], lo: float,
          hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, clipped to ``[lo, hi]``, as
    disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def _op_name(name: str) -> str:
    """An operation's name without its HLO signature (``%while.218 =
    (...) while(...)`` is ``%while.218``)."""
    return name.split(" = ", 1)[0]


def _window(planes) -> tuple[float, float]:
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW_MARK:
                    return s, e
    raise TraceError(f"no {WINDOW_MARK!r} host event in the trace")


def _op_line(plane):
    lines = {line.name: line for line in plane.lines}
    for name in OP_LINES:
        if name in lines:
            return lines[name]
    return None


def reduce_planes(planes) -> dict:
    """The reduction over ``planes`` (objects with ``name`` and ``lines``;
    each line has ``name`` and ``events`` with ``name``, ``start_ns`` and
    ``duration_ns``, as ``jax.profiler.ProfileData`` gives them)."""
    planes = list(planes)
    lo, hi = _window(planes)
    if hi <= lo:
        raise TraceError(f"the profiled window is empty ({lo}..{hi} ns)")
    devices = [p for p in planes if p.name.startswith("/device:")
               and not p.name.startswith("/device:CUSTOM")]
    if not devices:
        raise TraceError("the trace has no device plane: planes "
                         + ", ".join(p.name for p in planes))
    busy_ns, per_op = [], defaultdict(float)
    first_busy = None
    for plane in devices:
        line = _op_line(plane)
        if line is None:
            continue
        spans = []
        for name, s, e in _events(line):
            s2, e2 = max(s, lo), min(e, hi)
            if e2 > s2:
                spans.append((s, e))
                per_op[_op_name(name)] += (e2 - s2) * 1e-9
        merged = union(spans, lo, hi)
        if merged:
            busy_ns.append(sum(e - s for s, e in merged))
            if first_busy is None:
                first_busy = merged
    if not busy_ns:
        raise TraceError(
            "no device operation in the profiled window: device planes "
            + "; ".join(f"{p.name} [{', '.join(l.name for l in p.lines)}]"
                        for p in devices))
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    host = [(n, s, e) for p in planes if not p.name.startswith("/device:")
            for line in p.lines for n, s, e in _events(line)
            if n != WINDOW_MARK and e > lo and s < hi]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    labelled = []
    for s, e in idle:
        over = [(min(e, he) - max(s, hs), n) for n, hs, he in host
                if he > s and hs < e]
        labelled.append([max(over)[1] if over else "unattributed",
                         (e - s) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": window_s, "busy_s": busy_s,
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": labelled}


def reduce_file(path: str | Path) -> dict:
    """:func:`reduce_planes` over one ``.xplane.pb`` file; its size in
    bytes comes back as ``trace_bytes``."""
    from jax.profiler import ProfileData

    out = reduce_planes(ProfileData.from_file(str(path)).planes)
    out["trace_bytes"] = Path(path).stat().st_size
    return out


def find_xplane(directory: str | Path) -> Path:
    """The one ``.xplane.pb`` that a ``start_trace(directory)`` wrote."""
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise TraceError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(found)}")
    return found[0]
