"""A device flush's stages on its ``device.dispatch`` span (queued, pack,
run, unpack, thread CPU) and their profiler annotations, on the CPU
backend with a tiny jitted program."""

from __future__ import annotations

import asyncio

import jax
import numpy as np
import pytest

from quantum_resistant_p2p_tpu.obs import trace as obs_trace
from quantum_resistant_p2p_tpu.provider.base import sliced_dispatch
from quantum_resistant_p2p_tpu.provider.batched import (STAGE_ANNOTATIONS,
                                                        Breaker, OpQueue)

STAGES = ("pack", "run", "unpack")


@jax.jit
def _double(x):
    return x * 2 + 1


def _batch_fn(items):
    """Pack the items, run the program through sliced_dispatch, unpack."""
    rows = np.stack([np.full(4, it, np.int32) for it in items])
    out = sliced_dispatch(_double, 8, rows)
    return [int(r[0]) for r in out]


def _flush(items, label="toy.double", fallback=None):
    """One flush of ``items`` through a warm queue; the spans it made."""
    q = OpQueue(_batch_fn, max_batch=64, max_wait_ms=1.0, label=label,
                fallback_fn=fallback, breaker=Breaker())
    q.mark_warm(1)
    for b in (2, 4, 8):
        q.mark_warm(b)
    _batch_fn([0])  # compile outside the measured flush

    async def main():
        return await asyncio.gather(*(q.submit(it) for it in items))

    obs_trace.TRACER.reset()
    loop = asyncio.new_event_loop()
    try:
        got = loop.run_until_complete(main())
    finally:
        loop.close()
    assert got == [2 * it + 1 for it in items]
    return obs_trace.TRACER.snapshot()


@pytest.mark.parametrize("fallback", [None, _batch_fn],
                         ids=["direct", "breaker"])
def test_flush_gives_one_dispatch_with_its_stages(fallback):
    spans = _flush([1, 2, 3], fallback=fallback)
    (d,) = [s for s in spans if s["name"] == "device.dispatch"]
    (f,) = [s for s in spans if s["name"] == "queue.flush"]
    a = d["attrs"]
    assert a["route"] == ("direct" if fallback is None else "device")
    for key in ("queued_ms", "cpu_ms") + tuple(f"{s}_ms" for s in STAGES):
        assert a[key] >= 0.0, key
    # the stages partition the span, to float rounding
    assert sum(a[f"{s}_ms"] for s in STAGES) == pytest.approx(
        d["dur"] * 1e3, abs=1e-6)
    # queued covers the flush's wait for its timer, and the worker starts
    # after the flush did
    assert a["queued_ms"] >= f["attrs"]["waited_ms"]
    assert d["parent_id"] == f["span_id"]


def test_fallback_and_warmup_dispatches_have_no_stages():
    q = OpQueue(_batch_fn, max_batch=64, max_wait_ms=1.0, label="toy.cold",
                fallback_fn=_batch_fn, breaker=Breaker())

    async def main():
        out = await q.submit(5)
        for _ in range(200):  # the cold bucket's background warm-up
            if 1 in q._warm_buckets:
                break
            await asyncio.sleep(0.01)
        return out

    obs_trace.TRACER.reset()
    loop = asyncio.new_event_loop()
    try:
        assert loop.run_until_complete(main()) == 11
    finally:
        loop.close()
    spans = obs_trace.TRACER.snapshot()
    routes = {s["attrs"].get("route") for s in spans
              if s["name"] in ("device.dispatch", "fallback.dispatch")}
    assert routes == {"fallback", "warmup"}
    for s in spans:
        assert not {"queued_ms", "cpu_ms", "pack_ms", "run_ms",
                    "unpack_ms"} & set(s["attrs"]), s["name"]


def test_profiler_holds_the_stage_annotations_in_order(tmp_path):
    """On one host thread of a profiler trace: pack, run, unpack of the
    flush, named after its queue, one after the other."""
    label = "toy.annotated"
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans = _flush([7, 8], label=label)
    finally:
        jax.profiler.stop_trace()
    (d,) = [s for s in spans if s["name"] == "device.dispatch"]
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {f"{STAGE_ANNOTATIONS[s]} {label}": s for s in STAGES}
    lines = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.duration_ns, names[ev.name])
                   for ev in line.events if ev.name in names]
            if evs:
                lines.append(sorted(evs))
    (evs,) = lines  # one thread holds them all
    assert [s for _, _, s in evs] == list(STAGES)
    for (s0, d0, _), (s1, _, _) in zip(evs, evs[1:]):
        assert s0 + d0 <= s1
    # each annotation lasts about as long as the span's stage
    for _, dur, stage in evs:
        assert dur * 1e-6 == pytest.approx(d["attrs"][f"{stage}_ms"],
                                           abs=5.0)


def test_sliced_dispatch_with_several_slices_keeps_the_partition():
    """Five slices of two rows: run and unpack alternate, and the stages
    still add up to the span."""
    obs_trace.TRACER.reset()
    with obs_trace.span("device.dispatch", op="toy") as sp:
        sp.begin_stages("pack")
        out = sliced_dispatch(_double, 2,
                              np.arange(9, dtype=np.int32)[:, None])
    assert out[:, 0].tolist() == [2 * i + 1 for i in range(9)]
    (rec,) = obs_trace.TRACER.snapshot()
    a = rec["attrs"]
    assert all(a[f"{s}_ms"] >= 0 for s in STAGES)
    assert sum(a[f"{s}_ms"] for s in STAGES) == pytest.approx(
        rec["dur"] * 1e3, abs=1e-6)
