"""The program's spans of a window (``lib/spans.py``): the readers of the
device flushes' stages, the window's recorder, and idle gaps named after
the worker's stage annotations."""

from dataclasses import dataclass, field

import pytest

from benchmark.lib import spans, trace
from benchmark.lib.compiles import LEDGER


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


def _host(lo=1000, hi=11000, extra=()):
    return Plane("/host:CPU", [Line("python", [
        Ev(trace.WINDOW_MARK, lo, hi - lo), *extra])])


def _device(ops):
    return Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev(n, s, d) for n, s, d in ops])])


FUSED = "ML-KEM-768+ML-DSA-65.encaps_verify_sign"
VERIFY = "ML-DSA-65.verify"


def _dispatch(op, route="device", **ms):
    attrs = {"op": op, "n": 1, "route": route}
    attrs.update({f"{k}_ms": v for k, v in ms.items()})
    return {"name": "device.dispatch", "dur": 0.0, "attrs": attrs}


def _run(**kw) -> dict:
    """Three fused dispatches and one verify, two accepted responses and
    one refused, and the loop's view of the fused flushes."""
    records = [
        _dispatch(FUSED, queued=10, pack=20, run=300, unpack=30, cpu=40),
        _dispatch(FUSED, queued=20, pack=10, run=320, unpack=20, cpu=30),
        _dispatch(FUSED, "probe", queued=30, pack=30, run=310, unpack=10,
                  cpu=35),
        _dispatch(FUSED, "warmup", cpu=1000),       # a compile: not counted
        _dispatch(VERIFY, queued=5, pack=1, run=30, unpack=1, cpu=15),
        {"name": "fallback.dispatch", "dur": 1.0,
         "attrs": {"op": FUSED, "route": "fallback"}},
        {"name": "handshake.respond", "dur": 0.4,
         "attrs": {"outcome": "accepted"}},
        {"name": "handshake.respond", "dur": 0.5,
         "attrs": {"outcome": "accepted"}},
        {"name": "handshake.respond", "dur": 0.1,
         "attrs": {"outcome": "rejected"}},
        {"name": "queue.flush", "dur": 0.350, "attrs": {
            "op": FUSED, "waited_ms": 5.0}},
        {"name": "queue.flush", "dur": 0.370, "attrs": {
            "op": FUSED, "waited_ms": 5.0}},
        {"name": "queue.flush", "dur": 0.040, "attrs": {
            "op": VERIFY, "waited_ms": 5.0}},
    ]
    out = {"spans": records, "handshakes_done_in_window": 2}
    out.update(kw)
    return out


EXPECTED = {"respond_ms.handshake": 450.0,
            "queue_wait_ms.fused": 20.0,
            "device_ms.fused": 310.0,
            "host_ms.fused": 40.0,
            "flush_cpu_ms.handshake": (40 + 30 + 35 + 15) / 2}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_values(name):
    assert spans.READERS[name](_run()) == pytest.approx(EXPECTED[name])


def _parent_run() -> dict:
    """What a program without the stages and outcomes records."""
    run = _run()
    for s in run["spans"]:
        s["attrs"] = {k: v for k, v in s["attrs"].items()
                      if k in ("op", "n", "route", "waited_ms")}
    return run


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("run", [
    {}, {"spans": []}, {"spans": [], "handshakes_done_in_window": 3},
    _parent_run()], ids=["no-spans", "empty", "empty-done", "parent"])
def test_reader_finds_nothing_returns_none(name, run):
    assert spans.READERS[name](run) is None


def test_flush_cpu_without_a_completed_handshake_is_none():
    assert spans.flush_cpu_ms(_run(handshakes_done_in_window=0)) is None
    assert spans.flush_cpu_ms(_run(handshakes_done_in_window=None)) is None


def test_flush_account_sets_the_stages_beside_the_loop():
    got = spans.flush_account(_run())
    assert got["stages_p50_ms"] == pytest.approx(370.0)
    assert got["loop_p50_ms"] == pytest.approx(365.0)
    assert spans.flush_account(_parent_run())["stages_p50_ms"] is None


def test_recorder_keeps_only_the_window():
    rec = spans.Recorder()
    span = {"name": "device.dispatch", "dur": 0.1, "attrs": {"n": 1},
            "trace_id": "t", "span_id": "s", "parent_id": None, "t0": 0.0,
            "thread": "w", "node": ""}
    was = LEDGER.window_open
    try:
        LEDGER.window_open = False
        rec(span)
        LEDGER.window_open = True
        rec(span)
        rec(dict(span, name="queue.flush"))
    finally:
        LEDGER.window_open = was
    assert [r["name"] for r in rec.take()] == ["device.dispatch",
                                               "queue.flush"]
    assert set(rec.records) == set() and rec.take() == []


UNPACK = f"qrp2p.host.unpack {FUSED}"


def test_gap_covered_equally_is_named_by_the_stage():
    """A device-idle gap that ``np.asarray(jax.Array)`` and the worker's
    unpack stage both cover is named after the stage."""
    extra = [Ev("np.asarray(jax.Array)", 3000, 5000), Ev(UNPACK, 2500, 6000)]
    planes = [_host(extra=extra), _device([("a", 1000, 2000),
                                           ("b", 8000, 3000)])]
    out = trace.reduce_planes(planes)
    assert out["idle_gaps"][0] == [UNPACK, pytest.approx(5e-6)]
    # the same with the stage annotation on a thread of its own
    planes = [Plane("/host:CPU", [
        Line("python", [Ev(trace.WINDOW_MARK, 1000, 10000),
                        Ev("np.asarray(jax.Array)", 3000, 5000)]),
        Line("worker", [Ev(UNPACK, 3000, 5000)])]),
        _device([("a", 1000, 2000), ("b", 8000, 3000)])]
    assert trace.reduce_planes(planes)["idle_gaps"][0][0] == UNPACK


def test_stage_gaps_say_which_stage_was_open():
    extra = [Ev("np.asarray(jax.Array)", 3000, 5000), Ev(UNPACK, 2500, 6000),
             Ev("PjitFunction(run)", 11500, 100)]
    planes = [_host(lo=1000, hi=20000, extra=extra),
              _device([("a", 1000, 2000), ("b", 8000, 3000)])]
    got = spans.stage_gaps(planes, min_s=1e-6)
    assert got == [
        {"ms": pytest.approx(5e-3), "label": UNPACK, "stage": UNPACK},
        {"ms": pytest.approx(9e-3), "label": "PjitFunction(run)",
         "stage": None}]
    assert spans.stage_gaps(planes, min_s=6e-6) == got[1:]


def test_recorder_on_a_cpu_hub_run():
    """A short run of the cell's traffic through a hub on the CPU backend
    (the native core, small flushes): the window's spans carry the
    responder's outcome and the dispatches' stages.  That hub has no fused
    queue and runs no device program, so each dispatch is all ``pack``."""
    import asyncio
    import dataclasses
    import json
    import time

    import jax

    from benchmark.lib import harness, hub, spec

    cell = spec.cell("d1-handshake-open")
    config = json.loads(json.dumps(cell.config))
    config["hub"].update(backend="cpu", batch_floor=1, max_batch=64)
    cell = dataclasses.replace(cell, config=config, traffic=dict(
        cell.traffic, rate_per_s=25, warmup_s=0.5, client_procs=2,
        check_sample=0, forged_share=0.1, linger_s=0.3))
    rec = spans.Recorder().install()
    was = hub.GIVE_UP_S
    hub.GIVE_UP_S = 4.0
    try:
        state = asyncio.run(harness.run_cell(
            cell, lambda runs: None if runs else (2**33 + 5, cell.traffic,
                                                  None),
            2.0, False, time.monotonic(), jax.devices()[:1]))
    finally:
        hub.GIVE_UP_S = was
        from quantum_resistant_p2p_tpu.obs import trace as obs_trace

        obs_trace.TRACER._listeners.remove(rec)
    view = harness.summary(state["runs"][0])
    view["spans"] = rec.take()
    names = {s["name"] for s in view["spans"]}
    assert {"handshake.respond", "queue.flush", "device.dispatch"} <= names
    outcomes = {s["attrs"]["outcome"] for s in view["spans"]
                if s["name"] == "handshake.respond"}
    assert outcomes == {"accepted", "rejected"}
    assert spans.respond_ms(view) > 0
    assert spans.flush_cpu_ms(view) > 0
    assert spans.queue_wait_ms(view) is None
    dispatches = spans._dispatches(view)
    assert dispatches
    for a in dispatches:
        assert a["queued_ms"] > 0 and a["pack_ms"] > 0 and a["cpu_ms"] >= 0
        assert "run_ms" not in a


def test_stage_gaps_of_a_recorded_chip_trace():
    """On a profile recorded on one v5e (a program without stage
    annotations): every gap has its reduction's name and no stage."""
    from jax.profiler import ProfileData

    from benchmark.lib.spec import BENCH

    path = BENCH / "testdata" / "small_tpu.xplane.pb"
    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    got = spans.stage_gaps(planes, min_s=0.0)
    named = trace.reduce_planes(planes)["idle_gaps"]
    assert got and all(g["stage"] is None for g in got)
    longest = sorted(got, key=lambda g: -g["ms"])[:len(named)]
    assert [g["label"] for g in longest] == [n for n, _ in named]
    assert [g["ms"] for g in longest] == pytest.approx(
        [1e3 * s for _, s in named])
