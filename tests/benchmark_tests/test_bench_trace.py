"""The trace reduction: busy time is the union of the device's operation
intervals clipped to the profiled window, and a trace that cannot give it
raises instead of reporting."""

from dataclasses import dataclass, field

import pytest

from benchmark.lib import hub, schedule, trace
from benchmark.lib.spec import BENCH

RECORDED = BENCH / "testdata" / "small_tpu.xplane.pb"


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


def _device(ops, name="/device:TPU:0", line="XLA Ops"):
    return Plane(name, [Line("Steps", [Ev("step", 0, 99999)]),
                        Line(line, [Ev(n, s, d) for n, s, d in ops])])


def test_union_merges_overlaps_and_clips():
    got = trace.union([(5, 8), (0, 3), (2, 4), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 12), (20, 25)]
    assert trace.union([(0, 1)], 2, 3) == []


def test_gaps_cover_the_rest_of_the_window():
    busy = trace.union([(2, 4), (6, 7)], 0, 10)
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_busy_is_the_clipped_union():
    # overlapping ops count once; the parts outside the window not at all
    planes = [_host(), _device([("a", 0, 3000), ("b", 2000, 1000),
                                ("c", 9500, 5000), ("d", 20000, 100)])]
    out = trace.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx((3000 - 1000 + 11000 - 9500) * 1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [n for n, _ in out["device_ops"]] == ["a", "c", "b"]


def test_busy_never_exceeds_the_window():
    planes = [_host(), _device([("all", 0, 50000), ("more", 500, 20000)])]
    out = trace.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(out["window_s"])
    assert out["idle_gaps"] == []


def test_busy_is_averaged_over_the_chips_that_ran():
    planes = [_host(), _device([("x", 1000, 10000)]),
              _device([("y", 1000, 5000)], name="/device:TPU:1"),
              _device([], name="/device:TPU:2")]
    out = trace.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(7500e-9)


def test_idle_gaps_are_named_by_the_overlapping_host_event():
    host = _host(extra=[Ev("PjitFunction(run)", 4500, 300),
                        Ev("queue.flush", 4000, 2000)])
    out = trace.reduce_planes([host, _device([("a", 1000, 3000),
                                              ("b", 6000, 5000)])])
    assert out["idle_gaps"] == [["queue.flush", pytest.approx(2000e-9)]]
    out = trace.reduce_planes([_host(), _device([("a", 1000, 3000)])])
    assert out["idle_gaps"][0][0] == "unattributed"


def test_module_line_stands_in_for_missing_op_line():
    out = trace.reduce_planes([_host(), _device([("run", 3000, 1000)],
                                                line="XLA Modules")])
    assert out["busy_s"] == pytest.approx(1000e-9)


def test_no_device_plane_raises():
    with pytest.raises(trace.TraceError, match="no device plane"):
        trace.reduce_planes([_host()])


def test_no_device_event_in_the_window_raises():
    with pytest.raises(trace.TraceError, match="no device operation"):
        trace.reduce_planes([_host(), _device([("late", 50000, 10)])])
    with pytest.raises(trace.TraceError, match="no device operation"):
        trace.reduce_planes([_host(), _device([], line="XLA Ops")])


def test_missing_window_mark_raises():
    with pytest.raises(trace.TraceError, match="profiled_window"):
        trace.reduce_planes([Plane("/host:CPU", [Line("python")]),
                             _device([("a", 0, 10)])])


def test_find_xplane_wants_exactly_one(tmp_path):
    with pytest.raises(trace.TraceError):
        trace.find_xplane(tmp_path)
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    assert trace.find_xplane(tmp_path).name == "host.xplane.pb"
    (run / "other.xplane.pb").write_bytes(b"")
    with pytest.raises(trace.TraceError):
        trace.find_xplane(tmp_path)


def test_recorded_chip_trace():
    """A profile recorded on one v5e: a jitted matmul loop under the
    window mark."""
    out = trace.reduce_file(RECORDED)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
    assert len(out["idle_gaps"]) <= trace.TOP


@pytest.mark.parametrize("rate,seconds,seed", [(1.6, 30, 2**31 + 9),
                                               (1.6, 30, 3), (0.5, 10, 4),
                                               (40.0, 10, 5)])
def test_profiled_sub_window_holds_an_arrival_inside_the_window(rate, seconds,
                                                                seed):
    """The traced run's sub-window opens shortly before an arrival of the
    window, and closes inside the window."""
    traffic = {"kind": "handshake_open", "rate_per_s": rate, "warmup_s": 5,
               "message_bytes": 64, "forged_share": 0.05}
    events = schedule.build(traffic, seed, seconds)
    t0 = 100.0
    ws, we = t0 + 5, t0 + 5 + seconds
    start = hub.profiled_start(events, t0, we)
    assert ws <= start and start + hub.PROFILE_S <= we
    dues = [t0 + e.due for e in events if e.in_window]
    assert any(start + hub.PROFILE_LEAD_S == pytest.approx(d) for d in dues)
