"""The metric readers and the lookup of a cell's files by name."""

import json
import re

import numpy as np
import pytest

from benchmark.lib import spec
from benchmark.lib.spec import BENCH, ROOT

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
CELLS = [w["name"] for w in DOC["workloads"]]
RECORDED = sorted((BENCH / "testdata").glob("summary_*.json"))
TRACED = sorted((BENCH / "testdata").glob("result_traced_*.json"))


def _summary() -> dict:
    """A run as the readers see it, small enough to check by hand."""
    return {"kind": "handshake_open", "seconds": 10.0, "setup_s": 123.5,
            "attempted": 5, "late": 0, "cpu_s": 4.06,
            "profile": {"busy_s": 3.0, "window_s": 4.0},
            "queues": {"fused.encaps_verify_sign": {"ops": 300, "flushes": 12},
                       "sig.verify": {"ops": 900, "flushes": 30}},
            "handshake_latency_s": [0.1, 0.2, 0.3, 0.4, 10.0],
            "handshakes_done_in_window": 812}


@pytest.mark.parametrize("name", METRICS)
def test_every_metric_has_a_reader(name):
    assert callable(spec.reader(name))


LATENCIES = [0.1, 0.2, 0.3, 0.4, 10.0]
#: what each reader gives on ``_summary()``; the readers of metrics that no
#: cell reports yet are tested too
EXPECTED = {
    "setup_s": 123.5,
    "handshake_p99_ms": np.percentile(LATENCIES, 99) * 1e3,
    "handshake_p50_ms": 300.0,
    "handshakes_per_s": 81.2,
    "hub_cpu_ms.handshake": 5.0,
    "flush_rows.fused": 25.0,
    "idle_share.handshake": 25.0,
}
READERS = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))


def test_expected_covers_every_reader():
    assert set(EXPECTED) == set(READERS)
    assert set(METRICS) <= set(READERS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_values(name):
    assert spec.reader(name)(_summary()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", [m for m in READERS if m != "setup_s"])
def test_reader_finds_nothing_returns_none(name):
    empty = {"kind": "handshake_open", "seconds": 10.0, "setup_s": None,
             "queues": {}, "profile": None, "cpu_s": 0.0}
    assert spec.reader(name)(empty) is None
    zero = dict(empty, profile={"busy_s": 1.0, "window_s": 4.0}, cpu_s=1.0,
                handshake_latency_s=[], handshakes_done_in_window=None,
                queues={"fused.encaps_verify_sign": {"ops": 0, "flushes": 0},
                        "sig.verify": {"ops": 0, "flushes": 0}})
    if not name.startswith("idle_share"):
        assert spec.reader(name)(zero) is None


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_readers_on_recorded_chip_runs(path):
    """An untraced knee-sweep trial recorded on one v5e (3.2 handshakes/s,
    past the knee): the readers agree with the recorded latencies and
    counts, and the trace's readers find nothing to read."""
    rec = json.loads(path.read_text())
    cell = spec.cell(rec["workload"])
    view = rec["summary"]
    lat = view["handshake_latency_s"]
    assert len(lat) == view["attempted"]
    assert spec.reader("handshake_p99_ms")(view) == pytest.approx(
        np.percentile(lat, 99) * 1e3)
    assert spec.reader("handshakes_per_s")(view) == pytest.approx(
        view["handshakes_done_in_window"] / view["seconds"])
    for m in cell.end_to_end:
        if m.name != "setup_s":
            assert spec.reader(m.name)(view) > 0, m.name
    for m in cell.per_layer:
        value = spec.reader(m.name)(view)
        assert (value is None) == (m.source == "device_trace"), m.name


@pytest.mark.parametrize("path", TRACED, ids=lambda p: p.stem)
def test_recorded_traced_result_line(path):
    """The last line of a --trace 1 run recorded on one v5e keeps to the
    benchmark's output contract."""
    line = json.loads(path.read_text())
    cell = spec.cell(path.stem.removeprefix("result_traced_"))
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    dev = line["device"]
    assert dev["platform"] == "tpu" and dev["count"] == cell.chips
    assert dev["memory_peak_bytes"] > 0
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(line["metrics"]) == {m.name for m in cell.per_layer}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert line["correct"] and all(c["value"] <= c["limit"]
                                   for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_e2e_and_a_layer(cell):
    c = spec.cell(cell)
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        moves = next(d["moves"] for d in DOC["per_layer"]
                     if d["name"] == m.name)
        assert moves in names


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")


def test_config_files_match_the_benchmark():
    for c in DOC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert data["hub"]["backend"] == "tpu"


def test_names_and_units_keep_to_the_contract():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
    for w in DOC["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()


def _sweep_line(**kw) -> dict:
    line = {"correct": True, "late": 0, "rate_per_s": 2.0,
            "knee": {"handshake_p99_ms": 1500.0, "handshakes_per_s": 1.9}}
    for k, v in kw.items():
        if k in line["knee"]:
            line["knee"][k] = v
        else:
            line[k] = v
    return line


@pytest.mark.parametrize("kw,ok", [
    ({}, True),
    ({"correct": False}, False),
    ({"late": 1}, False),
    ({"handshake_p99_ms": 2000.5}, False),
    ({"handshakes_per_s": 1.7}, False),
    ({"handshake_p99_ms": None}, False),
], ids=["keeps-up", "incorrect", "late", "slo", "backlog", "no-latency"])
def test_knee_sweep_criterion(kw, ok):
    from benchmark import readings

    assert readings.keeps_up(_sweep_line(**kw)) is ok
