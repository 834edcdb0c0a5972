"""The traffic generator: seeded, at the rate asked, with the same work for
every seed; and the lookup of a traffic's kind by its name."""

import json

import numpy as np
import pytest

from benchmark.lib import schedule, spec
from benchmark.lib.spec import BENCH

HANDSHAKE = {"kind": "handshake_open", "rate_per_s": 80, "warmup_s": 2,
             "message_bytes": 64, "forged_share": 0.02, "check_sample": 16}
BIG_SEED = 2**33 + 12345


def _window(events):
    return [e for e in events if e.in_window]


@pytest.mark.parametrize("seed", [0, BIG_SEED, 2**31 + 5])
def test_same_seed_same_events(seed):
    assert schedule.build(HANDSHAKE, seed, 10) == schedule.build(
        HANDSHAKE, seed, 10)


@pytest.mark.parametrize("traffic", [HANDSHAKE, dict(HANDSHAKE, forged_share=0.2)],
                         ids=["handshake", "forged-fifth"])
def test_seeds_share_the_work_in_another_order(traffic):
    a = _window(schedule.build(traffic, 1, 10))
    b = _window(schedule.build(traffic, BIG_SEED, 10))
    gaps = [np.diff([e.due for e in w]) for w in (a, b)]
    assert not np.array_equal(gaps[0], gaps[1])
    assert sorted(e.size for e in a) == sorted(e.size for e in b)
    assert sum(e.forged for e in a) == sum(e.forged for e in b)
    span = [w[-1].due - w[0].due for w in (a, b)]
    assert span[0] == pytest.approx(span[1], rel=0.05)


@pytest.mark.parametrize("rate,seconds", [(80, 10), (250, 20), (7, 30)])
def test_rate_as_asked(rate, seconds):
    traffic = dict(HANDSHAKE, rate_per_s=rate)
    events = schedule.build(traffic, 99, seconds)
    window = _window(events)
    assert len(window) == round(rate * seconds)
    assert len(events) - len(window) == round(rate * traffic["warmup_s"])
    dues = np.array([e.due for e in window])
    assert dues.min() >= traffic["warmup_s"]
    assert dues.max() <= traffic["warmup_s"] + seconds * 1.02
    # the window's requests arrive at the rate asked
    assert (len(window) - 1) / (dues.max() - dues.min()) == pytest.approx(
        rate, rel=0.1)
    gaps = np.diff(dues)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.25)


def test_poisson_gaps_sum_to_the_duration():
    gaps = schedule.quantile_gaps(50.0, 20.0, np.random.default_rng(0))
    assert len(gaps) == 1000
    assert gaps.sum() == pytest.approx(20.0, rel=0.01)


def test_each_request_is_a_new_identity():
    events = schedule.build(HANDSHAKE, 7, 10)
    assert [e.session for e in events] == [e.index for e in events]
    assert {e.size for e in events} == {HANDSHAKE["message_bytes"]}


def test_unknown_kind_is_refused():
    with pytest.raises(KeyError):
        schedule.build(dict(HANDSHAKE, kind="no_such_kind"), 1, 1)


@pytest.mark.parametrize("name", sorted(p.stem for p in (BENCH / "kinds")
                                        .glob("*.py")))
def test_every_kind_has_what_the_harness_calls(name):
    kind = spec.kind(name)
    for fn in ("draw", "sender", "send", "awaits_delivery", "summary",
               "checks"):
        assert callable(getattr(kind, fn)), fn


def test_check_sample_is_seeded_and_inside_the_window():
    events = schedule.build(HANDSHAKE, 11, 10)
    sample = schedule.check_sample(HANDSHAKE, 11, events)
    assert sample == schedule.check_sample(HANDSHAKE, 11, events)
    window = {e.index for e in _window(events)}
    assert sample <= window
    forged = {e.index for e in events if e.forged}
    assert len(sample - forged) == HANDSHAKE["check_sample"]
    assert sample & forged


def test_payload_carries_its_index_and_size():
    p = schedule.payload(BIG_SEED, 4321, 16)
    assert len(p) == 16 and p.startswith(b"4321:")
    assert schedule.payload(BIG_SEED, 4321, 16) == p
    assert schedule.payload(BIG_SEED + 1, 4321, 16) != p
    assert len(schedule.payload(1, 5, 8192)) == 8192


@pytest.mark.parametrize("path", sorted((BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_traffic_file_builds(path):
    traffic = json.loads(path.read_text())
    events = schedule.build(traffic, BIG_SEED, 5)
    assert _window(events)
    assert (BENCH / "kinds" / f"{traffic['kind']}.py").is_file()
